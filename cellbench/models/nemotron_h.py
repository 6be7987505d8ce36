"""Builds the program's serving objects for a Nemotron-H configuration
(one sublayer a layer: Mamba-2 mixers in groups, position-free GQA
attention, LatentMoE expert layers of which this chip holds a share),
through the entry points a user calls:
`Generator(...).serving_decoder()` -> `ServeServer`, with architecture
arguments only. The weights come from the benchmark
(`cellbench.reference.nemotron_h.make_params`)."""
from mxnet_tpu.generation import Generator
from mxnet_tpu.serve import ServeServer

from cellbench.models.opt import served_logits  # noqa: F401 — the drive's
from cellbench.reference import nemotron_h as ref

_KIND = {"mamba": "mamba2", "attention": "attention",
         "experts": "experts", "mlp": "mlp"}


def generator_args(cfg):
    """The architecture as `Generator` takes it, from the published
    keys and the configuration's statement of the chip's share."""
    s = ref.sizes(cfg)
    args = dict(
        layer_kinds=[_KIND[k] for k in s["kinds"]],
        num_heads=s["heads"], dim=s["dim"], ffn_hidden=s["ffn"],
        num_kv_heads=s["kv_heads"], head_dim=s["head"],
        norm="rms", norm_eps=s["eps"], ffn="relu2",
        pos_encoding="none", use_bias=False, tie_embeddings=False)
    # the sizes of a kind of layer are given where the pattern has one
    # (the program refuses sizes that no layer reads)
    if "mamba" in s["kinds"]:
        args["mamba2"] = dict(
            num_heads=s["m_heads"], head_dim=s["m_head"],
            d_state=s["m_state"], d_conv=s["m_conv"],
            chunk=s["m_chunk"], n_groups=s["m_groups"])
    if "experts" in s["kinds"]:
        args.update(
            num_experts=s["experts"], experts_per_token=s["top_k"],
            expert_hidden=s["expert_ffn"], norm_topk_prob=s["renorm"],
            expert_scoring="sigmoid", routed_scaling_factor=s["scale"],
            expert_latent=s["latent"], shared_expert_hidden=s["shared"],
            experts_held=(s["first"], s["held"]))
    return args


def build_server(cfg, traffic, params, low=False):
    """(generator, decoder, server) serving `params` with the pool the
    traffic file states: `slots` rows of `max_len` positions. The
    program has no lower-precision path for its expert weights, so the
    control is the reference's int8 twin (the drive's), not a switch
    here."""
    if low:
        raise ValueError("the program has no int8 path for expert "
                         "weights: the control is the reference's twin")
    s = ref.sizes(cfg)
    max_len = int(traffic["max_len"])
    if max_len > s["positions"]:
        raise ValueError("traffic max_len %d exceeds the model's %d "
                         "positions" % (max_len, s["positions"]))
    gen = Generator(params, s["vocab"], max_len,
                    batch_size=int(traffic["slots"]),
                    dtype=cfg["compute_dtype"], **generator_args(cfg))
    decoder = gen.serving_decoder(queue_cap=int(traffic["queue_cap"]))
    return gen, decoder, ServeServer(decoder)
