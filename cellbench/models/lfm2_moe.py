"""Builds the program's serving objects for an LFM2-MoE configuration
(a gated short convolution in three layers of four, QK-normed rotary
GQA in the fourth; a dense SwiGLU FFN in the leading layers, sigmoid-
routed SwiGLU experts after; the head tied to the table), through the
entry points a user calls: `Generator(...).serving_decoder()` ->
`ServeServer`, with architecture arguments only. The weights come from
the benchmark (`cellbench.reference.lfm2_moe.make_params`)."""
from mxnet_tpu.generation import Generator
from mxnet_tpu.serve import ServeServer

from cellbench.models.opt import served_logits  # noqa: F401 — the drive's
from cellbench.reference import lfm2_moe as ref

_KIND = {"conv": "shortconv", "attention": "attention",
         "experts": "experts", "mlp": "mlp"}


def generator_args(cfg):
    """The architecture as `Generator` takes it, from the published
    keys and the configuration's `assumed` sizes: the pre-norm block's
    two sublayers are two entries of `layer_kinds`."""
    s = ref.sizes(cfg)
    args = dict(
        layer_kinds=[_KIND[k] for k in s["kinds"]],
        num_heads=s["heads"], dim=s["dim"], ffn_hidden=s["ffn"],
        num_kv_heads=s["kv_heads"], head_dim=s["head"], qk_norm=True,
        pos_encoding="rope", rope_base=s["theta"],
        shortconv_kernel=s["taps"], norm="rms", norm_eps=s["eps"],
        ffn="gated_silu", use_bias=False, tie_embeddings=True)
    # the expert layers' sizes are given where the stack has one (the
    # program refuses sizes that no layer reads)
    if "experts" in s["kinds"]:
        args.update(
            num_experts=s["experts"], experts_per_token=s["top_k"],
            expert_hidden=s["expert_ffn"], norm_topk_prob=s["renorm"],
            norm_topk_eps=ref._RENORM_EPS, expert_scoring="sigmoid",
            routed_scaling_factor=s["scale"])
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
