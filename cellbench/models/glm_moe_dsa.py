"""Builds the program's serving objects for a GLM-MoE-DSA
configuration (multi-head latent attention whose keys a learned
indexer selects; a dense SwiGLU FFN in the leading layers, sigmoid-
routed SwiGLU experts of which this chip holds a share beside a shared
expert after; an untied head), through the entry points a user calls:
`Generator(...).serving_decoder()` -> `ServeServer`, with architecture
arguments only. The weights come from the benchmark
(`cellbench.reference.glm_moe_dsa.make_params`, which also states the
one layout the program's differs in: rotary pairs)."""
from mxnet_tpu import config
from mxnet_tpu.generation import Generator
from mxnet_tpu.serve import ServeServer

from cellbench.models.opt import served_logits  # noqa: F401 — the drive's
from cellbench.reference import glm_moe_dsa as ref


def generator_args(cfg):
    """The architecture as `Generator` takes it, from the published
    keys and the configuration's statement of the chip's share: the
    pre-norm block's two sublayers are two entries of `layer_kinds`,
    the mixer's sizes ONE dict."""
    s = ref.sizes(cfg)
    args = dict(
        layer_kinds=list(s["kinds"]), num_heads=s["heads"],
        dim=s["dim"], ffn_hidden=s["ffn"],
        mla=dict(q_lora_rank=s["q_rank"], kv_lora_rank=s["kv_rank"],
                 qk_nope_head_dim=s["nope"], qk_rope_head_dim=s["rope"],
                 v_head_dim=s["v_head"], index_heads=s["index_heads"],
                 index_head_dim=s["index_head"],
                 index_topk=s["index_topk"]),
        pos_encoding="rope", rope_base=s["theta"], norm="rms",
        norm_eps=s["eps"], ffn="gated_silu", use_bias=False)
    # the expert layers' sizes are given where the stack has one (the
    # program refuses sizes that no layer reads)
    if "experts" in s["kinds"]:
        args.update(
            num_experts=s["experts"], experts_per_token=s["top_k"],
            expert_hidden=s["expert_ffn"], norm_topk_prob=s["renorm"],
            expert_scoring="sigmoid", routed_scaling_factor=s["scale"],
            shared_expert_hidden=s["expert_ffn"],
            experts_held=(s["first"], s["held"]))
    return args


def build_server(cfg, traffic, params, low=False):
    """(generator, decoder, server) serving `params` with the pool the
    traffic file states: `slots` rows of `max_len` positions, prompts
    fed `prefill_chunk` tokens a forward (the program's own
    MXNET_PREFILL_CHUNK). The program has no lower-precision path for
    its expert weights, so the control is the reference's int8 twin
    (the drive's), not a switch here."""
    if low:
        raise ValueError("the program has no int8 path for expert "
                         "weights: the control is the reference's twin")
    s = ref.sizes(cfg)
    max_len = int(traffic["max_len"])
    if max_len > s["positions"]:
        raise ValueError("traffic max_len %d exceeds the model's %d "
                         "positions" % (max_len, s["positions"]))
    config.set_override("MXNET_PREFILL_CHUNK",
                        int(traffic["prefill_chunk"]))
    gen = Generator(params, s["vocab"], max_len,
                    batch_size=int(traffic["slots"]),
                    dtype=cfg["compute_dtype"], **generator_args(cfg))
    decoder = gen.serving_decoder(queue_cap=int(traffic["queue_cap"]))
    return gen, decoder, ServeServer(decoder)
