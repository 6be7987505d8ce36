"""Builds the program's serving objects for a Cohere2-MoE configuration
(a parallel block on one bias-free LayerNorm; sliding-window rotary
attention in three layers of four over circular cache rows, position-
free full attention in the fourth over `max_len` rows; sigmoid-routed
gated-SiLU experts of which this chip holds a share, beside averaged
shared experts; the head tied to the table), through the entry points
a user calls: `Generator(...).serving_decoder()` -> `ServeServer`, with
architecture arguments only. The weights come from the benchmark
(`cellbench.reference.cohere2_moe.make_params`, which also states the
two layouts the program's differ in: rotary pairs, and the shared
experts side by side with 1/m folded into their downs)."""
from mxnet_tpu import config
from mxnet_tpu.generation import Generator
from mxnet_tpu.serve import ServeServer

from cellbench.models.opt import served_logits  # noqa: F401 — the drive's
from cellbench.reference import cohere2_moe as ref


def generator_args(cfg):
    """The architecture as `Generator` takes it, from the published
    keys and the configuration's statement of the chip's share. A
    sliding layer's circular buffer is sized by the program (its
    window and the chunk a prompt is fed by)."""
    s = ref.sizes(cfg)
    sliding = dict(window=s["window"], cache="rolling", pos="rope")
    full = dict(window=0, cache="full", pos="none")
    return dict(
        num_layers=s["layers"], num_heads=s["heads"], dim=s["dim"],
        num_kv_heads=s["kv_heads"], head_dim=s["head"],
        pos_encoding="rope", rope_base=s["theta"],
        attention_layers=[sliding if t == "sliding" else full
                          for t in s["types"]],
        parallel_block=True, norm="layer_gain", norm_eps=s["eps"],
        ffn="gated_silu", use_bias=False, tie_embeddings=True,
        logits_scaling=1.0 / s["logit_scale"],
        num_experts=s["experts"], experts_per_token=s["top_k"],
        expert_hidden=s["expert_ffn"], norm_topk_prob=s["renorm"],
        expert_scoring="sigmoid",
        shared_expert_hidden=s["shared"] * s["expert_ffn"],
        experts_held=(s["first"], s["held"]))


def build_server(cfg, traffic, params, low=False):
    """(generator, decoder, server) serving `params` with the pool the
    traffic file states: `slots` rows of `max_len` positions, prompts
    fed `prefill_chunk` tokens a forward (the program's own
    MXNET_PREFILL_CHUNK, set here before the generator sizes its
    circular buffers by it). The program has no lower-precision path
    for its expert weights, so the control is the reference's int8
    twin (the drive's), not a switch here."""
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
