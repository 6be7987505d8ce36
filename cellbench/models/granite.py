"""Builds the program's serving objects for a Granite 4.0-H
configuration (Mamba-2 mixers beside attention layers), through the
entry points a user calls: `Generator(...).serving_decoder()` ->
`ServeServer`, with architecture arguments only. The weights come from
the benchmark (`cellbench.reference.granite.make_params`)."""
from mxnet_tpu.generation import Generator
from mxnet_tpu.serve import ServeServer

from cellbench.models.opt import served_logits  # noqa: F401 — the drive's
from cellbench.reference import granite as ref


def generator_args(cfg):
    """The architecture as `Generator` takes it, from the published
    keys alone."""
    s = ref.sizes(cfg)
    return dict(
        num_layers=s["layers"], num_heads=s["heads"], dim=s["dim"],
        ffn_hidden=s["ffn"], num_kv_heads=s["kv_heads"],
        block_type=["mamba2" if k == "mamba" else "attention"
                    for k in s["kinds"]],
        mamba2=dict(num_heads=s["m_heads"], head_dim=s["m_head"],
                    d_state=s["m_state"], d_conv=s["m_conv"],
                    chunk=int(cfg["mamba_chunk_size"])),
        norm="rms", norm_eps=s["eps"], ffn="gated_silu",
        pos_encoding="none", use_bias=False, tie_embeddings=True,
        embedding_multiplier=s["emb_mult"],
        residual_multiplier=s["res_mult"],
        logits_scaling=s["logit_div"], attention_scale=s["att_mult"])


def build_server(cfg, traffic, params, low=False):
    """(generator, decoder, server) serving `params` with the pool the
    traffic file states: `slots` rows of `max_len` positions. `low`
    switches on the program's own lower-precision path (int8 weights,
    the tied table among them, and an int8 key/value cache): the
    control, never a benchmark run."""
    s = ref.sizes(cfg)
    max_len = int(traffic["max_len"])
    if max_len > s["positions"]:
        raise ValueError("traffic max_len %d exceeds the model's %d "
                         "positions" % (max_len, s["positions"]))
    gen = Generator(params, s["vocab"], max_len,
                    batch_size=int(traffic["slots"]),
                    dtype=cfg["compute_dtype"],
                    quantize="int8" if low else None, quantize_kv=low,
                    **generator_args(cfg))
    decoder = gen.serving_decoder(queue_cap=int(traffic["queue_cap"]))
    return gen, decoder, ServeServer(decoder)
