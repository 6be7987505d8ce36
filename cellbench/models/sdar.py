"""Builds the program's serving objects for an SDAR configuration
(routed experts in every layer, generation by diffusion over blocks),
through the entry points a user calls: `Generator(...,
diffusion=...).serving_decoder()` -> `ServeServer`, with architecture
arguments only. The weights come from the benchmark
(`cellbench.reference.sdar.make_params`)."""
import numpy as np

from mxnet_tpu.generation import Generator
from mxnet_tpu.serve import ServeServer

from cellbench.reference import sdar as ref


def generator_args(cfg, traffic):
    """The architecture as `Generator` takes it, from the published
    keys, the configuration's `assumed` sampler sizes and the traffic
    file's schedule (`denoising_steps`, `remasking`)."""
    s = ref.sizes(cfg)
    return dict(
        num_layers=s["layers"], num_heads=s["heads"], dim=s["dim"],
        num_kv_heads=s["kv_heads"], head_dim=s["head"], qk_norm=True,
        pos_encoding="rope", rope_base=s["theta"],
        num_experts=s["experts"], experts_per_token=s["top_k"],
        expert_hidden=s["expert_ffn"], norm_topk_prob=s["renorm"],
        norm="rms", norm_eps=s["eps"], ffn="gated_silu",
        use_bias=False, tie_embeddings=False,
        diffusion=dict(block_length=s["block"], mask_id=s["mask_id"],
                       steps=int(traffic["denoising_steps"]),
                       remasking=traffic["remasking"]))


def build_server(cfg, traffic, params):
    """(generator, decoder, server) serving `params` with the pool the
    traffic file states: `slots` rows of `max_len` positions. The
    program has no lower-precision path for its expert weights, so the
    control is the reference's int8 twin (the drive's), not a switch
    here."""
    s = ref.sizes(cfg)
    max_len = int(traffic["max_len"])
    if max_len > s["positions"]:
        raise ValueError("traffic max_len %d exceeds the model's %d "
                         "positions" % (max_len, s["positions"]))
    gen = Generator(params, s["vocab"], max_len,
                    batch_size=int(traffic["slots"]),
                    dtype=cfg["compute_dtype"],
                    **generator_args(cfg, traffic))
    decoder = gen.serving_decoder(queue_cap=int(traffic["queue_cap"]))
    return gen, decoder, ServeServer(decoder)


def served_logits(decoder, prompts, max_new):
    """Serve `prompts` through the decoder the window drove, by its own
    admission and block step, and keep what the tokens were picked
    from: (rows, logits), a full id row and the float32 logits
    (max_new, V) for each prompt, each served token's from the
    denoising forward that unmasked it. The program hands every
    denoising forward's logits to `decoder.on_block_logits`; that is
    where they are read, so nothing of the path is rebuilt."""
    seen = {}

    def keep(req, start, _ids, masked, logits):
        seen.setdefault(id(req), []).append(
            (start, masked, np.array(logits, np.float32)))

    decoder.on_block_logits = keep
    try:
        futs = [decoder.submit(p, max_new) for p in prompts]
        rows = [np.asarray(f.result(timeout=600)) for f in futs]
    finally:
        decoder.on_block_logits = None
    out = []
    for p, f in zip(prompts, futs):
        got = np.zeros((max_new, seen[id(f)][0][2].shape[-1]),
                       np.float32)
        for start, masked, logits in seen[id(f)]:
            for r in np.flatnonzero(masked):
                at = start + r - len(p)
                # later forwards overwrite: the forward that unmasked a
                # position is the last one that saw it masked
                if 0 <= at < max_new:
                    got[at] = logits[r]
        out.append(got)
    return rows, out
