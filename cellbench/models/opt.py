"""Builds the program's serving objects for an OPT-family
configuration, through the entry points a user calls:
`Generator(...).serving_decoder()` -> `ServeServer`. The weights come
from the benchmark (`cellbench.reference.opt.make_params`)."""
import numpy as np

from mxnet_tpu.generation import Generator
from mxnet_tpu.serve import ServeServer
from mxnet_tpu.serve import decode

from cellbench.reference import opt as ref


def build_server(cfg, traffic, params, low=False):
    """(generator, decoder, server) serving `params` with the pool the
    traffic file states: `slots` rows of `max_len` positions. `low`
    switches on the program's own lower-precision path (int8 weights
    and an int8 key/value cache): the control, never a benchmark run."""
    s = ref.sizes(cfg)
    max_len = int(traffic["max_len"])
    if max_len > s["positions"]:
        raise ValueError("traffic max_len %d exceeds the model's %d "
                         "positions" % (max_len, s["positions"]))
    gen = Generator(params, s["vocab"], max_len,
                    num_layers=s["layers"], num_heads=s["heads"],
                    dim=s["dim"], ffn_hidden=s["ffn"],
                    batch_size=int(traffic["slots"]),
                    dtype=cfg["compute_dtype"],
                    quantize="int8" if low else None, quantize_kv=low)
    decoder = gen.serving_decoder(queue_cap=int(traffic["queue_cap"]))
    return gen, decoder, ServeServer(decoder)


def served_logits(decoder, prompts, max_new):
    """Serve `prompts` through the decoder the window drove, by its own
    admission and decode step, and keep what the tokens were picked
    from: (rows, logits), a full id row and the float32 logits
    (max_new, V) behind its served tokens for each prompt. The program
    hands every request its logits row in `DecodeFuture._pick`; that
    is where they are read, so nothing of the path is rebuilt."""
    seen = {}
    pick = decode.DecodeFuture._pick

    def keeping(self, row_logits):
        seen.setdefault(id(self), []).append(
            np.array(row_logits, np.float32))
        return pick(self, row_logits)

    decode.DecodeFuture._pick = keeping
    try:
        futs = [decoder.submit(p, max_new) for p in prompts]
        rows = [np.asarray(f.result(timeout=600)) for f in futs]
    finally:
        decode.DecodeFuture._pick = pick
    return rows, [np.stack(seen[id(f)]) for f in futs]
