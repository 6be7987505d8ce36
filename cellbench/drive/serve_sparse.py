"""Traffic kind `serve_sparse`: kind `serve_mixed` (its window, its
callers, its checks, its control and its readings, run by its own
`run`, nothing copied) for a pool whose attention layers select the
keys each query attends: two more of the decoder's counters are read
over the window beside the expert layers' and the chunks',
`dsa_keys_visible` and `dsa_keys_selected` (what a step's queries
could see and what they attended, summed over rows, layers and steps).

It is a kind of its own for that alone: `serve_mixed` reads the
counters its `_STATS` names into `stats.*`, the tuple is a constant of
that file, and a benchmark file that exists is not a `model_config`
PR's to edit. A program without the two counters reads 0, and a metric
over them is left out.
"""
from cellbench.drive import serve_mixed

_STATS = serve_mixed._STATS + ("dsa_keys_visible", "dsa_keys_selected")


def run(ctx):
    """One run of a serve_sparse cell: `serve_mixed.run` with the two
    counters among those it snapshots and hands on."""
    before = serve_mixed._STATS
    serve_mixed._STATS = _STATS
    try:
        return serve_mixed.run(ctx)
    finally:
        serve_mixed._STATS = before
