"""The controls and the planted faults, kept at a size a test run can
hold. On the chip the controls ran at the cells' own sizes (PERF.md
gives those readings); here the same code runs at toy size: what
stands in the program's place in the next precision down has to miss
a limit that the program meets, and a timed path broken underneath has
to come out not correct, by the number that is there to catch it."""
import jax.numpy as jnp
import mxnet_tpu as mx

from cellbench import run
from cellbench.tests import toy


def _failed(res):
    return {c["name"] for c in res["checks"] if not c["ok"]}


def _train(**kw):
    return run.run_cell(toy.RESNET, toy.IMAGES, 1, 1.0, **kw)


def test_train_program_meets_what_the_fp8_reference_misses():
    good = _train()
    assert good["correct"] is True and not _failed(good)
    low = _train(control=True)
    assert low["correct"] is False
    assert "update_total_gap" in _failed(low)
    # the program's own numbers are printed beside it, judged by none
    beside = {c["name"]: c for c in low["checks"]}
    assert beside["program.update_total_gap"]["limit"] is None
    assert beside["program.update_total_gap"]["value"] < \
        toy.IMAGES["limits"]["update_total_gap"]


def test_a_step_that_changes_nothing_is_not_correct():
    def freeze(step):
        fit = step.fit
        step.fit = lambda *a, **kw: fit(*a, **dict(kw, lr=0.0))

    assert "update_total_gap" in _failed(_train(program_hook=freeze))


def test_one_small_leaf_left_unchanged_is_not_correct():
    """The classifier's weight moves the norms over all leaves by a
    fraction of their limits; by the leaf it falls short by all of
    its norm."""
    def freeze_leaf(step):
        fit = step.fit

        def patched(feed, **kw):
            kept = jnp.copy(kw["arg_params"]["fc1_weight"])
            end = kw["epoch_end_callback"]

            def epoch_end(epoch, state):
                params = dict(state[0], fc1_weight=kept)
                return end(epoch, (params,) + tuple(state[1:]))

            return fit(feed, **dict(kw, epoch_end_callback=epoch_end))

        step.fit = patched

    bad = _train(program_hook=freeze_leaf)
    assert bad["correct"] is False
    assert _failed(bad) == {"grad_leaf_deficit", "update_leaf_deficit"}


class _HalfTheBatch:
    """The feed with the second half of every batch's rows replaced
    by the first half: the step trains on half the batch."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __iter__(self):
        return self

    def __next__(self):
        batch = self._inner.next()
        for arrays in (batch.data, batch.label):
            for k, a in enumerate(arrays):
                x = a.asnumpy()
                x[len(x) // 2:] = x[:len(x) // 2]
                arrays[k] = mx.nd.array(x)
        return batch

    next = __next__


def test_a_part_of_the_batch_left_out_is_not_correct():
    def half(step):
        fit = step.fit
        step.fit = lambda feed, **kw: fit(_HalfTheBatch(feed), **kw)

    bad = _train(program_hook=half)
    assert bad["correct"] is False
    assert {"loss_gap", "grad_total_gap"} <= _failed(bad)


def test_serve_program_s_own_int8_path_is_not_correct():
    """The control for serving is the program itself with its int8
    weights and int8 cache switched on. Its greedy tokens are as good
    as bfloat16's (the gaps pass); what gives it away is that its
    error lies along the step from the reference to the reference's
    int8 twin."""
    good = run.run_cell(toy.OPT, toy.DECK, 3, 1.5)
    assert good["correct"] is True and not _failed(good)
    low = run.run_cell(toy.OPT, toy.DECK, 3, 1.5, control=True)
    assert low["correct"] is False
    assert _failed(low) == {"int8_share"}
    share = {c["name"]: c["value"] for c in low["checks"]}["int8_share"]
    assert share > 0.8
