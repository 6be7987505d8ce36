"""Routed experts as deployed (top-k of float32 softmax scores, nothing
dropped, only the routed pairs computed) and generation by diffusion
over blocks (the block mask, a step that yields 0 to L tokens a row),
through the op, `Generator.generate` and `ContinuousDecoder`, against
the benchmark's plain float32 reference at toy widths with seeded
weights."""
import json
import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cellbench.models import sdar as model
from cellbench.reference import sdar as ref
from mxnet_tpu.generation import (REMASKING, Generator, canon_diffusion,
                                  unmask_choice)
from mxnet_tpu.ops.attention import cached_attention
from mxnet_tpu.parallel.moe import dense_moe, routed_experts

pytestmark = pytest.mark.serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, T, SEED, L, MASK = 97, 48, 11, 4, 96
with open(os.path.join(ROOT, "cellbench", "configs",
                       "sdar-30b-a3b-chat.json")) as _f:
    TOY = json.load(_f)
TOY.update(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
           head_dim=16, num_experts=8, num_experts_per_tok=2,
           moe_intermediate_size=16, vocab_size=V, num_hidden_layers=2,
           max_position_embeddings=64, initializer_range=0.3,
           compute_dtype="float32")
TOY["assumed"] = dict(TOY["assumed"], mask_token_id=MASK)


@pytest.fixture(scope="module")
def params():
    return ref.make_params(TOY, SEED, "float32")


def _gen(params, batch_size, steps=2, rule="sequential", **over):
    args = model.generator_args(
        TOY, {"denoising_steps": steps, "remasking": rule})
    args["diffusion"].update(over.pop("diffusion", {}))
    return Generator(params, V, T, batch_size=batch_size,
                     **dict(args, **over))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, MASK, n, dtype=np.int64) for n in lengths]


# -- the expert layer --------------------------------------------------------

def _expert_inputs(E, k, seed=0, N=24, D=32, H=16, act="gated_silu"):
    rng = np.random.default_rng(seed)
    f32 = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
    wide = 2 * H if act == "gated_silu" else H
    return f32(N, D) / 0.3, f32(D, E), f32(E, D, wide), f32(E, H, D)


def _reference_experts(x, g, w1, w2, k):
    s = dict(top_k=k, renorm=True, expert_ffn=w2.shape[1])
    with jax.default_matmul_precision("highest"):
        return ref._experts(x, {"gate_weight": g, "experts_w1_weight": w1,
                                "experts_w2_weight": w2}, s)


@pytest.mark.parametrize("k,E", [(1, 8), (2, 8), (8, 16)])
def test_routed_experts_match_the_reference(k, E):
    x, g, w1, w2 = _expert_inputs(E, k)
    y, stats = routed_experts(x, g, w1, w2, top_k=k, act="gated_silu",
                              renormalize=True)
    np.testing.assert_allclose(y, _reference_experts(x, g, w1, w2, k),
                               rtol=2e-5, atol=2e-5)
    pairs, hit, largest = np.asarray(stats)
    assert pairs == 24 * k and 1 <= hit <= E
    assert largest >= -(-pairs // E)       # the mean, at least


def test_every_token_to_one_expert_and_nothing_is_dropped():
    """A router that sends all 24 tokens to expert 3: one ragged batch
    of 24 rows, seven empty ones, every token served (a Switch capacity
    of ceil(24 * 1.25 / 8) = 4 would have zeroed 20 of them)."""
    x, g, w1, w2 = _expert_inputs(8, 1)
    g = jnp.zeros_like(g).at[:, 3].set(jnp.sign(x.mean(0)) * 4.0)
    x = jnp.abs(x) * jnp.sign(x.mean(0))       # every score of 3 wins
    y, stats = routed_experts(x, g, w1, w2, top_k=1, act="gated_silu",
                              renormalize=True)
    assert np.asarray(stats).tolist() == [24, 1, 24]
    f = w2.shape[1]
    gu = x @ w1[3]
    want = (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ w2[3]
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    assert float(jnp.abs(y).min(axis=1).max()) > 0   # no zeroed row


def test_a_tie_in_the_router_goes_to_the_lower_expert():
    x, g, w1, w2 = _expert_inputs(8, 2)
    g = g.at[:, 5].set(g[:, 2])                # experts 2 and 5 tie
    y, _ = routed_experts(x, g, w1, w2, top_k=2, act="gated_silu",
                          renormalize=True)
    np.testing.assert_allclose(y, _reference_experts(x, g, w1, w2, 2),
                               rtol=2e-5, atol=2e-5)
    # and the tie did decide something: with expert 5 nudged ahead the
    # answer is another
    y5, _ = routed_experts(x, g.at[:, 5].add(g[:, 2] * 1e-3), w1, w2,
                           top_k=2, act="gated_silu", renormalize=True)
    assert float(jnp.abs(y5 - y).max()) > 1e-3


@pytest.mark.parametrize("seed", [0, 1])
def test_a_switch_layer_served_by_routed_pairs_is_what_it_was(seed):
    """top-1, ReLU, the score itself as the weight: the capacity-buffer
    form with the capacity raised to every token (what the decode
    symbol built before) and the routed pairs give one answer."""
    x, g, w1, w2 = _expert_inputs(8, 1, seed=seed, act="relu")
    old = dense_moe(x, g, w1, w2, capacity_factor=8)
    new, _ = routed_experts(x, g, w1, w2, top_k=1, act="relu")
    np.testing.assert_allclose(new, old, rtol=2e-5, atol=2e-5)


# -- the block mask ----------------------------------------------------------

@pytest.mark.parametrize("per_row", [False, True])
def test_block_mask_against_dense_masked_attention(per_row):
    """The L new rows see each other both ways and every block before
    theirs, at a shared position (a prefill of three blocks) and at
    per-row block starts (a step)."""
    rng = np.random.default_rng(3)
    B, H, Hkv, D, C = 2, 4, 2, 8, 16
    Tn = L if per_row else 3 * L
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, k, v = f32(B, H, Tn, D), f32(B, Hkv, Tn, D), f32(B, Hkv, Tn, D)
    kc, vc = f32(B, C, Hkv * D), f32(B, C, Hkv * D)
    pos = np.array([4, 8]) if per_row else np.array([0])
    out, kc2, vc2 = cached_attention(q, k, v, kc, vc, jnp.asarray(pos),
                                     block=L)
    for b in range(B):
        p = int(pos[b if per_row else 0])
        keys = np.array(kc[b]).reshape(C, Hkv, D)
        vals = np.array(vc[b]).reshape(C, Hkv, D)
        keys[p:p + Tn] = np.moveaxis(np.asarray(k[b]), 0, 1)
        vals[p:p + Tn] = np.moveaxis(np.asarray(v[b]), 0, 1)
        np.testing.assert_array_equal(
            np.asarray(kc2[b]).reshape(C, Hkv, D), keys)
        for h in range(H):
            s = np.asarray(q[b, h]) @ keys[:, h // 2].T / np.sqrt(D)
            at = p + np.arange(Tn)
            sees = np.arange(C)[None, :] // L <= at[:, None] // L
            s = np.where(sees, s, -np.inf)
            w = np.exp(s - s.max(-1, keepdims=True))
            want = (w / w.sum(-1, keepdims=True)) @ vals[:, h // 2]
            np.testing.assert_allclose(out[b, h], want, rtol=2e-5,
                                       atol=2e-5)


def test_block_mask_takes_no_window():
    z = jnp.zeros((1, 2, L, 8))
    c = jnp.zeros((1, 8, 16))
    with pytest.raises(ValueError, match="no window"):
        cached_attention(z, z, z, c, c, jnp.asarray([0]), window=4,
                         block=L)


# -- the unmasking rules -----------------------------------------------------

def test_unmask_choice_by_rule():
    d = lambda rule, steps: canon_diffusion(dict(
        block_length=4, mask_id=0, steps=steps, remasking=rule,
        threshold=0.9))
    masked = np.array([False, True, True, True])
    conf = np.array([0.99, 0.2, 0.95, 0.95])
    pick = lambda rule, steps: np.flatnonzero(
        unmask_choice(masked, conf, d(rule, steps))).tolist()
    assert pick("sequential", 2) == [1, 2]
    assert pick("sequential", 1) == [1, 2, 3]
    assert pick("low_confidence_static", 4) == [2]      # tie: the left
    assert pick("low_confidence_static", 2) == [2, 3]
    assert pick("low_confidence_dynamic", 4) == [2, 3]  # all over 0.9
    low = np.array([0.99, 0.2, 0.5, 0.4])
    assert np.flatnonzero(unmask_choice(
        masked, low, d("low_confidence_dynamic", 4))).tolist() == [2]
    with pytest.raises(ValueError, match="multiple of steps"):
        d("sequential", 3)
    with pytest.raises(ValueError, match="remasking"):
        d("random", 2)


def _unmask_by_loop(masked, conf, d):
    """The rule as the host's loop stated it before it had to run on
    the device too: one block, by indices."""
    where = np.flatnonzero(masked)
    per_step = d["block_length"] // d["steps"]
    c = np.asarray(conf, np.float64)[where]
    if d["remasking"] == "sequential":
        take = where[:per_step]
    elif d["remasking"] == "low_confidence_static":
        take = where[np.argsort(-c, kind="stable")[:per_step]]
    else:
        take = where[c > d["threshold"]]
        if not len(take) and len(where):
            take = where[[np.argmax(c)]]
    out = np.zeros_like(masked)
    out[take] = True
    return out


@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("rule", REMASKING)
def test_the_rule_on_the_device_is_the_rule_on_the_host(rule, steps):
    """`unmask_choice` over jax.numpy under jit, every row at once (what
    `block_step` runs), against numpy (what `generate()` runs) and
    against the loop: 400 seeded blocks of every mask, confidences from
    a set of five so that ties are the rule, a threshold among them,
    and blocks with no mask left (none is taken)."""
    d = canon_diffusion(dict(block_length=L, mask_id=0, steps=steps,
                             remasking=rule, threshold=0.5))
    rng = np.random.default_rng(steps)
    masked = rng.integers(0, 2, (400, L)).astype(bool)
    masked[:16] = [[bool(m >> i & 1) for i in range(L)]
                   for m in range(16)]
    conf = rng.choice(np.array([0.1, 0.5, 0.50001, 0.9, 1.0], np.float32),
                      (400, L))
    host = unmask_choice(masked, conf, d)
    device = jax.jit(lambda m, c: unmask_choice(m, c, d, xp=jnp))(
        masked, conf)
    assert device.dtype == bool and host.dtype == bool
    np.testing.assert_array_equal(np.asarray(device), host)
    np.testing.assert_array_equal(
        host, np.stack([_unmask_by_loop(m, c, d)
                        for m, c in zip(masked, conf)]))
    assert not host[~masked].any() and not host[0].any()
    assert host[masked.any(1)].any(1).all()      # one at least


# -- Generator.generate against the reference --------------------------------

def _state_logits(clean, start, noisy):
    """The reference's logits for one noisy block at `start` beside the
    clean tokens before it: the sampler's definition without a cache."""
    toks = np.concatenate([clean[:start], noisy])
    pos = np.concatenate([np.arange(start), start + np.arange(L)])
    state = np.concatenate([np.zeros(start, int), np.ones(L, int)])
    return np.asarray(ref.logits_at(
        TOY, SEED, toks, pos, pos // L, state,
        np.arange(start, start + L), "float32"))


@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("rule", REMASKING)
def test_generate_against_the_reference(params, rule, steps):
    """Every denoising forward's logits are the reference's for that
    very state (the clean blocks before it, the noisy block itself),
    every unmasked token is the reference's best there, positions are
    unmasked by the rule, and the row holds exactly max_new tokens."""
    gen = _gen(params, 2, steps, rule)
    prompt = np.stack(_prompts([6, 6], seed=steps))
    seen, streamed = [], []
    out = gen.generate(prompt, 7, on_block_logits=lambda *a: seen.append(a),
                       on_token=streamed.append)
    assert out.shape == (2, 13)
    np.testing.assert_array_equal(out[:, :6], prompt)
    np.testing.assert_array_equal(np.stack(streamed, 1), out[:, 6:])
    d = gen._diffusion
    final = np.concatenate([out, np.zeros((2, 3), np.int64)], 1)
    forwards = {}
    for start, ids, masked, logits in seen:
        forwards[start] = forwards.get(start, 0) + 1
        for b in range(2):
            if not masked[b].any():
                continue
            assert (ids[b][masked[b]] == MASK).all()
            want = _state_logits(final[b], start, ids[b])
            np.testing.assert_allclose(logits[b], want, rtol=2e-4,
                                       atol=2e-4)
            best = want.argmax(-1)
            prob = np.exp(want - want.max(-1, keepdims=True))
            conf = prob.max(-1) / prob.sum(-1)
            take = unmask_choice(masked[b], conf, d)
            # what this forward unmasked is what the row holds there
            sel = take & (start + np.arange(L) < out.shape[1])
            np.testing.assert_array_equal(
                final[b, start:start + L][sel], best[sel])
    if rule != "low_confidence_dynamic":
        # T forwards a block that starts all masked; the first block
        # holds two prompt tokens
        assert forwards[8] == steps and forwards[12] == steps
        assert forwards[4] == -(-2 // (L // steps))


def test_sequential_rows_replay_from_their_tokens_alone(params):
    """`plan_row` lays a served row out from its tokens alone, and the
    reference then puts every served token first."""
    gen = _gen(params, 1, 2)
    for p, n in ((6, 7), (5, 2), (8, 5), (7, 9)):
        row = gen.generate(np.stack(_prompts([p], seed=p)), n)[0]
        want = next(ref.served_logits(TOY, SEED, [(p, row)], 2,
                                      dtype="float32"))
        assert want.shape == (n, V)
        np.testing.assert_array_equal(want.argmax(-1), row[p:])


# -- the serving decoder -----------------------------------------------------

CASES = [(r, n) for r in range(L) for n in (2, 5)]


@pytest.fixture(scope="module", params=[2, 8],
                ids=lambda slots: "pool%d" % slots)
def served(request, params):
    """Eight requests, every remainder of the prompt against a block
    with max_new 2 and 5, all of one prefill length. Through a pool of
    two slots: six of them are admitted while others are mid-block.
    Through a pool of eight (rungs of 1 and 8 rows): one, then two
    from inside the first one's first emission, then five from inside
    the first emission of those, so a group runs on either rung, the
    later ones beside rows that are mid-block. Beside each, the one-shot row of a
    batch-1 generator."""
    slots = request.param
    one = _gen(params, 1, 2)
    dec = _gen(params, slots, 2).serving_decoder()
    prompts = _prompts([8 + r for r, _ in CASES], seed=5)
    streams = [[] for _ in CASES]
    futs, emit, first_in = [], dec._emit, threading.Event()

    def submit(*cases):
        for i in cases:
            futs.append(dec.submit(prompts[i], CASES[i][1]))
            futs[-1].subscribe(streams[i].append)

    def spy(req, tok):                 # on the decode thread: what it
        first_in.wait(30)              # submits lands in ONE round
        if len(futs) == 1:
            submit(1, 2)
        elif len(futs) == 3 and req is futs[1]:
            submit(3, 4, 5, 6, 7)
        emit(req, tok)

    try:
        if slots == 2:
            submit(*range(len(CASES)))
        else:
            dec._emit = spy
            submit(0)
            first_in.set()
            futs[0].result(timeout=120)
        rows = [np.asarray(f.result(timeout=120)) for f in futs]
    finally:
        dec.close(30)
    stats = dict(dec.stats(), slots=slots,
                 programs=(dec._step_fn._cache_size(),
                           dec._block_admit_fn._cache_size()))
    want = [one.generate(p[None], n)[0]
            for p, (_, n) in zip(prompts, CASES)]
    return rows, want, streams, stats


@pytest.mark.parametrize("case", range(len(CASES)),
                         ids=["rem%d_new%d" % c for c in CASES])
def test_decoder_rows_equal_the_one_shot_rows(served, case):
    rows, want, streams, _ = served
    r, n = CASES[case]
    assert rows[case].shape == (8 + r + n,)
    np.testing.assert_array_equal(rows[case], want[case])
    # streamed in order, each token once, then the sentinel
    assert streams[case] == list(want[case][8 + r:]) + [None]


def test_decoder_counts_forwards_blocks_and_expert_pairs(served):
    _, _, _, st = served
    assert st["steps"] >= 1 and st["prefills"] >= 1
    assert st["forwards"] >= st["steps"]
    # every block was stored by a forward that also denoised the block
    # after it; no forward was a commit and nothing else
    assert st["commit_forwards"] == 0
    assert st["fused_commits"] + st["commit_forwards"] == \
        st["blocks_committed"]
    # each request's last whole prompt block and every answered block
    # but its last (a finished row's last block is never stored)
    assert st["blocks_committed"] == sum(
        -(-(8 + r + n) // L) - 2 for r, n in CASES)
    assert st["blocks_committed"] < st["forwards"]
    assert st["tokens_unmasked"] >= sum(n for _, n in CASES)
    # a step runs 2L positions a row: the clean block and the open one
    layers, experts, k = 2, 8, 2
    assert st["moe_assignments"] == \
        st["steps"] * layers * st["slots"] * 2 * L * k
    assert 0 < st["moe_experts_hit"] <= st["steps"] * layers * experts
    assert 1.0 <= st["moe_max_load"] <= experts
    # the pool was never idle between the first admission and the last
    # row's end: every step but the first was dispatched with the one
    # before it unread, no forward was spent on a row that had ended,
    # and neither the step nor the admission of a row's block state
    # compiled a second time over six admission rounds
    assert st["steps_ahead"] == st["steps"] - 1
    assert st["idle_forwards"] == 0
    assert st["programs"] == (1, 1)
    # every group is one prefill at the rows of its rung: 1 + 8 + 8 in
    # the pool of eight; the pool of two has the rungs 1 and 2, so a
    # group of either size runs no row but its own
    if st["slots"] == 8:
        assert (st["prefills"], st["prefill_rows"]) == (3, 17)
    else:
        assert st["prefill_rows"] == st["admitted"] == len(CASES)


def _order_of(dec):
    """Log, as `dec` runs, ("dispatch", n) when step n goes to the
    device and ("read", n) when the host asks for its results."""
    order, step, read = [], dec._step_fn, dec._read_block_step

    def dispatching(*a):
        order.append(("dispatch", sum(k == "dispatch" for k, _ in order)))
        return step(*a)

    def reading(*a):
        order.append(("read", sum(k == "read" for k, _ in order)))
        return read(*a)

    dec._step_fn, dec._read_block_step = dispatching, reading
    return order


def test_next_step_is_in_flight_while_tokens_are_emitted(params):
    """Step n + 1 is dispatched before step n is READ: the device forms
    its inputs from the block state it keeps, so the decode thread has
    a step in flight at every token it emits. Two blocks at two steps a
    block: 2 + 2 forwards, the first of each pair storing the block
    before (the prompt's last, then the first answered); the fifth step
    went out before the host had read that the row ended in the fourth,
    and the row rode it as an idle slot."""
    dec = _gen(params, 2, 2).serving_decoder()
    order, seen, emit = _order_of(dec), [], dec._emit

    def spy(req, tok):                         # on the decode thread
        seen.append(dec._inflight is not None)
        emit(req, tok)

    dec._emit = spy
    try:
        dec.submit(_prompts([8], seed=3)[0], 8).result(timeout=60)
    finally:
        dec.close(30)
    st = dec.stats()
    assert order == [("dispatch", 0)] + [
        (k, n + (k == "dispatch")) for n in range(4)
        for k in ("dispatch", "read")] + [("read", 4)]
    assert seen == [True] * 8
    assert (st["steps"], st["steps_ahead"], st["idle_forwards"]) == (5, 4, 0)
    assert (st["forwards"], st["commit_forwards"], st["fused_commits"],
            st["blocks_committed"]) == (4, 0, 2, 2)


def test_rows_that_end_ride_no_further_forward(params):
    """One row ends by its eos id, one by its budget, both in their
    second forward and with tokens of the block still masked, beside a
    row that runs on for four more steps: the device makes each an idle
    slot in the step it ends in (the host reads that a step late), so
    no forward is spent on them after it."""
    prompts = _prompts([8, 9, 10], seed=12)
    one = _gen(params, 1, 2)
    want = [one.generate(p[None], 12)[0] for p in prompts]
    eos = int(want[0][8 + 2])
    k = list(want[0][8:]).index(eos)           # where it comes up first
    dec = _gen(params, 3, 2).serving_decoder()
    try:
        futs = [dec.submit(prompts[0], 12, eos_id=eos),
                dec.submit(prompts[1], 3), dec.submit(prompts[2], 12)]
        rows = [np.asarray(f.result(timeout=120)) for f in futs]
    finally:
        dec.close(30)
    st = dec.stats()
    np.testing.assert_array_equal(rows[0], want[0][:8 + k + 1])
    np.testing.assert_array_equal(rows[1], want[1][:9 + 3])
    np.testing.assert_array_equal(rows[2], want[2])
    # two tokens a forward; prompt 10 opens on two known positions, so
    # its first forward yields the block's other two
    assert st["forwards"] == (k // 2 + 1) + 2 + 6
    assert st["idle_forwards"] == 0 and st["step_failures"] == 0


def test_a_slot_freed_and_taken_again_while_a_step_is_in_flight(params):
    """Two slots, three requests: the short one ends, and the queued
    one is admitted into its slot while the step dispatched before the
    host read that end is still unread; its block state is written
    behind that step, it joins the one after, and every row is the
    one-shot row."""
    prompts = _prompts([8, 9, 11], seed=13)
    dec = _gen(params, 2, 2).serving_decoder()
    admit, found = dec._admit_group, []

    def admitting(P0, reqs, free):
        found.append((free[0], dec._inflight is not None))
        admit(P0, reqs, free)

    dec._admit_group = admitting
    try:
        futs = [dec.submit(prompts[0], 2), dec.submit(prompts[1], 12),
                dec.submit(prompts[2], 6)]
        rows = [np.asarray(f.result(timeout=120)) for f in futs]
    finally:
        dec.close(30)
    st = dec.stats()
    assert found[-1] == (0, True) and found[0][0] == 0
    one = _gen(params, 1, 2)
    for p, n, row in zip(prompts, (2, 12, 6), rows):
        np.testing.assert_array_equal(row, one.generate(p[None], n)[0])
    assert st["idle_forwards"] == 0
    assert st["steps_ahead"] == st["steps"] - 1


def test_a_pool_evacuated_lets_its_rows_go_on_the_device_too(params):
    """Session export refuses a diffusion generator, so an evacuation
    fails the active rows: their block state goes with them (nothing
    rides on for a request that has failed) and the next request is
    served from an idle state, exactly."""
    import threading
    prompts = _prompts([8, 9], seed=14)
    dec = _gen(params, 2, 2).serving_decoder()
    gone = []

    def sink(tok):                 # on the decode thread, mid-block
        if not gone:
            gone.append(threading.Thread(
                target=lambda: gone.append(dec.evacuate(30))))
            gone[0].start()

    try:
        first = dec.submit(prompts[0], 36)
        first.subscribe(sink)
        with pytest.raises(ValueError, match="export_session"):
            first.result(timeout=60)
        gone[0].join(30)
        assert gone[1:] == [0] and len(first.emitted) < 36
        assert dec._inflight is None
        assert not np.asarray(dec._bstate["live"]).any()
        row = dec.submit(prompts[1], 8).result(timeout=60)
    finally:
        dec.close(30)
    st = dec.stats()
    np.testing.assert_array_equal(
        row, _gen(params, 1, 2).generate(prompts[1][None], 8)[0])
    assert st["idle_forwards"] == 0 and st["step_failures"] == 0


def test_describe_counts_the_block_state_with_the_pool(params):
    """`describe()` lowers the step as the pool runs it: parameters,
    the pool and the rows' block state, both donated."""
    dec = _gen(params, 2, 2).serving_decoder()
    try:
        text = dec.describe()
    finally:
        dec.close(30)
    pool = sum(a.nbytes for a in dec._aux.values())
    state = sum(a.nbytes for a in dec._bstate.values())
    assert 0 < state < 64 * 2
    assert "writes %d of the pool's %d bytes" % (
        pool + state, pool + state) in text


# -- the fused step: a block's commit rides the next block's first forward ---

@pytest.fixture(scope="module", params=[
    (rule, steps) for rule in REMASKING for steps in (1, 2, 4)],
    ids=lambda p: "%s-%d" % p)
def fused(request, params):
    """Four requests, one of each remainder of the prompt against a
    block, nine tokens each, through a fused pool of two slots, beside
    the one-shot T + 1 rows of a batch-1 generator: one pool for the
    schedule's four cases."""
    rule, steps = request.param
    prompts = _prompts([8 + r for r in range(L)], seed=steps)
    dec = _gen(params, 2, steps, rule).serving_decoder()
    try:
        futs = [dec.submit(p, 9) for p in prompts]
        rows = [np.asarray(f.result(timeout=120)) for f in futs]
        st = dec.stats()
    finally:
        dec.close(30)
    one = _gen(params, 1, steps, rule)
    return (rule, steps, rows,
            [one.generate(p[None], 9)[0] for p in prompts], st)


@pytest.mark.parametrize("rem", range(L))
def test_fused_pool_rows_equal_the_one_shot_rows(fused, rem):
    rule, steps, rows, want, st = fused
    np.testing.assert_array_equal(rows[rem], want[rem])
    assert st["commit_forwards"] == 0
    assert st["fused_commits"] == st["blocks_committed"] > 0
    if rule != "low_confidence_dynamic" and steps == 1:
        # one forward a block: every forward is fused
        assert st["forwards"] == st["fused_commits"]


def _watch_writes(dec):
    """Check every step of `dec` as it runs: slot b's cache rows change
    inside [cache_pos[b], cache_pos[b] + 2L) and nowhere else (a start
    past capacity would clamp and land lower), where cache_pos is what
    the step forms from the block state it is given; and a row that
    rides a step with its clean block already stored rewrites that
    block's rows with the very values they hold. Returns the list the
    steps' (cache_pos, rewrites checked) go to."""
    real, seen = dec._step_fn, []

    def checked(args, held, rng):
        before = {k: np.asarray(v) for k, v in held[0].items()}
        st = {k: np.asarray(v) for k, v in held[1].items()}
        two = st["live"] & st["has_prev"]
        pos = np.where(st["live"], st["start"] - L * two, 0)
        outs, new = real(args, held, rng)
        again = 0
        for k, was in before.items():
            now = np.asarray(new[0][k])
            for b in range(len(pos)):
                lo, hi = pos[b], pos[b] + 2 * L
                assert hi <= now.shape[1]
                np.testing.assert_array_equal(now[b, :lo], was[b, :lo])
                np.testing.assert_array_equal(now[b, hi:], was[b, hi:])
                if two[b] and not st["fused"][b]:
                    np.testing.assert_array_equal(now[b, lo:lo + L],
                                                  was[b, lo:lo + L])
                    again += 1
        seen.append((pos.copy(), again))
        return outs, new

    dec._step_fn = checked
    return seen


def test_a_row_of_exactly_max_len_is_served_and_nothing_is_clamped(
        params):
    """prompt + max_new == max_len: the last block's forwards write up
    to the last position and not past it, beside a second row whose
    stored rows stay as they were."""
    prompts = _prompts([41, 9], seed=4)
    dec = _gen(params, 2, 2).serving_decoder()
    seen = _watch_writes(dec)
    try:
        futs = [dec.submit(prompts[0], 7), dec.submit(prompts[1], 30)]
        rows = [np.asarray(f.result(timeout=120)) for f in futs]
    finally:
        dec.close(30)
    assert len(rows[0]) == T
    assert max(int(pos.max()) for pos, _ in seen) == T - 2 * L
    assert sum(again for _, again in seen) > 0
    one = _gen(params, 1, 2)
    for p, n, row in zip(prompts, (7, 30), rows):
        np.testing.assert_array_equal(row, one.generate(p[None], n)[0])
    with pytest.raises(ValueError, match="2 x block_length"):
        Generator(params, V, L + 2, batch_size=1,
                  **model.generator_args(TOY, {
                      "denoising_steps": 2, "remasking": "sequential"})
                  ).serving_decoder()


@pytest.mark.parametrize("p,n", [(1, 7), (3, 1), (2, 2), (3, 9)])
def test_a_prompt_shorter_than_a_block(params, p, n):
    """No prefill and no block before the first: that block rides in
    the step's first L positions (head_pos 0), the rest as ever."""
    prompt = _prompts([p], seed=p + n)[0]
    dec = _gen(params, 2, 2).serving_decoder()
    seen = _watch_writes(dec)
    try:
        row = dec.submit(prompt, n).result(timeout=60)
        st = dec.stats()
    finally:
        dec.close(30)
    np.testing.assert_array_equal(
        row, _gen(params, 1, 2).generate(prompt[None], n)[0])
    assert st["prefills"] == 0 and seen
    # the first block is stored by the second block's first forward
    assert st["blocks_committed"] == -(-(p + n) // L) - 1


@pytest.mark.parametrize("slots,late,rows_run", [(2, 1, 1 + 1),
                                                 (16, 2, 2 + 2)])
def test_a_row_admitted_while_another_is_mid_block(params, slots, late,
                                                   rows_run):
    """The second request (in a pool of 16: a group of two, one
    prefill of two rows) is submitted from inside the first one's
    first emission, with the first row's second step already on the
    device: its block state is written behind that step, and it rides
    its first (fused) forward beside the other's third."""
    prompts = _prompts([9, 10, 11], seed=8)[:1 + late]
    dec = _gen(params, slots, 2).serving_decoder()
    emit, admit = dec._emit, dec._admit_group
    futs, found = [], []

    def spy(req, tok):
        if not futs:
            futs.extend(dec.submit(p, 6) for p in prompts[1:])
        emit(req, tok)

    def admitting(P0, reqs, free):
        st = {k: np.asarray(v) for k, v in dec._bstate.items()}
        found.extend((int(st["masked"][i].sum()), int(st["start"][i]),
                      bool(st["fused"][i]), r.n_cached)
                     for i, r in enumerate(dec._slots) if r is not None)
        admit(P0, reqs, free)

    dec._emit, dec._admit_group = spy, admitting
    try:
        first = dec.submit(prompts[0], 6)
        rows = [first.result(timeout=60)] + \
            [f.result(timeout=60) for f in futs]
    finally:
        dec.close(30)
    # the first row on the device: its first block's three masks gone
    # in two steps, the next block open and all masked, the block it
    # left yet to be stored; the host has read the first step only, in
    # which the prompt's last block was stored
    assert found == [(L, 12, True, 8)]
    st = dec.stats()
    assert (st["prefills"], st["prefill_rows"]) == (2, rows_run)
    one = _gen(params, 1, 2)
    for p, row in zip(prompts, rows):
        np.testing.assert_array_equal(row, one.generate(p[None], 6)[0])


def test_a_fused_step_in_flight_when_a_step_fails(params):
    """The step dispatched ahead raises: every active row fails with
    the error, nothing stays in flight, the pool and the rows' block
    state are built anew and the next request is served from them,
    exactly."""
    prompts = _prompts([8, 10, 9], seed=6)
    dec = _gen(params, 2, 2).serving_decoder()
    real, calls = dec._step_fn, []

    def failing(args, held, rng):
        calls.append(1)
        if len(calls) > 1 and None not in dec._slots and \
                "raised" not in calls:
            # a step dispatched ahead, both rows in it
            calls.append("raised")
            raise RuntimeError("injected step fault")
        return real(args, held, rng)

    def admitting(P0, reqs, free):
        # on the decode thread: what a round finds before it writes
        found.append((dec._inflight is None, any(
            np.asarray(v).any() for v in list(dec._aux.values()) +
            list(dec._bstate.values()))))
        admit(P0, reqs, free)

    admit, found = dec._admit_group, []
    dec._step_fn, dec._admit_group = failing, admitting
    try:
        first = [dec.submit(p, 8) for p in prompts[:2]]
        for f in first:
            with pytest.raises(RuntimeError, match="injected"):
                f.result(timeout=60)
        row = dec.submit(prompts[2], 8).result(timeout=60)
        st = dec.stats()
    finally:
        dec.close(30)
    assert st["step_failures"] == 1
    # the third request's round found nothing in flight and both the
    # pool and the rows' block state zeroed
    assert found[-1] == (True, False)
    np.testing.assert_array_equal(
        row, _gen(params, 1, 2).generate(prompts[2][None], 8)[0])


def test_logits_hook_on_a_fused_step(params):
    """`on_block_logits` gets the OPEN block's start and (L, V) logits,
    the reference's for that state, on the forwards that also store
    the block before (the first of each block here) as on the others."""
    prompt = _prompts([6], seed=2)[0]
    dec = _gen(params, 2, 2).serving_decoder()
    seen = []
    dec.on_block_logits = lambda req, start, ids, masked, logits: \
        seen.append((start, req.n_cached, ids, masked, logits))
    try:
        row = dec.submit(prompt, 9).result(timeout=60)
    finally:
        dec.close(30)
    final = np.concatenate([row, np.zeros(L, np.int64)])
    assert [(s, c) for s, c, *_ in seen] == [
        (4, 4), (8, 8), (8, 8), (12, 12), (12, 12)]
    for start, _, ids, masked, logits in seen:
        assert logits.shape == (L, V) and masked.any()
        assert (ids[masked] == MASK).all()
        np.testing.assert_allclose(
            logits, _state_logits(final, start, ids), rtol=2e-4,
            atol=2e-4)


def test_masked_is_a_matter_of_position_not_of_the_id(params):
    """A head of zeros makes every logit tie, so every served token is
    id 0; with id 0 as the mask id too, a prompt and an answer hold it,
    and the row still finishes with exactly max_new tokens."""
    tied = dict(params, lm_head_weight=jnp.zeros_like(
        params["lm_head_weight"]))
    over = {"diffusion": {"mask_id": 0}}
    prompt = np.array([3, 0, 0, 5, 0, 7], np.int64)
    want = _gen(tied, 1, 2, **over).generate(prompt[None], 7)[0]
    np.testing.assert_array_equal(want, np.concatenate(
        [prompt, np.zeros(7, np.int64)]))
    dec = _gen(tied, 2, 2, **over).serving_decoder()
    try:
        row = dec.submit(prompt, 7).result(timeout=60)
    finally:
        dec.close(30)
    np.testing.assert_array_equal(row, want)


def test_logits_hook_reads_what_tokens_were_picked_from(params):
    dec = _gen(params, 2, 2).serving_decoder()
    try:
        prompts = _prompts([6, 9], seed=9)
        rows, logits = model.served_logits(dec, prompts, 5)
    finally:
        dec.close(30)
    want = list(ref.served_logits(
        TOY, SEED, [(len(p), r) for p, r in zip(prompts, rows)], 2,
        dtype="float32"))
    for got, exp, p, row in zip(logits, want, prompts, rows):
        np.testing.assert_allclose(got, exp, rtol=2e-4, atol=2e-4)
        np.testing.assert_array_equal(got.argmax(-1), row[len(p):])


# -- the rows a prefill runs: a short ladder of row counts -------------------

SLOTS = 16                                     # the cell's pool: 2, 16


def _in_one_round(dec):
    """Hold `dec`'s admission while the returned event is clear, so
    that what is submitted meanwhile is admitted in ONE round."""
    admit, gate = dec._admit, threading.Event()

    def gated():
        gate.wait(30)
        admit()

    dec._admit = gated
    gate.set()
    return gate


@pytest.fixture(scope="module")
def groups(params):
    """k = 1 ... 16 prompts of one length, each k submitted in one
    breath to an idle pool of 16 slots (the harness's `_warm_groups`),
    then a second length in groups of 1, 3 and 5. For each group what
    the pool's counters rose by and how many prefill and merge
    programs existed after it; for some, the rows served."""
    gen = _gen(params, SLOTS, 2)
    dec = gen.serving_decoder()
    gate = _in_one_round(dec)
    seen = {}
    try:
        for length, k in [(9, k) for k in range(1, SLOTS + 1)] + \
                [(14, 1), (14, 3), (14, 5)]:
            prompts = _prompts([length] * k, seed=length + k)
            before = dec.stats()
            gate.clear()
            futs = [dec.submit(p, 2) for p in prompts]
            gate.set()
            rows = [np.asarray(f.result(timeout=120)) for f in futs]
            after = dec.stats()
            seen[length, k] = dict(
                {key: after[key] - before[key] for key in
                 ("prefills", "prefill_rows", "admit_rounds", "merges")},
                prefill_programs=gen._prefill_fn._cache_size(),
                merge_programs=after["merge_programs"],
                prompts=prompts, rows=rows)
    finally:
        dec.close(30)
    return seen


@pytest.mark.parametrize("k", range(1, SLOTS + 1))
def test_a_group_is_one_prefill_at_the_rung_that_holds_it(groups, k):
    """What the harness's warm-up counts on: k prompts of one length
    make ONE prefill whatever k is; it runs 2 or 16 rows."""
    got = groups[9, k]
    assert (got["admit_rounds"], got["prefills"], got["merges"]) == \
        (1, 1, 1)
    assert got["prefill_rows"] == (2 if k <= 2 else 16)


@pytest.mark.parametrize("length,k", [(9, 1), (9, 2), (9, 3), (9, 16),
                                      (14, 1), (14, 3)])
def test_rows_through_every_rung_equal_the_one_shot_rows(
        params, groups, length, k):
    one = _gen(params, 1, 2)
    got = groups[length, k]
    for p, row in zip(got["prompts"], got["rows"]):
        np.testing.assert_array_equal(row, one.generate(p[None], 2)[0])


def test_a_lengths_first_admission_builds_every_rung(groups):
    """Two prefill programs and two merge programs after the very
    first admission (one row of one length), and no more however the
    groups of that length grow; two more prefill programs at the
    second length's first sight, and the merge programs as they were:
    they do not follow the length."""
    assert [groups[9, k]["prefill_programs"]
            for k in range(1, SLOTS + 1)] == [2] * SLOTS
    assert [groups[14, k]["prefill_programs"] for k in (1, 3, 5)] == \
        [4, 4, 4]
    assert {g["merge_programs"] for g in groups.values()} == {2}


def test_a_group_size_first_met_later_compiles_nothing(params):
    """After a length's first admission (one row), groups of two, of
    three and of five at that length are served without one backend
    compile: nothing of the serving path is built inside it."""
    import jax.monitoring
    dec = _gen(params, SLOTS, 2).serving_decoder()
    gate = _in_one_round(dec)
    compiles = []

    def on_event(name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(name)

    try:
        dec.submit(_prompts([9])[0], 6).result(timeout=120)
        jax.monitoring.register_event_duration_secs_listener(on_event)
        for k in (2, 3, 5):
            gate.clear()
            futs = [dec.submit(p, 6)
                    for p in _prompts([8 + k % 4] * k, seed=k)]
            gate.set()
            for f in futs:
                f.result(timeout=120)
        st = dec.stats()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
        dec.close(30)
    assert compiles == []
    assert (st["prefills"], st["prefill_rows"]) == (4, 2 + 2 + 16 + 16)


@pytest.mark.parametrize("slots,data,rungs", [
    (16, 1, [2, 16]), (8, 1, [1, 8]), (4, 1, [1, 4]), (2, 1, [1, 2]),
    (1, 1, [1]), (32, 1, [4, 32]), (16, 2, [2, 16]), (8, 2, [8]),
    (32, 4, [4, 32]), (16, 4, [16])])
def test_the_ladder_is_a_rule_from_the_pools_width(slots, data, rungs):
    """An eighth of the pool, one row under eight slots, and the pool;
    where the caches are split over a mesh's `data` axis, only the
    rungs that axis divides."""
    from types import SimpleNamespace
    from jax.sharding import PartitionSpec
    from mxnet_tpu.serve.decode import _row_rungs
    gen = SimpleNamespace(batch_size=slots, _cache_sharding=None)
    if data > 1:
        gen._cache_sharding = SimpleNamespace(
            spec=PartitionSpec("data", None, None))
        gen.mesh = SimpleNamespace(shape={"data": data})
    assert _row_rungs(gen) == rungs


@pytest.mark.parametrize("slots", [2, 4])
def test_a_pool_under_eight_slots_has_a_rung_of_one_row(params, slots):
    """The bottom rung is one row where an eighth of the pool is less:
    a lone prompt prefills one row, a group of the pool's width that
    many, each in ONE prefill, and the rows are the one-shot rows."""
    prompts = _prompts([9] * (1 + slots), seed=slots)
    dec = _gen(params, slots, 2).serving_decoder()
    gate = _in_one_round(dec)
    try:
        assert dec._rungs == [1, slots]
        rows = [dec.submit(prompts[0], 6).result(timeout=120)]
        gate.clear()
        futs = [dec.submit(p, 6) for p in prompts[1:]]
        gate.set()
        rows += [f.result(timeout=120) for f in futs]
        st = dec.stats()
    finally:
        dec.close(30)
    assert (st["prefills"], st["prefill_rows"]) == (2, 1 + slots)
    one = _gen(params, 1, 2)
    for p, row in zip(prompts, rows):
        np.testing.assert_array_equal(row, one.generate(p[None], 6)[0])


def test_a_pool_over_a_data_mesh_runs_the_rungs_it_can_split(params):
    """Sixteen slots over a `data` axis of two: a group of one and a
    group of two each run two rows, one on each device, and the rows
    are the one-shot rows."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    prompts = _prompts([9, 10, 11], seed=21)
    dec = _gen(params, SLOTS, 2, mesh=mesh).serving_decoder()
    gate = _in_one_round(dec)
    try:
        assert dec._rungs == [2, SLOTS]
        rows = [dec.submit(prompts[0], 6).result(timeout=120)]
        gate.clear()
        futs = [dec.submit(p, 6) for p in prompts[1:]]
        gate.set()
        rows += [f.result(timeout=120) for f in futs]
        st = dec.stats()
    finally:
        dec.close(30)
    assert (st["prefills"], st["prefill_rows"]) == (2, 2 + 2)
    one = _gen(params, 1, 2)
    for p, row in zip(prompts, rows):
        np.testing.assert_array_equal(row, one.generate(p[None], 6)[0])


def test_the_prefill_span_says_the_rows_it_ran(params, tmp_path):
    """`admit.prefill` carries `P`, `rows` (the real ones) and `run`
    (the rung); `admit.build` is there once a length, with the rungs
    it built."""
    import sys
    from mxnet_tpu import config, trace
    sys.path.insert(0, ROOT)
    from tools import trace_report
    trace.stop_tracing()
    config.set_override("MXNET_TRACE", str(tmp_path / "spill"))
    try:
        dec = _gen(params, SLOTS, 2).serving_decoder()
        gate = _in_one_round(dec)
        try:
            dec.submit(_prompts([9])[0], 2).result(timeout=120)
            gate.clear()
            futs = [dec.submit(p, 2) for p in _prompts([10] * 3, seed=1)]
            gate.set()
            for f in futs:
                f.result(timeout=120)
        finally:
            dec.close(30)
    finally:
        path = trace.stop_tracing()
        config.clear_override("MXNET_TRACE")
    spans = [r for r in trace_report.load(path)
             if r.get("kind") == "span"]
    named = lambda name: [r["attrs"] for r in spans
                          if r["name"] == name]
    assert named("admit.prefill") == [
        {"P": 4, "rows": 1, "run": 2}, {"P": 4, "rows": 3, "run": 16}]
    assert named("admit.build") == [{"P": 4, "rungs": [2, 16]}]
    assert [a["rows"] for a in named("admit.merge")] == [1, 3]
    ids = {r["span"]: r["name"] for r in spans}
    assert {ids[r["parent"]] for r in spans if r["name"] in (
        "admit.prefill", "admit.build", "admit.merge")} == \
        {"serve.decode.admit"}


# -- what refuses a diffusion generator --------------------------------------

@pytest.mark.parametrize("call", [
    lambda g, p: g.generate_on_device(p, 4),
    lambda g, p: g.beam_search(p, 4),
    lambda g, p: g.beam_search_on_device(p, 4),
    lambda g, p: g.log_likelihood(p),
    lambda g, p: g.truncated_draft(1),
    lambda g, p: g.generate_speculative(g, p, 4),
    lambda g, p: g.generate(p, 4, temperature=0.7),
], ids=["on_device", "beam", "beam_on_device", "log_likelihood",
        "truncated_draft", "speculative", "temperature"])
def test_entry_points_that_refuse_diffusion(params, call):
    gen = _gen(params, 1, 2)
    with pytest.raises(ValueError, match="diffusion"):
        call(gen, np.stack(_prompts([6])))


def test_decoder_refuses_drafts_chunks_and_sampling(params, monkeypatch):
    gen = _gen(params, 1, 2)
    with pytest.raises(ValueError, match="drafts"):
        gen.serving_decoder(draft=gen)
    dec = gen.serving_decoder()
    try:
        with pytest.raises(ValueError, match="greedy"):
            dec.submit(_prompts([6])[0], 4, temperature=0.5)
        monkeypatch.setenv("MXNET_PREFILL_CHUNK", "4")
        with pytest.raises(ValueError, match="chunked prefill"):
            dec.submit(_prompts([6])[0], 4)
        monkeypatch.delenv("MXNET_PREFILL_CHUNK")
        # the last block is run whole: 41 + 6 tokens end in block 44-47
        dec.submit(_prompts([41])[0], 6).result(timeout=60)
        with pytest.raises(ValueError, match="capacity"):
            dec.submit(_prompts([42])[0], 7)
    finally:
        dec.close(30)


def test_defaults_leave_the_symbol_as_it_was():
    """Each new argument's default builds the symbol of before: the
    same arguments, the same states, no second output."""
    from mxnet_tpu.models import transformer
    base = transformer.get_decode_symbol(V, T, num_layers=2, num_heads=4,
                                         dim=32)
    same = transformer.get_decode_symbol(
        V, T, num_layers=2, num_heads=4, dim=32, experts_per_token=1,
        expert_hidden=None, norm_topk_prob=False, head_dim=None,
        qk_norm=False, rope_base=None, attention_block=0,
        moe_stats=False, head_rows=0)
    assert base.list_arguments() == same.list_arguments()
    assert base.list_auxiliary_states() == same.list_auxiliary_states()
    assert len(base.list_outputs()) == len(same.list_outputs()) == 1
    ops = lambda sym: [(n["op"], n.get("attrs", n.get("param")))
                       for n in json.loads(sym.tojson())["nodes"]]
    assert ops(base) == ops(same)
    with pytest.raises(ValueError, match="moe_stats"):
        transformer.get_decode_symbol(V, T, moe_stats=True)
    with pytest.raises(ValueError, match="per_row_pos"):
        transformer.get_decode_symbol(V, T, head_rows=L)


def test_head_rows_reads_each_rows_own_positions():
    """The head of a per-row symbol built with head_rows=L reads L
    positions from head_pos[b] on: the logits the whole forward gives
    there, whatever the offset of a row."""
    from mxnet_tpu.executor import _graph_eval_fn
    from mxnet_tpu.models import transformer
    opts = dict(num_layers=1, num_heads=2, dim=16, pos_encoding="rope",
                per_row_pos=True)
    whole = transformer.get_decode_symbol(V, T, **opts)
    part = transformer.get_decode_symbol(V, T, head_rows=L, **opts)
    assert [a for a in part.list_arguments() if a != "head_pos"] == \
        whole.list_arguments()
    rng = np.random.default_rng(0)
    shapes, _, aux_shapes = whole.infer_shape(
        data=(3, 2 * L), positions=(3, 2 * L), cache_pos=(3,))
    args = {k: jnp.asarray(rng.normal(size=v) * 0.3, jnp.float32)
            for k, v in zip(whole.list_arguments(), shapes)}
    args["data"] = jnp.asarray(rng.integers(0, V, (3, 2 * L)),
                               jnp.float32)
    args["cache_pos"] = jnp.asarray([0.0, 4.0, 8.0])
    args["positions"] = args["cache_pos"][:, None] + jnp.arange(2.0 * L)
    aux = lambda: {k: jnp.zeros(v, jnp.float32) for k, v in zip(
        whole.list_auxiliary_states(), aux_shapes)}
    key = jax.random.PRNGKey(0)
    full, _ = _graph_eval_fn(whole)(args, aux(), key, False)
    head = [0, L, 2]
    got, _ = _graph_eval_fn(part)(
        dict(args, head_pos=jnp.asarray(head, jnp.float32)), aux(), key,
        False)
    assert got[0].shape == (3, L, V)
    for b, h in enumerate(head):
        np.testing.assert_allclose(got[0][b], full[0][b, h:h + L],
                                   rtol=1e-5, atol=1e-5)
