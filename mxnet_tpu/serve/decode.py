"""Continuous-batching decode for the transformer ``Generator``
(docs/serving.md §continuous decode).

Static-batch generation dies with its slowest sequence: a (B,) batch
holds every slot until the LAST row finishes, so mean device
utilization decays toward 1/B as lengths diverge. Continuous batching
(the O(1)-per-token cached-decode serving model, arXiv:2603.09555)
fixes the shape instead of the membership: a fixed slot pool over the
on-device decode state — per-slot KV-cache rows for attention blocks,
a constant (H, hd, hd) recurrent blob for ``block_type="ssm"`` layers
— where a finished sequence frees its slot at the step it finishes
and the next queued prompt is admitted at the following step. Decode
throughput then tracks offered load, not the longest request in
flight.

What makes the single compiled step possible is the per-row-position
decode graph (``get_decode_symbol(per_row_pos=True)`` →
``cached_attention`` — or ``cached_attention_q8`` under
``quantize_kv`` — with a (B,) ``pos``): every slot decodes at its
own depth inside ONE (B, 1) XLA program, so slot membership changes
never recompile. SSM layers need no twin at all — the recurrent
state carries its own position, so their per-row graph IS the shared
graph and the same one-program discipline holds for free. Prompt
admission reuses the Generator's ordinary shared-position prefill
(all admitted rows start at position 0) and merges the prefilled
state into the pool with a batch-axis scatter — under ``quantize_kv``
that merge carries the per-token f32 scale caches alongside the int8
rows, and SSM state blobs ride the same scatter with no length axis.
A PREFILL RUNS THE ROWS IT ADMITS, not the pool's width: every
prefill forward of either kind of pool — a round's group of
equal-length prompts, one chunk of a chunked prompt, the draft's
twins of both — runs at the smallest rung of a short ladder of row
counts that holds its real rows (:func:`_row_rungs`: an eighth of the
pool, at least one row, and the pool), on a fresh state of that many
rows (``Generator._fresh_aux(rows)``), and the merge installs the
real rows from that narrower source. One admission for both pools
(:meth:`ContinuousDecoder._prefill_group`); a prompt length's first
sight builds every rung's programs side by side
(:meth:`ContinuousDecoder._build_rungs`), so a group size first met
later compiles nothing; ``stats()`` counts the rows run
(``prefill_rows``, ``chunk_rows``) and the ``admit.prefill`` and
``serve.decode.prefill_chunk`` spans carry them as ``run``.

Decode is bandwidth-bound and the per-slot state is its dominant HBM
stream (re-read every step; each weight read once), so shrinking that
state directly raises how many slots fit a chip: an int8 cache
(``Generator(quantize_kv=True)``) roughly halves an attention slot's
bytes, and an SSM slot pins a CONSTANT byte count independent of
``max_len`` entirely. The ``serve.decode.kv_bytes_per_slot`` gauge
(state-agnostic despite the legacy name —
``Generator.state_bytes_per_slot()``) and :meth:`describe` /
``MXNET_DECODE_SLOTS=auto`` report the sizing math.

Exactness contract: greedy decode (temperature 0) emits token-for-token
what ``Generator.generate`` emits for the same prompt — the per-row
graph computes the same per-row math and rows are independent (pinned
in tests/test_serve_decode.py). Sampled requests are reproducible per
request (each carries its own PRNG stream keyed by ``seed``, split once
per emitted token exactly like ``generate``'s loop) and match a
``batch_size=1`` ``Generator.generate(seed=...)``, but not a
multi-row static batch — ``jax.random.categorical`` draws one noise
tensor per CALL, so row b of a (B, V) batch and the same logits alone
see different noise.

Because every piece of a mid-decode sequence is either portable
(cache rows via ``export_kv_rows``) or derivable (PRNG progress =
``len(emitted)`` splits — ``generation.replay_key``), an active
session survives its replica: :meth:`export_session` packages one
slot's full recovery state, ``submit(resume=...)`` readmits it on
another pool at its own depth, and :meth:`evacuate` (also wired to
SIGTERM via ``install_sigterm=True``) exports every active slot at
once so a migrating recycle is bounded by export+import cost instead
of longest-sequence drain (docs/robustness.md, fleet failure
semantics).

Speculative decoding (docs/serving.md §speculative): an optional
draft Generator (``draft=`` or ``MXNET_SPEC_DRAFT``) gives the pool a
second, smaller model sharing the slot shape. When any live slot
opted in (``submit(speculative=True)``), a loop iteration becomes a
ROUND: γ compiled (B, 1) draft steps propose tokens per slot, then
ONE (B, γ+1) target verify forward scores them all — with PER-ROW
acceptance (each slot keeps its own longest-matching prefix, unlike
the eager path's lockstep rule) and per-row position bookkeeping, so
rejected speculative cache entries are simply overwritten in place
and never attended. Verification is common-random-numbers exact: the
emission at index j is always ``_pick_token(target_logits_j, sub_j)``
with ``sub_j`` the request stream's (j+1)-th split, and the draft
proposes with the SAME sub — so output is byte-identical to plain
``generate``/non-speculative serving for the same (seed, prompt,
sampling args), which keeps failover replay and the dedup contract
token-exact. ``speculative`` is therefore a pure performance hint: a
draft-less replica admits the same request down the ordinary (B, 1)
path with identical output. Every emission funnels through
:meth:`_emit` one token at a time, so TTFT/inter-token metrics,
streamed frames and mid-stream failover cursors work unchanged.

The loop's order (docs/serving.md §the loop's order): a call of
:meth:`_step` DISPATCHES STEP t + 1, THEN READS STEP t, for both kinds
of pool, so the device runs the next program while the host reads,
picks, emits, finishes and admits. An autoregressive row's state stays
on the host (its position at t + 1 is its position at t plus one, and
whether its budget ends it is known from ``len(emitted)``); only the
token it feeds is not known before step t is read, so only the token
moves: ``next_tokens`` (:func:`_next_program`), one small compiled
program beside ``decode_step``, takes step t's logits as the step
returned them and forms step t + 1's (B, 1) input on the device: the
first index of the largest of a row's float32 last-position logits, or
the host's ``pending`` token for a row that was not in step t
(admitted, handed off, resumed or done with its chunks since). Its
second result is those float32 rows, whose copy to the host starts at
dispatch; the host reads them a step late and runs what it always ran
(``_pick``, :meth:`_emit`, :meth:`_maybe_finish`), over the very
values the device picked from, first index among equals on both
sides. A row whose budget ends with the emission in flight is not
dispatched again (``steps`` is what a loop that reads first would
count). A row whose eos id comes up in step t was dispatched in t + 1
already: that result is skipped and counted (``idle_forwards``), and
the slot's cache rows are garbage until the next admission's merge,
queued behind the step in flight by the donation chain, overwrites
them wholesale, recurrent state included. WHAT MAY NOT RIDE AHEAD
READS FIRST, decided by the requests in the pool and nothing else:
while a held row samples (its key is split on the host and
``_pick_token``'s arguments are static) each call reads the step in
flight before it forms the next from the host's tokens; a speculative
round, ``evacuate`` / ``export_session`` read it first too
(:meth:`_read_inflight`), so ``pending`` / ``n_cached`` are exact for
whoever looks. A dispatch that raises loses no token of the step
before it. ``stats()`` counts ``steps_ahead`` (steps dispatched with
the step before unread: ``steps - 1`` in a kept-full greedy pool) and
``idle_forwards``; ``on_logits`` hands each step's rows to whoever
asks.

Streamed generates (docs/serving.md §the streamed path's threads): an
emission wakes nobody. A streamed request's sink (:meth:`_note`) puts
the token on its stream's list of the turn; after each emitting part
of a turn (an admission round, a chunk's last forward, a step or
round) the loop hands what it noted to the decoder's ONE relay thread
in one piece (:meth:`_hand_off`; ``stats()["stream_handoffs"]``), and
the relay writes each stream's frame onto its connection without
blocking (:meth:`_relay_to`). A stream's handler thread
(:meth:`handle_generate_stream`) sleeps until the relay says the
sequence has settled with every frame out; a connection that does not
take a write whole becomes its handler's until the handler has caught
up (``stats()["stream_frames_late"]``), so a client that stops reading
delays no other stream and never the loop.

Generation by diffusion over blocks (``Generator(diffusion=...)``):
the pool's one compiled program is then ``block_step``, and a step no
longer yields one token a row. A row's state LIVES ON THE DEVICE,
beside the pool and donated to the step with it (``_fresh_block_state``):
its OPEN block's ids and which of its L positions are still masked (by
position: a prompt or an answer may hold the mask id), the ids of the
CLEAN block before it, where the open block starts, whether the clean
block is stored yet, whether the slot is live, the tokens the row
still owes and its eos id. ``block_step`` does a step's whole turn
from that state: it forms its own (B, 2L) inputs, runs the forward,
picks each position's best id and its confidence, unmasks some by the
generator's rule (``generation.unmask_choice``, the one statement of
it, over ``jax.numpy`` here), ends the rows whose budget or eos id
came up among the tokens that stream in order, and opens the next
block of a row that has no mask left. Every forward of a row is a
denoising forward of its open block over 2L positions: the clean
block at its own depth, then the open block at depth + L. Under the
block mask the clean block is blind to the open one, so the rows it
writes are its final key/value rows: a block's COMMIT rides the next
block's first denoising forward (a FUSED forward, after which the
row's cached depth advances by L), and a block costs T forwards, not
T + 1. A later forward of the same open block carries the same clean
block at the same depth again (the same program over the same inputs
writes the same rows); the open block's own rows land past the cached
depth, where the next forward overwrites them. So no forward writes
past the end of a row's open block, and a row of exactly ``max_len``
positions is served. What a new row brings as its clean block is its
prompt's last whole block (admission prefills the whole blocks before
that one, at the rows the group needs and not the pool's width
(``_row_rungs``), and picks nothing, then writes the row's state on
the device with one compiled ``block_admit``, queued behind the step
in flight);
only a prompt shorter than a block has none, and its first block rides
in the first L positions with L ignored ones after it. The final norm
and the head read the open block's L positions alone, wherever a row
has them, so the logits stay (B, L, V), on the device
(``on_block_logits`` reads them).

Since a step needs nothing the host holds, the loop runs ONE STEP
AHEAD of what it has read: a call dispatches step n + 1 (parameters,
pool, state), then reads step n's small record (the ids and mask it
ran on, the picks, what it unmasked, which slots were live, fused,
done, the experts' counts) and emits its 0 to L tokens a row, each as
soon as it and all before it are unmasked, one at a time through
:meth:`_emit`. The device runs while the host reads, emits, finishes
and admits; a row admitted meanwhile joins the step after. A row that
ends in step n is an idle slot in step n + 1 already (the device ended
it; the host learns it a step late and frees the slot then), so a
finished row rides no further forward and its last block is never
stored; the price of running ahead is one all-idle step after the
pool's last row ends. ``stats()`` counts ``steps_ahead`` (steps
dispatched with the step before unread) and ``idle_forwards``
(row-forwards the device spent on a row the host had let go: 0).
Greedy only; drafts, chunked prefill, handoff, resume and session
export refuse such a generator.
"""
from __future__ import annotations

import functools
import logging
import queue
import signal
import threading
from collections import OrderedDict, deque
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import jax
import jax.numpy as jnp

from .. import config as _config
from .. import telemetry as _telemetry
from .. import trace as _trace
from ..executor import _graph_eval_fn
from ..generation import (_pick_token, block_picks, replay_key,
                          unmask_choice)
from ..models import transformer
from .engine import (EngineClosed, Overloaded, RequestTimeout,
                     SessionEvacuated)

__all__ = ["ContinuousDecoder", "DecodeFuture", "drain_timeout",
           "prefill_chunk", "spec_draft"]

# replay dedup (PR 1's (cid, seq) pattern on the serving side): how
# many admit ids a decode replica remembers. Sized far past any
# plausible in-flight window — eviction is LRU, and evicting an id
# that could still be replayed would re-open the double-admit hole,
# so the cap exists only to bound memory over a replica's lifetime.
_DEDUP_CAP = 4096


def drain_timeout():
    """``MXNET_DECODE_DRAIN_TIMEOUT``, loudly validated: the drain
    budget for a decode replica — :meth:`ContinuousDecoder.close`
    waits this long for admitted sequences to finish, and the fleet
    router's :meth:`~mxnet_tpu.serve.ServeRouter.recycle` of a
    replica whose hello declared ``role: decode`` budgets its drain
    from the SAME knob (one drain clock; the router knob keeps
    covering every other role)."""
    import math
    t = float(_config.get("MXNET_DECODE_DRAIN_TIMEOUT"))
    if not (math.isfinite(t) and t > 0):
        raise ValueError(
            "MXNET_DECODE_DRAIN_TIMEOUT=%r: wants a positive finite "
            "number of seconds (a non-positive or non-finite drain "
            "budget would wedge or skip the drain silently)" % (t,))
    return t


def prefill_chunk():
    """``MXNET_PREFILL_CHUNK``, loudly validated: the colocated
    chunked-prefill width in tokens (0 = off, whole-prompt prefill).
    Read per admission round so tests and live reconfigures take
    effect without rebuilding the pool."""
    c = int(_config.get("MXNET_PREFILL_CHUNK") or 0)
    if c < 0:
        raise ValueError(
            "MXNET_PREFILL_CHUNK=%r: wants a non-negative chunk width "
            "in tokens (0 disables chunking)" % (c,))
    return c


def spec_draft():
    """``MXNET_SPEC_DRAFT``, loudly validated: the serving fleet's
    zero-config speculative draft. ``'layers=<d>[,gamma=<g>]'`` makes
    every :class:`ContinuousDecoder` built WITHOUT an explicit
    ``draft=`` attach ``generator.truncated_draft(num_layers=<d>)``
    and verify ``<g>`` proposals per round (default 4) — subprocess
    replicas (the chaos harness's children, spawned fleets) opt in
    through the environment with zero code changes. Empty = no draft.
    Returns ``(layers, gamma)`` or ``None``."""
    raw = str(_config.get("MXNET_SPEC_DRAFT") or "").strip()
    if not raw:
        return None
    layers, gamma = None, 4
    for part in raw.split(","):
        if "=" not in part:
            raise ValueError(
                "MXNET_SPEC_DRAFT=%r: wants 'layers=<d>[,gamma=<g>]' "
                "(got the fieldless part %r)" % (raw, part))
        k, v = (s.strip() for s in part.split("=", 1))
        try:
            val = int(v)
        except ValueError:
            raise ValueError(
                "MXNET_SPEC_DRAFT=%r: %s wants an integer, got %r"
                % (raw, k, v)) from None
        if k == "layers":
            layers = val
        elif k == "gamma":
            gamma = val
        else:
            raise ValueError(
                "MXNET_SPEC_DRAFT=%r: unknown field %r (supported: "
                "layers, gamma)" % (raw, k))
    if layers is None or layers < 1:
        raise ValueError(
            "MXNET_SPEC_DRAFT=%r: wants layers >= 1 (the draft must "
            "run at least one block)" % (raw,))
    if gamma < 1:
        raise ValueError(
            "MXNET_SPEC_DRAFT=%r: wants gamma >= 1 (a round must "
            "propose at least one token)" % (raw,))
    return layers, gamma


class DecodeFuture:
    """One sequence's pending result: the full token row
    (prompt + generated, eos included when hit) or a typed error.

    Streaming consumers :meth:`subscribe` a sink to see every emitted
    token as the decode loop picks it (plus a ``None`` sentinel when
    the sequence settles) — the engine half of the serve path's
    streamed generate frames."""

    __slots__ = ("prompt", "max_new", "eos_id", "temperature", "top_k",
                 "top_p", "seed", "_key", "t_enq", "t_admit", "t_last",
                 "tc", "emitted", "pending", "n_cached", "handoff",
                 "resume", "speculative",
                 "_ev", "_value", "_exc", "_slock", "_sinks")

    def __init__(self, prompt, max_new, eos_id, temperature, top_k,
                 top_p, seed, handoff=None, speculative=False):
        self.prompt = prompt               # (P,) int64
        self.max_new = max_new
        self.eos_id = eos_id
        self.temperature = float(temperature or 0.0)
        self.top_k = top_k
        self.top_p = top_p
        self.seed = int(seed or 0)         # kept for export_session
        # one PRNG stream per request, split once per emitted token —
        # the exact key discipline of Generator.generate's loop, so a
        # sampled request reproduces independently of what else shares
        # the pool
        self._key = jax.random.PRNGKey(seed) \
            if self.temperature > 0 else None
        self.handoff = handoff             # remote-prefill admit state
        self.resume = None                 # migrated-session admit state
        self.speculative = bool(speculative)   # performance HINT only
        if handoff is not None and self._key is not None:
            # the remote prefill consumed the stream's FIRST split for
            # the first token it ships — advance past it so local
            # picks continue the exact generate() key discipline
            self._key, _ = jax.random.split(self._key)
        self.t_enq = _telemetry.now_ms()
        self.t_admit = None                # set when a slot is claimed
        self.t_last = None                 # last emission (inter-token)
        self.tc = _trace.current_context()  # submitter's span, if any
        self.emitted = []
        self.pending = None                # sampled but not yet fed
        self.n_cached = 0
        self._ev = threading.Event()
        self._value = None
        self._exc = None
        self._slock = threading.Lock()     # emitted/sink consistency
        self._sinks = []                   # streaming subscribers

    def _pick(self, row_logits):
        """Next token id from this row's last-position logits."""
        if self.temperature > 0:
            self._key, sub = jax.random.split(self._key)
            return int(np.asarray(_pick_token(
                row_logits[None], self.temperature, self.top_k, sub,
                self.top_p))[0])
        return int(np.argmax(np.asarray(row_logits)))

    def _peek_subs(self, k):
        """The next ``k`` sampling subs WITHOUT advancing the stream —
        the speculative draft proposes with the SAME noise the verify
        pick will use (common random numbers), and the stream itself
        only advances per EMITTED token (via :meth:`_pick`), so the
        key discipline stays exactly ``generate``'s whatever mix of
        proposals gets accepted."""
        key, subs = self._key, []
        for _ in range(k):
            key, sub = jax.random.split(key)
            subs.append(sub)
        return subs

    def subscribe(self, sink):
        """Register a token sink: it is first fed every
        already-emitted token in order (the replayed prefix a deduped
        or resumed streaming attempt owes its client), then each new
        token as the loop emits it, then ``None`` once the sequence
        settles (result or error). Delivery holds the emission lock,
        so a sink sees the stream exactly once, in order, with no gap
        between the prefix replay and live emissions — sinks must be
        cheap and non-blocking (a list append: the streamed path's
        :meth:`ContinuousDecoder._note`)."""
        with self._slock:
            for t in self.emitted:
                sink(t)
            if self._ev.is_set():
                sink(None)
            else:
                self._sinks.append(sink)

    def unsubscribe(self, sink):
        with self._slock:
            try:
                self._sinks.remove(sink)
            except ValueError:
                pass

    def _emit(self, tok):
        """One emission: append + notify streaming sinks atomically
        (decode loop thread only)."""
        with self._slock:
            self.emitted.append(tok)
            for s in self._sinks:
                s(tok)
        self.pending = tok

    def _settle_sinks(self):
        with self._slock:
            self._ev.set()
            sinks, self._sinks = self._sinks, []
        for s in sinks:
            s(None)

    def _finish_ok(self):
        self._value = np.concatenate(
            [self.prompt, np.asarray(self.emitted, np.int64)])
        self._settle_sinks()

    def _fail(self, exc):
        self._exc = exc
        self._settle_sinks()

    def done(self):
        return self._ev.is_set()

    def result(self, timeout=None):
        if not self._ev.wait(timeout):
            raise RequestTimeout(
                "sequence still decoding after %.3fs" % timeout)
        if self._exc is not None:
            raise self._exc
        return self._value


class _Stream:
    """One streamed generate between the three threads that serve it
    (:meth:`ContinuousDecoder.handle_generate_stream`): the decode
    loop notes its tokens, the decoder's relay writes its frames, its
    handler sleeps until the sequence settles. The condition guards
    every field, and only the relay and the handler ever take it.

    ``late`` says whose the connection is: the handler's while it
    sends the replayed prefix and, ``slow``, after a write that the
    socket did not take whole, until it has sent all of ``backlog``
    (calls, each sending what is left of one frame or one whole
    frame); the relay's otherwise."""

    __slots__ = ("emit", "nowait", "offset", "cv", "late", "slow",
                 "backlog", "settled", "error", "closed")

    def __init__(self, emit):
        self.emit = emit
        # an emit with no socket under it (an in-process caller's) is
        # its own nowait: like a sink, it may not block
        self.nowait = getattr(emit, "nowait", emit)
        self.offset = 0            # emission index of the next frame
        self.cv = threading.Condition(threading.Lock())
        self.late = True
        self.slow = False
        self.backlog = []
        self.settled = False       # every frame before this is out
        self.error = None          # what a write to the connection raised
        self.closed = False        # the handler has left


def _merge_program(generator):
    """The compiled cache merge for ``generator``'s decode-state
    pytree: rows ``src[name][i]`` land at batch positions ``slots[i]``
    for ``i < n``, for every name at once. ONE program whatever ``n``
    is (the row count is a traced scalar: a loop of ``n`` whole-row
    ``dynamic_update_slice`` per cache array) and whatever the pytree
    holds — bf16 k/v rows, int8 rows with their 3-D f32 scale caches,
    SSM ``*_state`` blobs; it reads nothing but shapes. The pool is
    donated, so the rows are written in place instead of into a copy
    of every cache array; under a mesh the result keeps the
    generator's cache placement, which donation needs."""
    def cache_merge(pool, src, slots, n):
        # named, not a lambda: PjitFunction(cache_merge) and the
        # device's module name say which program ran
        def one_row(i, pool):
            return {name: jax.lax.dynamic_update_slice_in_dim(
                pool[name],
                jax.lax.dynamic_slice_in_dim(src[name], i, 1, 0),
                slots[i], 0) for name in pool}
        return jax.lax.fori_loop(0, n, one_row, pool)

    return jax.jit(cache_merge, donate_argnums=0,
                   out_shardings=generator._aux_shardings())


# What the rungs below were chosen from (my chip runs, PR 37; TPU v5
# lite; the SDAR cell: 16 slots, six layers of 128 experts of 768, top
# 8, bf16). One `block_prefill` on the device, ms, at 56 / 120 / 248 /
# 504 positions a row: 16 rows 9.98 / 18.70 / 46.18 / 87.92, 2 rows
# 4.24 / 5.79 / 7.99 / 12.19. Of a window's 350 groups 97% are one
# row, 3% two, one in 300 three. Tokens/s, one seed, by ladder: (2,
# 16) 2 683.7; (1, 16) 2 692.2; (1, 2, 16) 2 692.3; (1, 4, 16)
# 2 685.1, where 16 alone reads 2 148-2 184 over three seeds: beside
# an eighth of the pool a rung of one row or of a quarter buys nothing
# the runs' spread (0.6%) would show, and each costs a compile of 4-6 s
# a prompt length on an empty cache.
# What the same ladder gave the autoregressive pool (my chip runs,
# PR 43; TPU v5 lite). The OPT cell (8 slots, 24 layers of 2 048, bf16):
# one `generator_step` on the device, ms, at 128 / 256 / 512 / 1 024
# positions: 1 row 5.43 / 9.81 / 12.47 / 22.88, 8 rows - / - / 96.3 /
# 191.1; 47 of a traced window's 49 groups are one row; tokens/s
# 394.0 -> 763.0. The command-a cell (4 slots, chunks of 256): a chunk
# forward 110.0 ms at 4 rows, 22.7 at 1; tokens/s 25.0 -> 99.9. On an
# empty compile cache the OPT cell's window opened 17 s later (301.3 ->
# 318.3 s) for the one-row programs of its four prompt lengths.
def _row_rungs(generator):
    """The row counts a pool may prefill at, ascending, for both kinds
    of pool and every prefill forward they make: a whole-prompt length
    group runs at the smallest that holds it
    (:meth:`ContinuousDecoder._group_rows`), a chunk of a chunked
    prompt, which is ONE row, at the bottom rung, and the pool's width
    ``B`` is the top rung, so a group of any size is ONE prefill. A
    rule from ``B``, not a knob: an eighth of the pool, one row under
    eight slots, and the pool ({1, 4} at 4 slots, {1, 8}, {2, 16},
    {4, 32}); where the caches are split over a mesh's ``data`` axis,
    only the rungs that axis divides. A rung below ``B`` is one more
    compiled shape of the prefill a prompt length (and one of
    ``fresh_aux`` and of ``cache_merge``), so it stays only where the
    chip showed that it pays."""
    B = int(generator.batch_size)
    split = 1
    shard = generator._cache_sharding
    if shard is not None and shard.spec[0] is not None:
        split = generator.mesh.shape[shard.spec[0]]
    return sorted({r for r in (max(1, B // 8), B) if r % split == 0})


def _step_program(step, generator):
    """The compiled per-row step of ``generator``'s pool: ``step(args,
    aux, rng) -> (outs, aux)`` with the pool DONATED, so each row's new
    entries are written into the pool's own buffers instead of into a
    copy of every cache array. The caller rebinds its pool to the
    second result at once; the pytree it passed is deleted. Under a
    mesh the returned pool keeps the generator's cache placement,
    which donation needs (as in :func:`_merge_program`)."""
    return jax.jit(step, donate_argnums=1,
                   out_shardings=(None, generator._aux_shardings()))


def _next_program(whole):
    """The small compiled program beside an autoregressive pool's
    step: ``next_tokens(logits, host_tok, use_host) -> (data, last)``
    over a step's logits AS THE STEP RETURNED THEM. ``last`` is what
    the host reads of the step, each row's last position in float32;
    ``data`` is the (B, 1) float32 input of the step after: a row's
    ``host_tok`` where ``use_host`` (a row that was not in the step: the
    host picked its pending token), else the first index of the largest
    of ``last``, which is what ``DecodeFuture._pick`` makes of the very
    same float32 values when it reads them (ties included: both take
    the first). So the step after can be dispatched before the host has
    read this one. Both results lie at ``whole``
    (:func:`_row_placement`), so the step sees one placement of its
    ``data`` whoever formed it."""
    def next_tokens(logits, host_tok, use_host):
        # named, not a lambda: the device's module name says which
        # program ran
        last = logits[:, -1].astype(jnp.float32)
        picked = jnp.argmax(last, -1).astype(jnp.float32)
        return jnp.where(use_host, host_tok[:, 0], picked)[:, None], last

    return jax.jit(next_tokens, out_shardings=(whole, whole))


def _last_rows(logits):
    """Each row's float32 logits at the last position of a prefill
    forward, read on the host (the blocking read of an admission): two
    small eager programs a (rows, positions) shape, which a length's
    first sight builds with the prefill (:meth:`_build_rungs`)."""
    return np.asarray(logits[:, -1].astype(jnp.float32))


def _row_placement(generator):
    """Where a step's ``data`` lies under a mesh, from the host or
    from ``next_tokens``: whole on every device (None without a mesh:
    the default device)."""
    if generator.mesh is None:
        return None
    from ..parallel import sharding as shd
    return shd.replicated(generator.mesh)


def _fresh_block_state(B, L):
    """A diffusion pool's block state, every slot idle: what
    ``block_step`` reads its inputs from and advances, kept on the
    device beside the pool and donated with it. For each slot the open
    block's ``ids`` (the mask id where still masked) and which of its L
    positions are ``masked``, where it starts (``start``), the ids of
    the clean block before it (``prev``, if ``has_prev``) and whether
    that block is yet to be stored (``fused``: the row's next forward
    stores it); whether the slot is ``live``, and what ends a row: the
    tokens it still owes (``owed``) and its ``eos`` id (-1: none).
    Built from shapes alone, whatever the model."""
    def zeros(dtype, *tail):
        return jnp.zeros((B,) + tail, dtype)
    return {"ids": zeros(jnp.int32, L), "masked": zeros(bool, L),
            "prev": zeros(jnp.int32, L), "has_prev": zeros(bool),
            "start": zeros(jnp.int32), "fused": zeros(bool),
            "live": zeros(bool), "owed": zeros(jnp.int32),
            "eos": zeros(jnp.int32)}


def _block_programs(eval_fn, generator):
    """The two compiled programs of a diffusion pool, over the block
    state of :func:`_fresh_block_state`.

    ``block_step(params, (pool, state), rng) -> ((best, conf, logits,
    record), (pool, state))`` does a step's whole turn on the device,
    with the pool and the state donated: it forms the (B, 2L) forward's
    inputs from the state (a row's clean block at its own depth and its
    open block after it, the head on the open block; a row with no
    block before its open one feeds that one first; an idle slot feeds
    zeros at position 0), runs the forward, picks
    (:func:`block_picks`), unmasks by the generator's rule
    (:func:`unmask_choice`, the host's own statement of it), ends the
    rows whose budget or eos id came up among the tokens that stream in
    order (such a row is an idle slot from the next step on), and opens
    the next block of a row that has no mask left. So the step after
    needs nothing from the host, which reads a step late: each
    position's ``best`` id and its ``conf``, and the ``record``: the
    ``ids`` and ``masked`` the forward ran on, what it unmasked
    (``take``), ``start``, which slots were ``live``, which forwards
    were ``fused`` (stored a clean block), which rows are ``done``, and
    the expert layers' ``stats``. The float32 logits of 16 x 4
    positions over a 152k vocabulary are 39 MB a step: they stay on the
    device for whoever asks (``on_block_logits``).

    ``block_admit(state, rows, sel) -> state`` writes the rows ``sel``
    marks, one compiled shape whatever their number, the state donated
    (queued behind the step in flight, like the cache merge)."""
    d = generator._diffusion
    L, mask_id = d["block_length"], d["mask_id"]

    def first(flags):
        # the leftmost True of each row, L where there is none
        return jnp.where(flags.any(-1), jnp.argmax(flags, -1), L)

    def block_step(params, held, rng):
        # named, not a lambda: the device's module name says which
        # program ran
        aux, state = held
        ids, masked, start = state["ids"], state["masked"], state["start"]
        live, owed = state["live"], state["owed"]
        two = live & state["has_prev"]
        data = jnp.concatenate(
            [jnp.where(two[:, None], state["prev"], ids),
             jnp.where(two[:, None], ids, 0)], axis=1)
        pos = jnp.where(live, start - jnp.where(two, L, 0), 0).astype(
            jnp.float32)
        args = dict(
            params,
            data=jnp.where(live[:, None], data, 0).astype(jnp.float32),
            positions=pos[:, None] + jnp.arange(2 * L, dtype=jnp.float32),
            cache_pos=pos, head_pos=jnp.where(two, float(L), 0.0))
        outs, aux = eval_fn(args, aux, rng, False)
        best, conf = block_picks(outs[0])
        take = unmask_choice(masked, conf, d, xp=jnp) & live[:, None]
        ids, left = jnp.where(take, best, ids), masked & ~take
        # in order: a token streams once it and all before it are
        # unmasked, and the row ends with the first that is its eos id
        # or its last owed (the rule of _maybe_finish)
        sent, upto = first(masked), first(left)
        at = jnp.arange(L)
        streams = (at >= sent[:, None]) & (at < upto[:, None])
        stop = jnp.minimum(
            first(streams & (ids == state["eos"][:, None])),
            sent + owed - 1)
        done = live & (stop < upto)
        opens = live & ~done & ~left.any(-1)
        record = {"ids": state["ids"], "masked": masked, "take": take,
                  "start": start, "live": live,
                  "fused": live & state["fused"], "done": done,
                  "stats": outs[1] if len(outs) > 1 else
                  jnp.zeros((0, 3), jnp.int32)}
        state = {"ids": jnp.where(opens[:, None], mask_id, ids),
                 "masked": left | opens[:, None],
                 "prev": jnp.where(opens[:, None], ids, state["prev"]),
                 "has_prev": state["has_prev"] | opens,
                 "start": start + jnp.where(opens, L, 0),
                 # this forward stored the clean block it carried; the
                 # one a row leaves behind waits for its next forward
                 "fused": opens, "live": live & ~done,
                 "owed": owed - (upto - sent), "eos": state["eos"]}
        return (best, conf, outs[0], record), (aux, state)

    def block_admit(state, rows, sel):
        return {k: jnp.where(sel.reshape((-1,) + (1,) * (v.ndim - 1)),
                             rows[k], v) for k, v in state.items()}

    return (jax.jit(block_step, donate_argnums=1,
                    out_shardings=(None, (generator._aux_shardings(),
                                          None))),
            jax.jit(block_admit, donate_argnums=0))


class ContinuousDecoder:
    """Fixed-slot continuous batching over a Generator's decode state
    (KV caches for attention blocks, O(1) recurrent blobs for ssm
    blocks, both side by side in a mixed stack).

    The pool width is the Generator's ``batch_size``; its ``max_len``
    caps prompt + max_new_tokens per request. Requests queue FIFO
    (bounded by ``queue_cap`` → typed ``Overloaded``); ``close()``
    drains: admitted sequences finish, new submissions raise
    ``EngineClosed``.

    Int8 KV caches (``Generator(quantize_kv=True)``) are supported:
    the per-row op scatters the int8 rows and their per-token f32
    scale rows at each slot's own depth, halving cache bytes per slot.
    SSM blocks (``block_type="ssm"``) are supported: each slot's state
    is a constant-size blob, so a slot costs the same HBM at any
    depth. Attention that differs by layer
    (``Generator(attention_layers=...)``) is supported: a rolling
    layer's circular rows (``window + MXNET_PREFILL_CHUNK - 1`` of
    them, whatever ``max_len``) live beside a full layer's ``max_len``
    rows in the one pool, each row at its own depth; a prompt longer
    than the chunk prefills by chunks between steps and merges, and a
    row steps past the buffer and past the window. Not supported: the
    whole-stack ``rolling_cache`` spelling (its ``max_len`` is the
    buffer, not the bound on a request), speculative drafts beside a
    circular cache (a rejected proposal would overwrite a live slot)
    and with ssm blocks (no per-position state to roll back) — all
    raised at construction here, not mid-request.

    Disaggregated serving (docs/serving.md §disaggregated prefill):
    ``submit(handoff=...)`` admits a sequence whose prefill ran on a
    REMOTE prefill replica — the shipped cache rows scatter into the
    slot (:meth:`import_kv_rows`) and admission runs zero prefill
    graph calls; the ``role`` attribute is what the fleet router's
    hello frame reads to learn this replica decodes."""

    role = "decode"                       # the hello frame's identity

    def __init__(self, generator, queue_cap=64, logger=None,
                 install_sigterm=False, draft=None, lookahead=None):
        if getattr(generator, "_rolling", False):
            raise ValueError(
                "continuous batching does not support rolling_cache= "
                "(its max_len is the circular capacity, not the bound "
                "on prompt + max_new_tokens): give the window layers "
                "by attention_layers=[dict(window=, cache='rolling'), "
                "...] and size max_len to prompt + max_new_tokens")
        self._gen = generator
        self._B = int(generator.batch_size)
        self._log = logger or logging.getLogger(__name__)
        self._cap = int(queue_cap)

        # the per-row-position twin of the generator's decode graph —
        # same parameter names, so the generator's own (placed, maybe
        # quantized) param dict binds unchanged
        opts = dict(generator._decode_opts, per_row_pos=True)
        self._diff = getattr(generator, "_diffusion", None)
        if self._diff:
            if draft is not None or spec_draft() is not None:
                raise ValueError(
                    "speculative drafts are not supported with a "
                    "diffusion generator (a block step already yields "
                    "several tokens a forward)")
            if generator.max_len < 2 * self._diff["block_length"]:
                raise ValueError(
                    "a diffusion pool's step runs two blocks a row: "
                    "max_len (%d) must hold 2 x block_length (%d)"
                    % (generator.max_len, self._diff["block_length"]))
            # the block step's head reads the open block's positions
            # alone
            opts["head_rows"] = self._diff["block_length"]
        # a step reports what its expert layers did, and what the
        # selection of its latent-attention layers did (one more
        # output of counts each, read back with the step's other
        # results)
        opts["moe_stats"] = bool(opts["num_experts"] or opts["mla"])
        sym_p = transformer.get_decode_symbol(**opts)
        if [a for a in sym_p.list_arguments() if a != "head_pos"] != \
                generator._sym.list_arguments():
            # checkpoint-binding contract: both variants must bind the
            # same parameter names (a bare assert would vanish under -O)
            raise ValueError(
                "per-row decode symbol drifted from the scalar twin: "
                "%r vs %r" % (sym_p.list_arguments(),
                              generator._sym.list_arguments()))
        eval_fn = _graph_eval_fn(sym_p, mesh=generator.mesh)

        def decode_step(args, aux, rng):
            # named, not a lambda: PjitFunction(decode_step) and the
            # device's module name say which program ran
            return eval_fn(args, aux, rng, False)

        self._rng0 = jax.random.PRNGKey(0)
        # set by whoever wants the logits behind served tokens: called
        # on the decode thread for every denoising forward of every
        # active row as fn(request, block_start, ids (L,), masked (L,),
        # logits (L, V) float32); costs a device-to-host copy a step
        self.on_block_logits = None
        # its twin for an autoregressive pool: called on the decode
        # thread as fn(request, logits (V,) float32) with what the
        # request's ``_pick`` is handed next, for every row of every
        # (B, 1) step (admission's first token is picked from the
        # prefill); the read-back it looks at is made anyway
        self.on_logits = None
        if self._diff:
            # a diffusion pool's rows live on the device: the step
            # program advances their block state and admission writes
            # a new row's (see _block_programs), so the host reads a
            # step only after it has dispatched the next
            self._step_fn, self._block_admit_fn = _block_programs(
                eval_fn, generator)
            self._bstate = _fresh_block_state(
                self._B, self._diff["block_length"])
        else:
            self._step_fn = _step_program(decode_step, generator)
            self._data_at = _row_placement(generator)
            self._next_fn = _next_program(self._data_at)
        # the step still unread: (rows, outputs) of a diffusion pool;
        # of an autoregressive one its rows, its logits, its expert
        # counts (or None) and ``last``, each row's last position in
        # float32, once ``next_tokens`` has run over the logits; with
        # rows the request each slot held when it was dispatched. ONE
        # step rides ahead of what the host has read, and one is
        # enough: the host's work on a step (a few ms) hides under the
        # program of the next, and a second would only delay what a row
        # admitted now joins
        self._inflight = None

        self._aux = generator._fresh_aux()     # the pool caches
        self._alias_bytes = None               # (aliased, held), lazily
        self._import_jit = {}                  # pos -> fused scatter
        self._merge_fn = _merge_program(generator)
        self._dmerge_fn = None                 # the draft pool's twin
        self._rungs = _row_rungs(generator)    # rows a prefill may run
        self._built_lengths = set()    # (P, draft) whose rungs are built

        # -- speculative decoding (docs/serving.md §speculative) --
        # draft=None consults MXNET_SPEC_DRAFT so subprocess replicas
        # opt whole fleets in through the environment; an explicit
        # draft= (any Generator sharing vocab + slot-pool width) wins
        if draft is None:
            cfg = spec_draft()
            if cfg is not None:
                layers, env_gamma = cfg
                draft = generator.truncated_draft(num_layers=layers)
                if lookahead is None:
                    lookahead = env_gamma
        self._draft = draft
        self._gamma = max(1, int(lookahead)) if lookahead else 4
        if draft is not None:
            generator._refuse_latent(draft)
            if getattr(generator, "_has_ssm", False) or \
                    getattr(draft, "_has_ssm", False):
                # the env path (MXNET_SPEC_DRAFT -> truncated_draft)
                # already refused above; this catches an explicit
                # draft= with ssm blocks on either side
                raise ValueError(
                    "speculative decoding is not supported with ssm "
                    "blocks: the recurrent state has no per-position "
                    "entries to overwrite, so rejected proposals "
                    "would corrupt it (serve SSM models without a "
                    "draft, or use attention blocks for speculative "
                    "serving)")
            if draft.vocab_size != generator.vocab_size or \
                    draft.batch_size != generator.batch_size:
                raise ValueError(
                    "speculative draft must share vocab_size/"
                    "batch_size with the target (draft %d/%d vs "
                    "target %d/%d) — the draft decodes the same slot "
                    "pool" % (draft.vocab_size, draft.batch_size,
                              generator.vocab_size,
                              generator.batch_size))
            if getattr(draft, "_wraps", False) or \
                    getattr(generator, "_wraps", False):
                raise ValueError(
                    "speculative decoding is not supported beside a "
                    "rolling cache, the target's or the draft's "
                    "(rejected entries would overwrite live slots of "
                    "the circular buffer)")
            # the draft's own per-row-position twin: γ (B, 1) propose
            # steps per round, ONE compiled program across slot
            # turnover — same discipline as the target step
            d_opts = dict(draft._decode_opts, per_row_pos=True)
            d_sym = transformer.get_decode_symbol(**d_opts)
            if d_sym.list_arguments() != draft._sym.list_arguments():
                raise ValueError(
                    "per-row draft symbol drifted from the scalar "
                    "twin: %r vs %r" % (d_sym.list_arguments(),
                                        draft._sym.list_arguments()))
            d_eval = _graph_eval_fn(d_sym, mesh=draft.mesh)

            def draft_step(args, aux, rng):
                return d_eval(args, aux, rng, False)

            self._draft_step_fn = _step_program(draft_step, draft)
            self._daux = draft._fresh_aux()    # the draft's pool caches
            self._dmerge_fn = _merge_program(draft)
            # verify rounds write up to γ speculative entries past a
            # row's live depth (on BOTH pools: the target's verify
            # chunk and the draft's propose steps), so every admission
            # needs γ headroom while a draft is attached — enforced
            # pool-wide in submit() because non-speculative rows ride
            # the same verify forward with junk tails
            self._spec_cap = min(int(generator.max_len),
                                 int(draft.max_len)) - self._gamma
            if self._spec_cap < 2:
                raise ValueError(
                    "lookahead %d leaves no speculative headroom at "
                    "min(target max_len=%d, draft max_len=%d) — grow "
                    "max_len or shrink lookahead"
                    % (self._gamma, generator.max_len, draft.max_len))
        else:
            self._draft_step_fn = None
            self._daux = None
            self._spec_cap = None
        self._slots = [None] * self._B         # DecodeFuture per slot
        self._reserved = set()                 # slots held mid-chunk
        self._chunking = None                  # in-progress chunked prefill
        self._queue = deque()
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._draining = False
        self._closed = False
        # replay dedup (admit id -> the admission's own future): a
        # fleet-router replay after a transient fault returns the
        # ORIGINAL admission instead of double-admitting
        self._dedup = OrderedDict()
        self._evac_waiters = []                # (Event, [result]) pairs
        self._evac_flag = False                # SIGTERM handler sets

        self._admitted = 0
        self._finished = 0
        self._shed = 0
        self._steps = 0
        self._prefills = 0
        self._admit_rounds = 0     # _admit calls that admitted
        self._prefill_rows = 0     # rows every prefill forward RAN
        self._merges = 0           # compiled cache-merge dispatches
        self._chunks = 0           # chunk forwards of chunked prefills
        self._chunk_rows = 0       # rows those forwards RAN (1 is real)
        self._step_failures = 0    # steps that raised (_step_failed)
        # diffusion pools, in rows x forwards (a step runs one forward
        # for every active row): all forwards, those that also stored
        # the clean block before the one they denoised, positions
        # unmasked; and what the expert layers report from the device,
        # summed over layers and steps (idle rows' pairs included:
        # they are computed)
        self._forwards = 0
        self._fused_commits = 0
        self._tokens_unmasked = 0
        # steps dispatched while the step before was still unread, and
        # row-forwards the device spent on a row the host had let go
        self._steps_ahead = 0
        self._idle_forwards = 0
        self._moe_assignments = 0  # pairs routed, over all experts
        self._moe_pairs_here = 0   # pairs whose expert this chip holds
        self._moe_experts_hit = 0
        self._moe_max_load = 0.0   # largest expert batch over the mean
        # what the latent-attention layers' selection did, summed over
        # rows (idle ones too), layers and steps: keys a query could
        # see, and keys it attended
        self._dsa_keys_visible = 0
        self._dsa_keys_selected = 0
        self._dsa_keys_computed = 0
        self._imported = 0
        self._resumed = 0
        self._evacuated = 0
        self._deduped = 0
        self._streams = 0
        self._streams_inflight = 0
        # the streamed path (handle_generate_stream): what the loop has
        # noted since its last hand-off, {stream: [tokens, then None
        # where the sequence settled]}, and the relay's queue of such
        # hand-offs. The lock is the dict's; the loop takes it once a
        # token and a handler once a stream
        self._noted = {}
        self._noted_lock = threading.Lock()
        self._handoffs = queue.SimpleQueue()
        self._stream_handoffs = 0
        self._stream_frames_late = 0
        self._g_active = _telemetry.gauge("serve.decode.active_slots")
        # pool-measured twin of the Generator's static sizing gauge:
        # actual device-array bytes of the live cache pytree per slot.
        # Re-published whenever a slot turns over (the gauge is
        # last-write-wins and any OTHER Generator construction — a
        # speculative draft, a second model — overwrites it with ITS
        # static figure; the live pool must win while it is serving)
        self._kv_bytes_per_slot = sum(
            int(v.nbytes) for v in self._aux.values()) // self._B
        self._g_kv = _telemetry.gauge("serve.decode.kv_bytes_per_slot")
        self._g_kv.set(self._kv_bytes_per_slot)
        # the same bytes by kind of state (k/v rows, SSM blob, Mamba-2
        # scan state, convolution window), from the shapes the pool
        # was allocated with. The kinds without a length axis get a
        # gauge of their own beside kv_bytes_per_slot, set here once
        # (a constant of the pool) and only where the pool has them, so
        # a pool of k/v rows leaves the global snapshot as it was
        self._bytes_by_kind = generator.state_bytes_by_kind()
        for kind, n in self._bytes_by_kind.items():
            if kind != "kv_rows":
                _telemetry.gauge("serve.decode.%s_bytes_per_slot"
                                 % kind).set(n)
        # one compiled (B, 1) executable across slot turnover is THE
        # property continuous batching exists for; with a speculative
        # draft the target owns exactly TWO programs — the (B, 1) step
        # plus the (B, γ+1) verify — and never more. The gauge feeds
        # the decode/decode_q8/spec_decode perf-gate fingerprints
        self._g_jit = _telemetry.gauge("serve.decode.jit_cache_size")
        self._h_slotfill = _telemetry.histogram(
            "serve.decode.slot_fill", buckets=_telemetry.COUNT_BUCKETS)
        self._h_req = _telemetry.histogram("serve.decode.request_ms")
        self._c_admitted = _telemetry.counter("serve.decode.admitted")
        self._c_finished = _telemetry.counter("serve.decode.finished")
        self._c_steps = _telemetry.counter("serve.decode.steps")
        self._c_imported = _telemetry.counter("serve.decode.imported")
        self._h_import = _telemetry.histogram("serve.decode.import_ms")
        self._c_resumed = _telemetry.counter("serve.decode.resumed")
        self._c_evacuated = _telemetry.counter("serve.decode.evacuated")
        self._c_deduped = _telemetry.counter("serve.decode.deduped")
        # interactive-latency product metrics (PR 17): time to first
        # emitted token (from enqueue) and the gap between consecutive
        # emissions of one sequence — what streaming users actually
        # feel; tools/telemetry_report.py renders the quantiles
        # (a step that emits several tokens of one sequence — a
        # speculative round, a block step — observes one step-long gap
        # for the first of them and a gap near zero for each of the
        # rest: the histogram is of tokens, not of steps)
        self._h_ttft = _telemetry.histogram("serve.ttft_ms")
        self._h_itl = _telemetry.histogram("serve.inter_token_ms")
        self._c_streams = _telemetry.counter("serve.decode.streams")
        self._g_streams = _telemetry.gauge(
            "serve.decode.streams_active")
        self._c_handoffs = _telemetry.counter(
            "serve.decode.stream_handoffs")
        self._c_frames_late = _telemetry.counter(
            "serve.decode.stream_frames_late")
        self._c_chunks = _telemetry.counter(
            "serve.decode.prefill_chunks")

        # speculative accounting: instance ints always (stats() deltas
        # for benches), but the serve.spec.* telemetry series register
        # ONLY when a draft is attached — a draft-less pool must leave
        # the global snapshot exactly as before (perf-gate baselines
        # fingerprint every counter in it)
        self._spec_rounds = 0
        self._draft_steps = 0
        self._verify_steps = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._draft_prefills = 0
        if self._draft is not None:
            self._c_srounds = _telemetry.counter("serve.spec.rounds")
            self._c_dsteps = _telemetry.counter(
                "serve.spec.draft_steps")
            self._c_vsteps = _telemetry.counter(
                "serve.spec.verify_steps")
            self._c_proposed = _telemetry.counter(
                "serve.spec.proposed")
            self._c_accepted = _telemetry.counter(
                "serve.spec.accepted")
            self._c_dprefills = _telemetry.counter(
                "serve.spec.draft_prefills")
            # per-round per-row accepted/γ in [0, 1]; eighths resolve
            # the useful range at any lookahead <= 8
            self._h_accept = _telemetry.histogram(
                "serve.spec.accept_rate",
                buckets=tuple((i + 1) / 8 for i in range(8)))
            # one compiled (B, 1) draft propose program across slot
            # turnover — the draft half of the jit-cache discipline
            # (the target's gauge covers its step + verify pair)
            self._g_djit = _telemetry.gauge(
                "serve.spec.draft_jit_cache_size")

        self._shutdown = None
        if install_sigterm:
            from .. import guardrail as _guardrail
            self._shutdown = _guardrail.GracefulShutdown(
                signals=(signal.SIGTERM,), logger=self._log,
                on_request=self._request_evacuate,
                action="decode pool evacuating (active sessions "
                       "export for migration, then the pool drains)"
            ).install()

        slots_hint = str(_config.get("MXNET_DECODE_SLOTS") or "")
        if slots_hint and not slots_hint.startswith("auto"):
            raise ValueError(
                "MXNET_DECODE_SLOTS=%r: supported forms are '' (off), "
                "'auto' (report against the device HBM limit) or "
                "'auto:<bytes>' — the pool width itself is the "
                "Generator's batch_size, not this knob" % (slots_hint,))
        if slots_hint:
            budget = None
            if ":" in slots_hint:
                raw = slots_hint.split(":", 1)[1]
                try:
                    budget = float(raw)
                except ValueError:
                    budget = float("nan")
                import math
                if not (math.isfinite(budget) and budget > 0):
                    raise ValueError(
                        "MXNET_DECODE_SLOTS=%r: the budget after "
                        "'auto:' must be a positive finite number of "
                        "bytes (e.g. auto:16e9), got %r"
                        % (slots_hint, raw))
            self._log.info("decode slot sizing\n%s",
                           self.describe(hbm_budget=budget))

        self._relay_thread = threading.Thread(
            target=self._relay, name="mxnet-serve-relay", daemon=True)
        self._relay_thread.start()
        self._thread = threading.Thread(
            target=self._loop, name="mxnet-serve-decode", daemon=True)
        self._thread.start()

    def describe(self, hbm_budget=None):
        """SpecLayout.describe()-style sizing report: pool geometry,
        state bytes per slot (KV rows — int8 + f32 scale rows under
        quantize_kv — and/or fixed-size SSM state blobs), and — given
        an HBM budget in bytes — how many slots would fit at the
        configured max_len. hbm_budget=None tries the device's
        reported bytes_limit (``MXNET_DECODE_SLOTS=auto:<bytes>``
        passes one explicitly). The budget math covers per-slot
        decode state only; weights and activations claim their share
        of HBM on top."""
        gen = self._gen
        bps = self._kv_bytes_per_slot
        kinds = []
        by_kind = self._bytes_by_kind
        dims = lambda shape: "x".join(str(d) for d in shape[1:])
        if "kv_rows" in by_kind:
            kind = "int8 + f32 per-token scales" if gen._quantize_kv \
                else str(jnp.dtype(gen._cache_dtype))
            kinds.append("KV rows %s (%s), %d bytes" % (
                dims(gen._cache_shape), kind, by_kind["kv_rows"]))
        if "kv_window" in by_kind:
            rings = sorted(set(gen._rings.values()))
            kinds.append(
                "rolling KV rows %s (%s, circular: O(1) in max_len) in "
                "%d layer(s), %d bytes" % (
                    ", ".join("%dx%d of window %d" % (
                        r, gen._cache_shape[2], w) for r, w in rings),
                    jnp.dtype(gen._cache_dtype), len(gen._rings),
                    by_kind["kv_window"]))
        for kind, suffix, what in (
                ("latent_rows", "_latent_cache", "latent rows"),
                ("index_rows", "_index_cache", "index-key rows")):
            if kind in by_kind:
                kinds.append("%s %dx%d (%s, shared by every head) in "
                             "%d layer(s), %d bytes" % (
                                 what, gen.max_len,
                                 gen._row_widths[suffix],
                                 jnp.dtype(gen._cache_dtype),
                                 sum(n.endswith(suffix)
                                     for n in self._aux),
                                 by_kind[kind]))
        if "ssm_state" in by_kind:
            kinds.append("ssm state %s (float32, O(1) in max_len), "
                         "%d bytes" % (dims(gen._state_shape),
                                       by_kind["ssm_state"]))
        if "scan_state" in by_kind:
            kinds.append("mamba2 scan state %s (float32, O(1) in "
                         "max_len), %d bytes" % (
                             dims(gen._scan_shape),
                             by_kind["scan_state"]))
        if "conv_window" in by_kind:
            # Mamba-2's window beside its scan state, a gated short
            # convolution's alone: a pool may hold either or both
            windows = {n: dims(s) for n, s in (
                ("mamba2", gen._conv_shape),
                ("shortconv", gen._short_shape)) if s}
            kinds.append("%s convolution window %s (%s, O(1) in "
                         "max_len), %d bytes" % (
                             " and ".join(windows),
                             ", ".join(windows.values()),
                             jnp.dtype(gen._cache_dtype),
                             by_kind["conv_window"]))
        stateful = len({n.split("_", 1)[0] for n in self._aux})
        lines = [
            "ContinuousDecoder pool: %d slot(s), max_len=%d, "
            "%d layer(s)%s" % (
                self._B, gen.max_len, gen.num_layers,
                "" if stateful == gen.num_layers else
                " (%d hold no decode state)"
                % (gen.num_layers - stateful)),
            "  per-slot state: %s" % "; ".join(kinds),
            "  kv_bytes_per_slot: %d (%.2f MiB)  pool total: %.2f MiB"
            % (bps, bps / 2 ** 20, bps * self._B / 2 ** 20),
        ]
        aliased, held = self._step_alias_bytes()
        if aliased is None:
            lines.append(
                "  step program: this backend reports no aliased bytes "
                "(%d pool bytes on a device)" % held)
        else:
            lines.append(
                "  step program writes %d of the pool's %d bytes on a "
                "device in place (donated)" % (aliased, held))
        rows = [a.sharding.shard_shape(a.shape) + (a.dtype.itemsize,)
                for n, a in self._aux.items()
                if gen._aux_kind(n).endswith("_rows")]
        if rows:
            # (B, C, Hkv*hd) token rows: what one step writes, and how
            lines.append(
                "  a token is %d contiguous bytes a row; %d row writes "
                "a step" % (max(r[2] * r[3] for r in rows),
                            sum(r[0] for r in rows)))
        if hbm_budget is None:
            try:
                stats = jax.local_devices()[0].memory_stats() or {}
                hbm_budget = float(stats.get("bytes_limit") or 0) \
                    or None
            except Exception:  # noqa: BLE001 — backends may not report
                hbm_budget = None
        if hbm_budget:
            fit = int(hbm_budget // bps) if bps else 0
            lines.append(
                "  HBM budget %.2f GiB -> %d slot(s) fit at "
                "max_len=%d (cache bytes only; weights/activations "
                "not counted)" % (hbm_budget / 2 ** 30, fit,
                                  gen.max_len))
        else:
            lines.append(
                "  no HBM budget known (backend reports no "
                "bytes_limit) — set MXNET_DECODE_SLOTS=auto:<bytes>")
        return "\n".join(lines)

    def _step_alias_bytes(self):
        """(aliased, held): the bytes the compiled (B, 1) step program
        updates in place of its donated pool, by XLA's own account
        (``memory_analysis().alias_size_in_bytes`` of the
        ``decode_step`` executable), beside the bytes the pool holds
        on one device. Equal when every cache array is written in
        place; fewer means the step copies what it could not alias,
        which is logged as a warning. ``aliased`` is None where the
        backend reports no analysis. A property of the program, so it
        is read once; that costs one compile of the step unless the
        compilation cache holds it."""
        if self._alias_bytes is None:
            def spec(a):
                return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=a.sharding)
            aux = {n: spec(a) for n, a in self._aux.items()}
            args = {n: spec(a) for n, a in self._gen._params.items()}
            if self._diff:
                # block_step forms its inputs from the rows' block
                # state, which is donated beside the pool and counted
                # with it
                aux = (aux, {n: spec(a)
                             for n, a in self._bstate.items()})
            else:
                row = jax.ShapeDtypeStruct((self._B, 1), jnp.float32)
                args.update(data=row, positions=row,
                            cache_pos=jax.ShapeDtypeStruct(
                                (self._B,), jnp.float32))
            held = sum(
                int(np.prod(a.sharding.shard_shape(a.shape)))
                * a.dtype.itemsize for a in jax.tree_util.tree_leaves(aux))
            analysis = self._step_fn.lower(
                args, aux, self._rng0).compile().memory_analysis()
            aliased = getattr(analysis, "alias_size_in_bytes", None)
            if aliased is not None and aliased < held:
                self._log.warning(
                    "decode_step aliases %d of the pool's %d bytes on "
                    "a device: XLA could not use every donated cache "
                    "array, so each step copies the rest", aliased,
                    held)
            self._alias_bytes = (aliased, held)
        return self._alias_bytes

    # -- admission ----------------------------------------------------------
    def _check_blob(self, blob, want_pos=None,
                    why="the handoff must ship exactly the prompt's "
                        "prefill state"):
        """Loud structural validation of a handoff/resume blob BEFORE
        it is queued: names/shapes/dtypes must match this pool's own
        cache spec exactly (a blob from a mismatched generator — wrong
        architecture, wrong quantize_kv, wrong dtype — would scatter
        silently-wrong state; device-roundtrip exactness starts with
        refusing anything that isn't bit-compatible). ``want_pos``:
        the cached depth the blob must cover exactly — the prompt
        length for a handoff, prompt + fed tokens for a migrated
        session (None = trust the blob's own ``pos`` — the bare
        import_kv_rows surface)."""
        if not isinstance(blob, dict) or blob.get("v") != 1:
            raise ValueError("kv_blob is not an export_kv_rows v1 "
                             "blob: %r" % (type(blob).__name__,))
        pos = int(blob.get("pos", 0))
        if not 1 <= pos <= self._gen.max_len:
            raise ValueError(
                "kv_blob pos %d out of range for max_len=%d"
                % (pos, self._gen.max_len))
        if want_pos is not None and pos != want_pos:
            raise ValueError(
                "kv_blob covers %d cached token(s) but the admission "
                "expects %d — %s" % (pos, want_pos, why))
        rows = blob.get("rows") or {}
        if set(rows) != set(self._aux):
            raise ValueError(
                "kv_blob caches %s do not match this pool's %s"
                % (sorted(rows), sorted(self._aux)))
        for name, arr in rows.items():
            _, dtype = self._gen._aux_spec(name)
            want = self._gen._aux_row_shape(name, pos)
            if np.asarray(arr).dtype != dtype or arr.shape != want:
                raise ValueError(
                    "kv_blob cache %r is %s%r, expected %s%r — blob "
                    "and pool generators disagree (architecture, "
                    "dtype or quantize_kv mismatch)"
                    % (name, np.asarray(arr).dtype, arr.shape, dtype,
                       want))
        return pos

    def import_kv_rows(self, slot, blob):
        """Scatter one exported sequence's decode state into ``slot``
        — the decode half of the handoff, exact to the bit vs the
        prefill device's own state. For KV caches only the blob's
        ``pos``-token prefix lands; stale entries past it in the slot
        are never attended (the per-row cache-position mask). SSM
        state blobs have no length axis and land whole — the same
        O(1) bytes at any ``pos``. Called by the decode loop during
        handoff admission; external callers must own a quiescent pool
        (the loop thread is the aux mutator)."""
        slot = int(slot)
        if not 0 <= slot < self._B:
            raise ValueError("slot %d out of range for %d-slot pool"
                             % (slot, self._B))
        pos = self._check_blob(blob)
        t0 = _telemetry.now_ms()
        # ONE fused scatter program per pos (slot rides as a traced
        # scalar; the pool aux is donated so the update is in place,
        # not a whole-pool copy) — a separate jit from the (B, 1)
        # step, whose cache-size-1 gauge it never touches
        fn = self._import_jit.get(pos)
        if fn is None:
            def scatter(aux, rows, slot_):
                out = dict(aux)
                for name, r in rows.items():
                    # the wire is head-major; the pool token-contiguous
                    r = self._gen._wire_rows(name, r, False)
                    start = (slot_,) + (0,) * (r.ndim)
                    out[name] = jax.lax.dynamic_update_slice(
                        aux[name], r[None], start)
                return out
            fn = jax.jit(scatter, donate_argnums=0)
            self._import_jit[pos] = fn
        self._aux = fn(self._aux,
                       {n: jnp.asarray(a)
                        for n, a in blob["rows"].items()},
                       jnp.int32(slot))
        # block before timing: JAX dispatch is async, and an import_ms
        # that records dispatch-only would read ~0 while the real
        # scatter cost silently lands on the next (B, 1) step — the
        # histogram exists to budget the decode side of the handoff
        jax.block_until_ready(self._aux)
        ms = _telemetry.now_ms() - t0
        self._imported += 1
        self._c_imported.inc()
        self._h_import.observe(ms)
        return pos

    def export_session(self, slot):
        """The portable mid-decode state of one active slot — every
        piece a survivor needs to continue the sequence bit-exactly:
        the cache rows at ``pos = prompt + fed`` (device-exact, via
        the Generator's ``export_kv_rows``), the full request
        contract (prompt, sampling opts, seed), the emitted tokens
        and the pending not-yet-fed one. PRNG progress ships as
        DERIVED state — the stream splits once per drawn token, so
        ``submit(resume=...)`` re-derives the key by advancing
        ``len(emitted)`` splits (``generation.replay_key``) instead
        of trusting a shipped key. Callers outside the decode loop
        must own a quiescent pool (the loop thread is the aux
        mutator; :meth:`evacuate` runs this ON the loop thread)."""
        slot = int(slot)
        if not 0 <= slot < self._B:
            raise ValueError("slot %d out of range for %d-slot pool"
                             % (slot, self._B))
        if not self._diff:
            # with a step in flight a row's depth and pending token are
            # a step behind its cache rows: read it first (it may end
            # the row)
            self._read_inflight()
        req = self._slots[slot]
        if req is None:
            raise ValueError("slot %d holds no active sequence" % slot)
        if self._diff:
            raise ValueError(
                "export_session is not supported with a diffusion "
                "generator (a row's open block is not part of the "
                "portable session state)")
        blob = self._gen.export_kv_rows(self._aux, slot, req.n_cached)
        return {"v": 1,
                "prompt": np.asarray(req.prompt, np.int64),
                "max_new_tokens": int(req.max_new),
                "eos_id": req.eos_id,
                "temperature": req.temperature,
                "top_k": req.top_k,
                "top_p": req.top_p,
                "seed": req.seed,
                "emitted": [int(t) for t in req.emitted],
                "pending": int(req.pending),
                # a HINT for the survivor, not identity: resume works
                # (byte-identically) whether or not it carries a draft
                "speculative": bool(req.speculative),
                "kv_blob": blob}

    def submit(self, prompt, max_new_tokens, eos_id=None,
               temperature=0.0, top_k=None, top_p=None, seed=0,
               handoff=None, admit_id=None, resume=None,
               speculative=False):
        """Queue one sequence; returns a :class:`DecodeFuture` whose
        result is the full (prompt + generated) id row, exactly as
        ``Generator.generate`` would emit it for this prompt alone.

        ``handoff``: a remote prefill's ``{"first_token", "kv_blob",
        "pos"}`` reply (the ``prefill`` wire frame / a
        :class:`PrefillEngine` return). Admission then scatters the
        shipped cache rows into the slot and emits the shipped first
        token — zero prefill graph calls on this replica (asserted by
        the ``prefills`` stat).

        ``admit_id``: opaque exactly-once token (the fleet router
        sends one per generate). A resubmission carrying an id this
        replica has already admitted returns the ORIGINAL admission's
        future — a failover replay after a transient transport fault
        can never double-admit onto a replica that actually survived.

        ``resume``: an :meth:`export_session` state dict — readmit a
        session migrated off another replica mid-decode. The request
        args must describe the SAME request (the router re-sends the
        originals); the state supplies the progress: emitted tokens,
        the pending not-yet-fed token, and the cache rows, which
        scatter at ``pos = prompt + fed`` with zero prefill graph
        calls. The PRNG stream re-derives its key by advancing
        ``len(emitted)`` splits (``generation.replay_key``), so the
        remaining tokens are bit-identical to an unmigrated run.

        ``speculative``: opt this request into draft/verify rounds
        when the pool carries a draft — a pure performance HINT, not
        part of the request's identity: output is byte-identical
        either way (common-random-numbers verification), so a
        draft-less replica — e.g. the failover survivor of a
        speculative session — admits the same request down the
        ordinary (B, 1) path, and a resume need not restate it."""
        self._gen._check_sampling(temperature, top_k, top_p)
        prefill_chunk()   # loud knob validation on the CALLER's
        #                   thread — the decode loop must never die
        #                   on a config typo
        prompt = np.asarray(prompt, np.int64).reshape(-1)
        P, n = int(prompt.shape[0]), int(max_new_tokens)
        if P < 1:
            raise ValueError("empty prompt")
        if self._diff:
            chunk = prefill_chunk()
            if handoff is not None or resume is not None or \
                    (temperature and float(temperature) > 0) or \
                    (chunk and P > chunk):
                raise ValueError(
                    "a diffusion generator serves greedy requests "
                    "prefilled here in one piece: no handoff, resume, "
                    "temperature or chunked prefill "
                    "(MXNET_PREFILL_CHUNK)")
        if handoff is not None and resume is not None:
            raise ValueError(
                "handoff and resume are mutually exclusive — a "
                "migrated session's state already contains its cache "
                "rows; the original handoff was consumed before the "
                "export")
        if handoff is not None:
            if not isinstance(handoff, dict) or \
                    "first_token" not in handoff or \
                    "kv_blob" not in handoff:
                raise ValueError(
                    "handoff wants the prefill frame's {'first_token',"
                    " 'kv_blob', 'pos'} dict, got %r"
                    % (type(handoff).__name__,))
            # structural blob validation happens HERE on the caller's
            # thread — a mismatched blob must fail the submission
            # loudly, never reach the decode loop
            self._check_blob(handoff["kv_blob"], P)
        emitted = None
        if resume is not None:
            if not isinstance(resume, dict) or resume.get("v") != 1 \
                    or "kv_blob" not in resume \
                    or "emitted" not in resume:
                raise ValueError(
                    "resume wants an export_session() state dict, "
                    "got %r" % (type(resume).__name__,))
            if not np.array_equal(
                    prompt, np.asarray(resume["prompt"],
                                       np.int64).reshape(-1)):
                raise ValueError(
                    "resume state is for a different prompt — the "
                    "request args and the migrated state must "
                    "describe the same generate")
            emitted = [int(t) for t in resume["emitted"]]
            if not emitted:
                raise ValueError(
                    "resume state carries no emitted tokens — a "
                    "session exports only after its first emission; "
                    "replay the request from scratch instead")
            if len(emitted) >= n:
                raise ValueError(
                    "resume state already holds %d emitted token(s) "
                    "of a max_new_tokens=%d request — nothing left "
                    "to decode" % (len(emitted), n))
            # the request args are authoritative, but they must
            # RESTATE the migrated request: a resume admitted under
            # different sampling opts would continue the stream
            # silently diverged from the donor (the PRNG key and the
            # pick discipline both derive from these args)
            for fld, have in (
                    ("temperature", float(temperature or 0.0)),
                    ("top_k", top_k), ("top_p", top_p),
                    ("seed", int(seed or 0))):
                theirs = resume.get(fld)
                if fld == "temperature":
                    theirs = float(theirs or 0.0)
                elif fld == "seed":
                    theirs = int(theirs or 0)
                if theirs != have:
                    raise ValueError(
                        "resume state was exported with %s=%r but "
                        "this admission says %s=%r — the request "
                        "args must restate the migrated request "
                        "(the resumed stream would silently "
                        "diverge)" % (fld, theirs, fld, have))
            # after k emitted tokens the last one is still pending
            # (not yet fed through the step), so the cache covers
            # exactly P + k - 1 positions
            self._check_blob(
                resume["kv_blob"], P + len(emitted) - 1,
                why="a migrated session's rows must cover prompt + "
                    "fed tokens")
        if self._gen.block_span(P, n) > self._gen.max_len:
            raise ValueError(
                "prompt (%d) + max_new_tokens (%d) exceeds the cache "
                "capacity max_len=%d" % (P, n, self._gen.max_len))
        if handoff is None and resume is None:
            # a prompt prefilled here is fed whole or by chunks: every
            # circular buffer must keep its window whole under that
            chunk = prefill_chunk()
            self._gen.check_feed(
                P, chunk if chunk and P > chunk else P)
        if self._draft is not None and P + n > self._spec_cap:
            # pool-wide, not per-request: verify rounds write up to
            # lookahead speculative entries past EVERY live row's
            # depth (non-speculative rows ride the verify forward with
            # junk tails), so the headroom must hold for any row that
            # could share a round
            raise ValueError(
                "prompt (%d) + max_new_tokens (%d) exceeds the "
                "speculative headroom %d = min(target max_len=%d, "
                "draft max_len=%d) - lookahead %d; while a draft is "
                "attached every admission needs the headroom"
                % (P, n, self._spec_cap, self._gen.max_len,
                   self._draft.max_len, self._gamma))
        if self._gen._pos_rows is not None and \
                P + n > self._gen._pos_rows:
            raise ValueError(
                "prompt (%d) + max_new_tokens (%d) exceeds the "
                "trained position table (%d rows)"
                % (P, n, self._gen._pos_rows))
        req = DecodeFuture(prompt, n, eos_id, temperature, top_k,
                           top_p, seed, handoff=handoff,
                           speculative=speculative)
        if resume is not None:
            # PRNG progress is DERIVED state: one split per drawn
            # token, whatever path drew it (local pick or remote
            # handoff) — re-derive rather than ship a key
            if req._key is not None:
                req._key = replay_key(req.seed, len(emitted))
            req.emitted = emitted
            req.pending = int(resume["pending"])
            req.resume = resume["kv_blob"]
        if n == 0:                        # generate()'s n=0 contract
            req._finish_ok()
            return req
        if admit_id is not None:
            admit_id = str(admit_id)
        with self._cond:
            if admit_id is not None:
                prev = self._dedup.get(admit_id)
                if prev is not None:
                    # exactly-once admit: the replay rides the
                    # original admission (checked before the draining
                    # gate so a replayed request can still collect
                    # its answer from a draining replica)
                    self._dedup.move_to_end(admit_id)
                    self._deduped += 1
                    self._c_deduped.inc()
                    return prev
            if self._draining or self._closed:
                raise EngineClosed(
                    "decoder is draining — sequence rejected")
            if len(self._queue) >= self._cap:
                self._shed += 1
                _telemetry.counter("serve.shed").inc()
                raise Overloaded(
                    "decode queue full (%d sequences)"
                    % len(self._queue))
            if admit_id is not None:
                self._dedup[admit_id] = req
                while len(self._dedup) > _DEDUP_CAP:
                    self._dedup.popitem(last=False)
            self._queue.append(req)
            self._admitted += 1
            self._c_admitted.inc()
            self._cond.notify_all()
        return req

    def handle_generate(self, payload):
        """The ``generate`` wire frame (serve/net.py): submit one
        sequence — with its ``handoff`` blob when a remote prefill ran
        — and block the handler thread until the full row is back
        (concurrency comes from concurrent connections, the wire's
        standing contract). Payload keys mirror :meth:`submit`."""
        fut = self.submit(
            payload["prompt"], payload["max_new_tokens"],
            eos_id=payload.get("eos_id"),
            temperature=payload.get("temperature") or 0.0,
            top_k=payload.get("top_k"), top_p=payload.get("top_p"),
            seed=payload.get("seed") or 0,
            handoff=payload.get("handoff"),
            admit_id=payload.get("admit_id"),
            resume=payload.get("resume"),
            speculative=bool(payload.get("speculative")))
        try:
            return fut.result(payload.get("timeout"))
        except SessionEvacuated as exc:
            # the reply IS the session's portable state — the fleet
            # router resumes it on a survivor (serve/router.py) rather
            # than surfacing an error for a request nothing lost
            return {"evacuated": exc.state}

    def handle_generate_stream(self, payload, emit):
        """The streamed twin of :meth:`handle_generate`
        (serve/net.py's ``generate`` frame with ``stream: True``):
        submit the sequence and have every emitted token reach
        ``emit(tokens, offset)``, a step's tokens of this sequence in
        one call, in order, all of them before this returns.
        ``offset`` is the emission index of the call's first token, so
        a deduped replay (whose subscription replays the
        already-emitted prefix from offset 0) lets the client resume
        token-exact with no duplicated or missing frames. Returns the
        same final value as the one-shot path (the full id row, or the
        ``evacuated`` state dict): the terminal frame carries it for
        bitwise comparison.

        Three threads serve a stream (docs/serving.md §streaming). The
        decode loop only NOTES a token (:meth:`_note`, the sink
        subscribed here) and, when a step's rows are done, hands all
        it has noted to the decoder's one relay thread in one piece
        (:meth:`_hand_off`): one thread woken a step, however many
        rows stream. The relay calls ``emit.nowait(tokens, offset)``
        for each stream of the step (:meth:`_relay_to`), which writes
        what the connection takes at once. THIS handler thread sends
        the replayed prefix, then sleeps until the sequence settles;
        it is woken earlier only where a write fell short (a client
        that has stopped reading: the connection is then this
        thread's, to block on, until it has caught up, and nobody
        else's frames wait) or raised (the error is raised here, and
        ends this stream alone)."""
        fut = self.submit(
            payload["prompt"], payload["max_new_tokens"],
            eos_id=payload.get("eos_id"),
            temperature=payload.get("temperature") or 0.0,
            top_k=payload.get("top_k"), top_p=payload.get("top_p"),
            seed=payload.get("seed") or 0,
            handoff=payload.get("handoff"),
            admit_id=payload.get("admit_id"),
            resume=payload.get("resume"),
            speculative=bool(payload.get("speculative")))
        stream = _Stream(emit)
        sink = functools.partial(self._note, stream)
        timeout = payload.get("timeout")
        deadline = None if timeout is None else \
            _telemetry.now_ms() + float(timeout) * 1000.0

        def owed():
            return stream.backlog or stream.settled or \
                stream.error is not None

        with self._lock:
            self._streams += 1
            self._streams_inflight += 1
            self._g_streams.set(self._streams_inflight)
        self._c_streams.inc()
        fut.subscribe(sink)
        try:
            # the replayed prefix (and the settle, where the sequence
            # had ended) was noted on this thread: it is this thread's
            # to send, unless the loop has handed it over since. Under
            # the lock, so that the next hand-off's frames of this
            # stream line up behind it
            with self._noted_lock:
                self._relay_to(stream, self._noted.pop(stream, ()))
            while True:
                with stream.cv:
                    if not owed():
                        # the connection is the relay's from here on
                        stream.late = stream.slow = False
                        wait = None if deadline is None else max(
                            0.0,
                            (deadline - _telemetry.now_ms()) / 1000.0)
                        if not stream.cv.wait_for(owed, wait):
                            raise RequestTimeout(
                                "sequence still decoding after %.3fs"
                                % float(timeout))
                    if stream.error is not None:
                        raise stream.error
                    sends, stream.backlog = stream.backlog, []
                if not sends:              # settled, every frame out
                    break
                for send in sends:
                    send()
        finally:
            fut.unsubscribe(sink)
            with stream.cv:
                stream.closed = True
            with self._lock:
                self._streams_inflight -= 1
                self._g_streams.set(self._streams_inflight)
        try:
            return fut.result(0)
        except SessionEvacuated as exc:
            return {"evacuated": exc.state}

    def _note(self, stream, tok):
        """A stream's sink (``DecodeFuture.subscribe``): the token, or
        the None that settles the sequence, goes onto the stream's
        list of the step. No queue, no condition, nobody woken."""
        with self._noted_lock:
            self._noted.setdefault(stream, []).append(tok)

    def _hand_off(self):
        """Everything noted since the last call goes to the relay in
        one piece (decode loop thread only, after each of its turn's
        emitting parts: an admission round, a chunk's last forward, a
        step or round). Nothing noted, nothing done."""
        if not self._noted:
            return
        with self._noted_lock:
            noted, self._noted = self._noted, {}
        if noted:
            self._stream_handoffs += 1
            self._c_handoffs.inc()
            self._handoffs.put(noted)

    def _relay(self):
        """The relay thread: each hand-off's frames onto their
        connections, in the order the loop handed them over, until the
        loop's last (None)."""
        while True:
            noted = self._handoffs.get()
            if noted is None:
                return
            for stream, toks in noted.items():
                self._relay_to(stream, toks)

    def _relay_to(self, stream, toks):
        """One stream's tokens of one hand-off (a None last where the
        sequence settled with them) as one frame at the stream's
        offset: written now, as far as the connection takes it at
        once, or queued for the stream's handler where the connection
        is the handler's (``_Stream.late``). Never blocks. The handler
        is woken where there is something for it: the backlog, the
        settle, a write's error."""
        settled = bool(toks) and toks[-1] is None
        if settled:
            toks = toks[:-1]
        with stream.cv:
            if stream.closed or stream.error is not None:
                return
            if toks:
                offset = stream.offset
                stream.offset += len(toks)
                if stream.late:
                    stream.backlog.append(
                        functools.partial(stream.emit, toks, offset))
                else:
                    try:
                        rest = stream.nowait(toks, offset)
                    except Exception as exc:   # noqa: BLE001 — raised
                        # again on the stream's own thread, which ends
                        # as it did when that thread made the write
                        stream.error = exc
                    else:
                        if rest is not None:
                            stream.late = stream.slow = True
                            stream.backlog.append(rest)
                if stream.slow:
                    self._stream_frames_late += 1
                    self._c_frames_late.inc()
            stream.settled = stream.settled or settled
            if stream.late or stream.settled or \
                    stream.error is not None:
                stream.cv.notify()

    def generate_many(self, prompts, max_new_tokens, eos_id=None,
                      timeout=None, **kwargs):
        """Submit a batch of (possibly ragged) prompts and wait for all
        results — the closed-loop convenience wrapper; returns a list
        of id rows (ragged lengths when eos fires early)."""
        futs = [self.submit(p, max_new_tokens, eos_id=eos_id, **kwargs)
                for p in prompts]
        return [f.result(timeout) for f in futs]

    # -- the decode loop ----------------------------------------------------
    def _free_slots(self):
        return [i for i, s in enumerate(self._slots)
                if s is None and i not in self._reserved]

    def _merge_rows(self, pool, src, slots, draft=False):
        """Install the prefilled rows ``src[name][:len(slots)]`` at
        batch positions ``slots`` of ``pool`` (the draft's, with
        ``draft``), for every cache array in ONE compiled dispatch
        (:func:`_merge_program`), and return the new pool. Whole rows
        land, all ``max_len`` positions, so one program serves every
        prompt length. ``pool`` is DONATED, as it is to the step
        programs (:func:`_step_program`) and to the import scatter
        (:meth:`import_kv_rows`): the caller rebinds ``self._aux`` /
        ``self._daux`` to the result at once, and a reference kept to
        the old pytree raises "Array has been deleted" when read. The
        loop thread is the one aux mutator. The prefill's own state
        (``src``, a fresh state of its rung's rows run through
        ``generator_step``: as wide as the pool or narrower, a shape
        of the one program each) is not donated."""
        padded = np.zeros((self._B,), np.int32)
        padded[:len(slots)] = slots
        self._merges += 1
        fn = self._dmerge_fn if draft else self._merge_fn
        return fn(pool, src, padded, np.int32(len(slots)))

    def _group_rows(self, prompts):
        """A prefill forward's tokens as it runs them: the
        equal-length ``prompts`` (a length group's, or the one chunk
        of a chunked prompt), then copies of the first up to the
        smallest rung that holds them (:func:`_row_rungs`), as the
        float32 the graph's ``data`` takes (converted here, on the
        host: no program of its own on the device). The copies' rows
        are never merged. ``len()`` of the result is the rows run."""
        run = next(r for r in self._rungs if r >= len(prompts))
        return np.stack(list(prompts) + [prompts[0]] *
                        (run - len(prompts))).astype(np.float32)

    def _draft_prefill_rows(self, slot, tokens):
        """Prefill the DRAFT cache for one admitted row from raw token
        ids — the local draft leg of handoff/resume admission (the
        wire blobs carry TARGET rows only; prefill replicas stay
        draft-agnostic). Rides the draft Generator's shared-position
        prefill graph at the pool's bottom rung of rows (one row is
        real), chunked by ``MXNET_PREFILL_CHUNK`` when set so
        arbitrary handoff lengths reuse the chunk-width programs
        instead of compiling one prefill shape per length."""
        toks = np.asarray(tokens, np.int64).reshape(-1)
        n = len(toks)
        aux = self._draft._fresh_aux(self._rungs[0])
        width = prefill_chunk() or n
        lo = 0
        while lo < n:
            hi = min(lo + width, n)
            _, aux = self._draft._forward(
                aux, self._group_rows([toks[lo:hi]]), lo)
            lo = hi
        self._daux = self._merge_rows(self._daux, aux, [slot],
                                      draft=True)
        self._draft_prefills += 1
        self._c_dprefills.inc()

    def _admit_handoff(self, slot, req):
        """Admit one remote-prefilled sequence: scatter its shipped
        cache rows into the slot (zero TARGET prefill graph calls —
        the ``prefills`` stat must not move; a speculative request
        does prefill its DRAFT cache locally) and emit the shipped
        first token. A bad blob fails THAT request's future and frees
        the slot; the loop and the other slots are untouched."""
        with _trace.phase("serve.decode.import", parent=req.tc,
                          slot=slot) as ph:
            try:
                pos = self.import_kv_rows(slot, req.handoff["kv_blob"])
                tok = int(req.handoff["first_token"])
                if self._draft is not None and req.speculative:
                    self._draft_prefill_rows(slot, req.prompt)
            except Exception as exc:      # noqa: BLE001 — the future
                # is this sequence's one response; a scatter failure
                # must not kill the decode loop for every other slot
                req._fail(exc)
                return
            ph.note(pos=pos)
        self._slots[slot] = req
        req.handoff = None     # the rows live on device now — holding
        #                        the host blob would double memory per
        #                        imported slot for the whole decode
        req.t_admit = _telemetry.now_ms()
        req.n_cached = pos
        self._emit(req, tok)
        self._maybe_finish(slot, tok)

    def _admit_resume(self, slot, req):
        """Admit one migrated mid-decode session: scatter its exported
        rows at ``pos = prompt + fed`` and continue the stream — no
        first-token emission (the state already carries the pending
        token) and no prefill graph call. A bad blob fails THAT
        request's future; the loop and the other slots are
        untouched."""
        with _trace.phase("serve.decode.resume", parent=req.tc,
                          slot=slot, emitted=len(req.emitted)) as ph:
            try:
                pos = self.import_kv_rows(slot, req.resume)
                if self._draft is not None and req.speculative:
                    # the cache covers prompt + fed tokens (the pending
                    # last emission is not yet fed) — prefill the draft
                    # over exactly that prefix
                    self._draft_prefill_rows(
                        slot, np.concatenate(
                            [np.asarray(req.prompt, np.int64),
                             np.asarray(req.emitted[:-1], np.int64)]))
            except Exception as exc:      # noqa: BLE001 — the future
                # is this sequence's one response; an import failure
                # must not kill the decode loop for every other slot
                req._fail(exc)
                return
            ph.note(pos=pos)
        self._slots[slot] = req
        req.resume = None      # the rows live on device now
        req.t_admit = _telemetry.now_ms()
        req.n_cached = pos
        self._resumed += 1
        self._c_resumed.inc()

    def _admit(self):
        """Move queued prompts into free slots. Remote-prefilled
        sequences (a ``handoff`` rode the submit) scatter their
        shipped rows directly — no prefill graph call. Fresh prompts:
        one shared-position prefill per distinct prompt length per
        round (all admitted rows start at position 0, so the
        Generator's ordinary prefill graph serves), at the smallest
        rung of rows that holds the group (:meth:`_prefill_group`);
        cache rows merge into the pool by ONE compiled, donated
        program over the WHOLE aux pytree (:meth:`_merge_rows`) —
        under quantize_kv that carries the per-token f32 scale caches
        alongside the int8 k/v rows (a merged row without its scales
        would dequant to garbage)."""
        with self._lock:
            free = self._free_slots()
            if not free or not self._queue:
                return
            batch = [self._queue.popleft()
                     for _ in range(min(len(free), len(self._queue)))]
        self._admit_rounds += 1
        with _trace.phase("serve.decode.admit", n=len(batch),
                          lengths=[len(r.prompt) for r in batch]):
            self._admit_batch(batch, free)
        self._publish_pool_gauges()

    def _admit_batch(self, batch, free):
        """The round's work under its ``serve.decode.admit`` phase:
        resumed and handed-off rows straight into their slots, a
        prompt longer than the chunk into the one chunked prefill (or
        back to the queue's front behind it), the rest by length,
        each length group through :meth:`_admit_group`."""
        chunk = prefill_chunk()
        by_len = {}
        waiting = []       # long prompts parked behind an active chunk
        for req in batch:
            if req.resume is not None:
                self._admit_resume(free.pop(0), req)
                continue
            if req.handoff is not None:
                self._admit_handoff(free.pop(0), req)
                continue
            if chunk and len(req.prompt) > chunk:
                # long prompt: feed it to the cache chunk-by-chunk,
                # interleaved with decode steps, instead of stalling
                # every active slot for one monolithic (B, P) forward.
                # One chunked prefill at a time; later long prompts
                # wait their turn at the queue front (short prompts
                # are deliberately NOT held behind them)
                if self._chunking is None:
                    slot = free.pop(0)
                    self._reserved.add(slot)
                    # its state is the bottom rung's rows, the fewest
                    # that hold the one row a chunk is
                    self._chunking = {
                        "req": req, "slot": slot, "pos": 0,
                        "aux": self._gen._fresh_aux(self._rungs[0])}
                    if self._draft is not None and req.speculative:
                        # the draft cache prefills alongside, chunk
                        # by chunk on the same widths and rows
                        self._chunking["daux"] = \
                            self._draft._fresh_aux(self._rungs[0])
                else:
                    waiting.append(req)
                continue
            P = len(req.prompt)
            if self._diff:
                # the whole blocks but the last are prefilled: that one
                # is stored by the row's first forward, which denoises
                # the block the remainder opens
                L = self._diff["block_length"]
                P = max(P // L * L - L, 0)
            by_len.setdefault(P, []).append(req)
        if waiting:
            with self._lock:
                self._queue.extendleft(reversed(waiting))
        for P, reqs in sorted(by_len.items()):
            self._admit_group(P, reqs, free)

    def _admit_group(self, P, reqs, free):
        """One length group of a round, in either kind of pool: its
        prefill (:meth:`_prefill_group`; the draft's after it where a
        row speculates), then each row into its slot: an
        autoregressive row with its first token, picked from the
        prefill's last logits; a diffusion row with its block state
        (:meth:`_open_blocks`)."""
        slots = free[:len(reqs)]
        last = None
        if P:
            last = self._prefill_group(self._gen, P, reqs, slots)
            if self._draft is not None and \
                    any(r.speculative for r in reqs):
                # the draft's cache rows for this group, the same rows
                # through the draft's OWN shared-position graph (its
                # per-row propose program never sees prefill shapes) —
                # scattered for the whole group: non-speculative rows'
                # draft rows are unread garbage either way
                self._prefill_group(self._draft, P, reqs, slots)
        with _trace.phase("admit.emit"):
            if self._diff:
                self._open_blocks(P, reqs, free)
                return
            for i, req in enumerate(reqs):
                slot = free.pop(0)
                self._slots[slot] = req
                req.t_admit = _telemetry.now_ms()
                req.n_cached = P
                tok = req._pick(last[i])
                self._emit(req, tok)
                self._maybe_finish(slot, tok)

    def _prefill_forward(self, gen, aux, rows):
        """One prefill forward of ``rows`` (R, P) token ids from
        position 0 on the R-row state ``aux``: (logits, caches). A
        diffusion prefill reads no logits (the first tokens come from
        the first block's denoising forward), so there the first is
        None and the head is never computed."""
        if self._diff:
            return None, gen._prefill(aux, rows)
        return gen._forward(aux, rows, 0)

    def _prefill_group(self, gen, P, reqs, slots):
        """THE prefill of a length group, for both kinds of pool and
        for the draft (``gen`` is the pool's generator or its draft):
        the first ``P`` positions of ``reqs``' prompts in one
        shared-position forward from position 0, at the rows of the
        smallest rung that holds the group (:meth:`_group_rows`: the
        pool's width only where the group needs it) on a fresh state
        of that many rows, merged from it into ``slots`` of ``gen``'s
        pool. Returns the real rows' float32 logits at the last
        position, read on the host (None for the draft, whose picks
        nobody reads, and for a diffusion pool). Each child phase is
        the boundary of one thing a later change would replace (fresh
        state, the prefill, the blocking logits read, the merge)."""
        draft = gen is not self._gen
        tag = {"draft": 1} if draft else {}
        if (P, draft) not in self._built_lengths:
            self._build_rungs(gen, P)
        rows = self._group_rows([r.prompt[:P] for r in reqs])
        with _trace.phase("admit.fresh_aux", **tag):
            fresh = gen._fresh_aux(len(rows))
        with _trace.phase("admit.prefill", P=P, rows=len(reqs),
                          run=len(rows), **tag):
            logits, pref_aux = self._prefill_forward(gen, fresh, rows)
        del fresh
        last = None
        if draft:
            self._draft_prefills += 1
            self._c_dprefills.inc()
        else:
            self._prefills += 1
            self._prefill_rows += len(rows)
            if logits is not None:
                with _trace.phase("admit.wait"):
                    last = _last_rows(logits)[:len(reqs)]
        with _trace.phase("admit.merge", rows=len(reqs), **tag):
            if draft:
                self._daux = self._merge_rows(self._daux, pref_aux,
                                              slots, draft=True)
            else:
                self._aux = self._merge_rows(self._aux, pref_aux,
                                             slots)
        return last

    def _open_blocks(self, P0, reqs, free):
        """A diffusion group after its prefill (prompts whose whole
        blocks but the last are the same ``P0`` positions; a prompt
        shorter than two blocks prefills nothing): each row's block
        state written on the device by one compiled program
        (``block_admit``) queued behind the step in flight, so the row
        joins the step after. No first token is picked here: it comes
        from the first block's denoising forward, which also stores
        the prompt's last whole block."""
        d = self._diff
        L = d["block_length"]
        # each row's first block, opened on the prompt's remainder
        # (the mask id elsewhere), and the clean block its first
        # forward stores: its prompt's last whole block (none where
        # the prompt is shorter than a block)
        rows = {k: np.zeros(v.shape, v.dtype)
                for k, v in self._bstate.items()}
        sel = np.zeros((self._B,), bool)
        for req in reqs:
            slot = free.pop(0)
            self._slots[slot] = req
            req.t_admit = _telemetry.now_ms()
            req.n_cached = P0
            start = len(req.prompt) // L * L
            known = req.prompt[start:start + L]
            rows["ids"][slot] = d["mask_id"]
            rows["ids"][slot, :len(known)] = known
            rows["masked"][slot] = np.arange(L) >= len(known)
            rows["prev"][slot] = req.prompt[start - L:start] \
                if start else 0
            rows["has_prev"][slot] = rows["fused"][slot] = start > 0
            rows["start"][slot] = start
            rows["owed"][slot] = req.max_new
            rows["eos"][slot] = -1 if req.eos_id is None \
                else req.eos_id
            rows["live"][slot] = sel[slot] = True
        self._bstate = self._block_admit_fn(self._bstate, rows, sel)

    def _build_rungs(self, gen, P):
        """The first sight of a prefill length (by the pool's
        generator or by its draft, ``gen``): every rung's programs for
        it are built here and now (the state, the prefill, the read of
        its last logits and the merge, each run once on a state that
        is thrown away; the merge installs no row), so a group size
        first met later at this length compiles nothing in the serving
        path. The rungs' prefills compile side by side, a thread each
        (the compiler holds no interpreter lock). On an empty compile
        cache a length's first sight took 12.6 / 10.4 / 13.7 / 17.9 s
        at 56 / 120 / 248 / 504 positions in the SDAR cell, where the
        pool's width alone took 8.6 / 7.0 / 10.6 / 17.0 and the 2-row
        program alone compiles for 4.9-6.2 (my chip runs, PR 37)."""
        draft = gen is not self._gen

        def prefilled(run):
            logits, aux = self._prefill_forward(
                gen, gen._fresh_aux(run), np.zeros((run, P), np.float32))
            if logits is not None and not draft:
                _last_rows(logits)
            return aux

        nothing = np.zeros((self._B,), np.int32)
        merge = self._dmerge_fn if draft else self._merge_fn
        with _trace.phase("admit.build", P=P, rungs=self._rungs,
                          **({"draft": 1} if draft else {})), \
                ThreadPoolExecutor(len(self._rungs)) as pool:
            for pref_aux in pool.map(prefilled, self._rungs):
                if draft:
                    self._daux = merge(self._daux, pref_aux, nothing,
                                       np.int32(0))
                else:
                    self._aux = merge(self._aux, pref_aux, nothing,
                                      np.int32(0))
        self._built_lengths.add((P, draft))

    def _emit(self, req, tok):
        """One token emission: latency metrics (TTFT on the first
        emission of a fresh request, inter-token gap after that), then
        the append + streaming-sink notify. Every emission path —
        fresh-prefill pick, shipped handoff token, chunked-prefill
        completion, per-step pick — funnels through here so the
        latency histograms and streamed frames can never drift from
        the row the one-shot path returns."""
        now = _telemetry.now_ms()
        if not req.emitted:
            self._h_ttft.observe(now - req.t_enq)
        elif req.t_last is not None:
            # resumed sessions arrive with a non-empty emitted prefix
            # but no local t_last — their first local emission gap
            # spans the migration, not a decode step, so it is skipped
            self._h_itl.observe(now - req.t_last)
        req.t_last = now
        req._emit(tok)

    def _maybe_finish(self, slot, tok):
        """Retire the slot's sequence if this emission ended it (eos or
        budget) — the slot frees NOW, so the next admission round can
        reuse it at the following step."""
        req = self._slots[slot]
        if (req.eos_id is not None and tok == req.eos_id) or \
                len(req.emitted) >= req.max_new:
            # before the waiter wakes: a caller that reads the gauges
            # right after result() sees this turnover's values
            self._publish_pool_gauges()
            req._finish_ok()
            now = _telemetry.now_ms()
            self._h_req.observe(now - req.t_enq)
            self._finished += 1
            self._c_finished.inc()
            _telemetry.journal_event(
                "serve.decode.finish",
                tokens=len(req.emitted),
                ms=round(now - req.t_enq, 3))
            if _trace.enabled():
                # sequence lifecycle spans, retroactive from the
                # timestamps already taken (they cross threads and
                # steps, so they cannot be live phases): queue wait,
                # then the slot occupancy from admission to the
                # finishing emission. Parented to the submitter's
                # span, else a root — never to the loop phase that
                # happened to retire the sequence
                ctx = _trace.add_span(
                    "serve.decode.seq", req.t_enq, now,
                    parent=req.tc or _trace.ROOT,
                    tokens=len(req.emitted), prompt=len(req.prompt))
                if req.t_admit is not None:
                    _trace.add_span("serve.decode.queue", req.t_enq,
                                    req.t_admit, parent=ctx)
                    _trace.add_span("serve.decode.slot", req.t_admit,
                                    now, parent=ctx, slot=slot,
                                    tokens=len(req.emitted))
                # one write for the retired sequence's records
                _trace.flush()
            self._slots[slot] = None

    def _step(self):
        """One call of an autoregressive pool's loop (see the module
        docstring): dispatch the next (B, 1) per-row-position step,
        THEN read the one the call before left in flight and emit its
        tokens. Every slot held rides the step at its own depth, but a
        row whose budget ends with the emission still in flight; a row
        of the step in flight feeds what ``next_tokens`` picks from
        that step's logits on the device, at its depth plus one, and a
        row that was not in it (admitted, handed off, resumed or done
        with its chunks since) its ``pending`` token, picked by the
        host. Inactive slots feed a dummy token at position 0 (their
        cache rows are garbage until the next admission overwrites
        them wholesale).

        What may not ride ahead reads first: while any held row samples
        (its key is split on the host and ``_pick_token``'s arguments
        are static), the step in flight is read and emitted before the
        next is formed, from the host's tokens alone. The phase notes
        ``active`` (rows dispatched) and ``ahead`` (dispatched with the
        step before unread)."""
        if self._diff:
            return self._block_step()
        if self._inflight is None and \
                all(s is None for s in self._slots):
            return
        with _trace.phase("serve.decode.step") as ph:
            last, self._inflight = self._inflight, None
            if last is not None and any(
                    s is not None and s.temperature > 0
                    for s in self._slots):
                self._read_step(**last)
                last = None
            try:
                with _trace.phase("step.inputs"):
                    rows = {}
                    ahead = last["rows"] if last is not None else {}
                    toks = np.zeros((self._B, 1), np.float32)
                    pos = np.zeros((self._B,), np.float32)
                    use_host = np.ones((self._B,), bool)
                    for i, req in enumerate(self._slots):
                        if req is None:
                            continue
                        if ahead.get(i) is not req:
                            toks[i, 0] = float(req.pending)
                            pos[i] = float(req.n_cached)
                        elif len(req.emitted) + 1 < req.max_new:
                            use_host[i] = False
                            pos[i] = float(req.n_cached + 1)
                        else:
                            # the emission in flight is its last
                            continue
                        rows[i] = req
                    args = dict(self._gen._params)
                    args["positions"] = jnp.asarray(pos[:, None])
                    args["cache_pos"] = jnp.asarray(pos)
                ph.note(active=len(rows),
                        ahead=bool(rows) and last is not None)
                if rows:
                    with _trace.phase("step.dispatch"):
                        if last is None:
                            args["data"] = jax.device_put(
                                toks, self._data_at)
                        else:
                            args["data"], last["last"] = self._next_fn(
                                last["logits"], toks, use_host)
                            last["last"].copy_to_host_async()
                        outs, self._aux = self._step_fn(
                            args, self._aux, self._rng0)
                    # the expert layers' counts ride the step's read
                    self._inflight = {
                        "rows": rows, "logits": outs[0], "last": None,
                        "stats": tuple(outs[1:]) or None}
                    self._steps_ahead += last is not None
            finally:
                # a dispatch that raises loses no token of the step
                # before it
                if last is not None:
                    self._read_step(**last)

    def _read_inflight(self):
        """Read and emit the step in flight, if there is one: what
        anything but :meth:`_step` does first (a speculative round, a
        session's export), so that every row's ``pending`` and
        ``n_cached`` are exact when it looks at them."""
        last, self._inflight = self._inflight, None
        if last is not None:
            # a step's phase of its own: the read half alone
            with _trace.phase("serve.decode.step", active=0,
                              ahead=False):
                self._read_step(**last)

    def _read_step(self, rows, logits, stats, last):
        """Read one step's results from the device and do the host's
        part of it: the counters, ``on_logits``, and each row's token
        through ``_pick`` / :meth:`_emit` / :meth:`_maybe_finish`.
        ``rows`` maps a slot to the request it held when the step was
        dispatched; a row the host has let go since (its eos id came up
        in the step before) is skipped and counted. ``last`` is None
        where no step was dispatched after this one: ``next_tokens``
        then runs for the read alone."""
        with _trace.phase("step.wait"):
            if last is None:
                _, last = self._next_fn(
                    logits, np.zeros((self._B, 1), np.float32),
                    np.ones((self._B,), bool))
            last, stats = jax.device_get((last, stats))
            if stats is not None:
                stats = list(stats)
                if self._gen._decode_opts["num_experts"]:
                    self._count_experts(stats.pop(0))
                for keys in stats:
                    # (mla layers, 3): keys visible, selected, computed
                    self._dsa_keys_visible += int(keys[:, 0].sum())
                    self._dsa_keys_selected += int(keys[:, 1].sum())
                    self._dsa_keys_computed += int(keys[:, 2].sum())
        with _trace.phase("step.emit"):
            mine = [(i, req) for i, req in rows.items()
                    if self._slots[i] is req]
            self._idle_forwards += len(rows) - len(mine)
            self._steps += 1
            self._c_steps.inc()
            self._h_slotfill.observe(len(mine))
            self._g_active.set(len(mine))
            if self.on_logits is not None:
                for i, req in mine:
                    self.on_logits(req, last[i])
            for i, req in mine:
                req.n_cached += 1
                tok = req._pick(last[i])
                self._emit(req, tok)
                self._maybe_finish(i, tok)

    def _count_experts(self, stats):
        """One step's expert counts from the device, (expert layers,
        3 or 4) int32 as models/transformer.py ``moe_stats`` lays them
        out: pairs routed, held experts hit, largest expert batch and,
        where the pool holds a share of the experts, pairs computed
        here (else every routed pair is)."""
        self._moe_assignments += int(stats[:, 0].sum())
        self._moe_pairs_here += int(stats[:, -1 if stats.shape[1] > 3
                                          else 0].sum())
        self._moe_experts_hit += int(stats[:, 1].sum())
        experts = self._gen._decode_opts["num_experts"]
        self._moe_max_load = max(
            self._moe_max_load,
            float((stats[:, 2] * experts / stats[:, 0]).max()))

    def _block_step(self):
        """One call of a diffusion pool's loop (see the module
        docstring): dispatch the next (B, 2L) step, THEN read the one
        the call before left in flight and emit its tokens. The device
        forms a step's inputs from the block state it keeps, so the
        dispatch needs nothing of the step before it and the device
        runs while the host reads, emits, finishes and admits; a row
        admitted meanwhile joins the step after. After an idle period
        there is nothing to read yet; with every slot let go there is
        nothing more to dispatch, and the last step is read alone.

        Every active row of the step read ran one denoising forward of
        its open block, which for a row whose clean block no forward
        had stored yet is the fused forward that stores it. The phase
        carries ``forward`` ("denoise" | "fused" where every row ran
        the same kind, else "mixed"), ``unmasked`` and ``fused`` (the
        blocks the step stored)."""
        last = self._inflight
        if last is None and all(s is None for s in self._slots):
            return
        with _trace.phase("serve.decode.step") as ph:
            self._inflight = None
            try:
                with _trace.phase("step.inputs"):
                    # the device holds a step's inputs; the host keeps
                    # who was in which slot, for the record's tokens
                    rows = {i: s for i, s in enumerate(self._slots)
                            if s is not None}
                if rows:
                    with _trace.phase("step.dispatch"):
                        out, (self._aux, self._bstate) = self._step_fn(
                            self._gen._params,
                            (self._aux, self._bstate), self._rng0)
                    self._inflight = (rows, out)
                    self._steps_ahead += last is not None
            finally:
                # a dispatch that raises loses no token of the step
                # before it
                if last is not None:
                    self._read_block_step(ph, *last)

    def _read_block_step(self, ph, rows, out):
        """Read one step's results from the device and do the host's
        part of it: the counters, ``on_block_logits``, and each row's
        tokens through :meth:`_emit` / :meth:`_maybe_finish`, from the
        ids and the mask the record says the forward ran on and what it
        unmasked. ``rows`` maps a slot to the request it held when the
        step was dispatched; which of them the step ran, the device
        says (a row it had ended rode as an idle slot)."""
        best, _, logits, rec = out
        with _trace.phase("step.wait"):
            best, rec = jax.device_get((best, rec))
        with _trace.phase("step.emit"):
            live, fused = rec["live"], int(rec["fused"].sum())
            active, unmasked = int(live.sum()), int(rec["take"].sum())
            self._steps += 1
            self._c_steps.inc()
            self._h_slotfill.observe(active)
            self._g_active.set(active)
            self._forwards += active
            self._fused_commits += fused
            self._tokens_unmasked += unmasked
            if len(rec["stats"]):
                self._count_experts(rec["stats"])
            ph.note(active=active, unmasked=unmasked, fused=fused,
                    forward="fused" if fused == active
                    else "mixed" if fused else "denoise")
            hook = self.on_block_logits
            if hook is not None:
                logits = np.asarray(logits.astype(jnp.float32))
            L = rec["ids"].shape[1]
            ids = np.where(rec["take"], best, rec["ids"]).tolist()
            masked = (rec["masked"] & ~rec["take"]).tolist()
            for i in np.flatnonzero(live).tolist():
                req = rows.get(i)
                if req is None or self._slots[i] is not req:
                    # the device ran a row the host had let go
                    self._idle_forwards += 1
                    continue
                start = int(rec["start"][i])
                if rec["fused"][i]:
                    req.n_cached = start
                if hook is not None:
                    hook(req, start, rec["ids"][i].astype(np.int64),
                         rec["masked"][i].copy(), logits[i])
                # in order: a token streams once it and all before it
                # are unmasked; the row ends by the rule of
                # _maybe_finish, which is the device's too
                sent = len(req.prompt) + len(req.emitted) - start
                while sent < L and not masked[i][sent] and \
                        self._slots[i] is req:
                    tok = ids[i][sent]
                    sent += 1
                    self._emit(req, tok)
                    self._maybe_finish(i, tok)
                if (self._slots[i] is not req) != bool(rec["done"][i]):
                    raise RuntimeError(
                        "slot %d: the host and the device disagree on "
                        "whether the row has ended" % i)

    def _step_failed(self, exc):
        """A step (or speculative round) raised. The pool it was given
        was donated, so its buffers may be deleted, and what came back
        may be poisoned by the same fault: no row of it can be
        trusted. Every active sequence fails with the error, both
        pools are built anew, and the loop goes on to admit the queue
        into them: it never steps on the old buffers again. (A chunked
        prefill in flight owns a pool of its own and carries on.)"""
        self._log.error("decode step failed; failing %d active "
                        "sequence(s) and rebuilding the pool",
                        sum(r is not None for r in self._slots),
                        exc_info=exc)
        for slot, req in enumerate(self._slots):
            if req is not None:
                self._slots[slot] = None
                req._fail(exc)
        self._step_failures += 1
        self._inflight = None
        self._g_active.set(0)
        _telemetry.journal_event("serve.decode.step_failed",
                                 error=type(exc).__name__)
        self._aux = self._gen._fresh_aux()
        if self._draft is not None:
            self._daux = self._draft._fresh_aux()
        if self._diff:
            self._bstate = _fresh_block_state(
                self._B, self._diff["block_length"])
        self._publish_pool_gauges()

    def _publish_pool_gauges(self):
        """The gauges that can only change when a slot turns over,
        published where one is admitted or freed (not per step): the
        live pool's bytes per slot, and the compiled-program counts —
        the target's stays 1 across slot turnover (2 with a draft:
        the (B, 1) step plus the (B, γ+1) verify), the draft's stays
        1; admissions must never recompile (gate-fingerprinted)."""
        self._g_kv.set(self._kv_bytes_per_slot)   # live pool wins
        cache_size = getattr(self._step_fn, "_cache_size", None)
        if cache_size is not None:
            self._g_jit.set(cache_size())
        if self._draft is not None:
            cache_size = getattr(self._draft_step_fn, "_cache_size",
                                 None)
            if cache_size is not None:
                self._g_djit.set(cache_size())

    def _draft_forward(self, toks, pos):
        """One (B, 1) per-row-position DRAFT step: the propose half of
        a speculative round. Returns the (B, V) last-position logits
        as float32 numpy."""
        args = dict(self._draft._params)
        args["data"] = jnp.asarray(toks)
        args["positions"] = jnp.asarray(pos[:, None])
        args["cache_pos"] = jnp.asarray(pos)
        outs, self._daux = self._draft_step_fn(args, self._daux,
                                               self._rng0)
        self._draft_steps += 1
        self._c_dsteps.inc()
        return np.asarray(outs[0][:, -1].astype(jnp.float32))

    def _spec_round(self):
        """One speculative draft/verify round: γ compiled (B, 1) draft
        steps propose per-slot continuations, ONE (B, γ+1) target
        forward verifies them, and each row keeps its own longest-
        matching prefix plus the target's next token — per-row
        acceptance, lifting the eager path's lockstep rule.

        Exactness: the emission at index j is ALWAYS the target's own
        ``_pick`` on its logits for that index, with the request
        stream's (j+1)-th split; the draft proposed with the SAME sub
        (``_peek_subs`` — common random numbers), so "proposal
        accepted" literally means "equals what generate() would have
        picked". Byte-identity to the non-speculative path follows for
        greedy AND sampled requests, up to the verify forward's
        Tnew=γ+1 kernel-numerics caveat (generation.py,
        generate_speculative docstring).

        Cache discipline, per row: the verify forward writes γ+1
        entries at positions n_cached..n_cached+γ; the walk advances
        n_cached once per EMITTED token, so rejected entries sit past
        the row's depth where (a) the per-row mask keeps any
        correctly-conditioned query from attending them and (b) the
        next round's writes overwrite them before the row's depth
        reaches them. Same argument on the draft pool, which is why
        every admission pays γ headroom (``submit``'s _spec_cap
        check). Non-speculative rows ride the verify forward with
        junk tails and take only their column-0 pick — identical math
        to :meth:`_step`. Returns the round's ``serve.spec.round``
        phase attrs (rows, proposed, accepted)."""
        active = [i for i, s in enumerate(self._slots)
                  if s is not None]
        spec = [i for i in active if self._slots[i].speculative]
        g = self._gamma
        toks = np.zeros((self._B, 1), np.float32)
        pos0 = np.zeros((self._B,), np.float32)
        for i in active:
            toks[i, 0] = float(self._slots[i].pending)
            pos0[i] = float(self._slots[i].n_cached)
        # peek each sampled row's subs WITHOUT advancing its stream —
        # the verify walk's _pick() calls advance it, once per
        # emitted token, exactly like every other emission path
        subs = {i: self._slots[i]._peek_subs(g) for i in spec
                if self._slots[i].temperature > 0}

        # -- propose: γ (B, 1) draft steps -----------------------------
        props = np.zeros((self._B, g), np.int64)
        cur = np.zeros((self._B, 1), np.float32)
        dpos = np.zeros((self._B,), np.float32)
        for t in range(g):
            for i in spec:
                cur[i, 0] = toks[i, 0] if t == 0 else \
                    float(props[i, t - 1])
                dpos[i] = pos0[i] + t
            # non-speculative and empty rows feed token 0 at draft
            # position 0: their draft cache rows are garbage by
            # definition, and position 0 is always in capacity
            dl = self._draft_forward(cur, dpos)
            for i in spec:
                req = self._slots[i]
                if req.temperature > 0:
                    props[i, t] = int(np.asarray(_pick_token(
                        dl[i][None], req.temperature, req.top_k,
                        subs[i][t], req.top_p))[0])
                else:
                    props[i, t] = int(np.argmax(dl[i]))

        # -- verify: ONE (B, γ+1) target forward -----------------------
        chunk = np.zeros((self._B, g + 1), np.float32)
        for i in active:
            chunk[i, 0] = toks[i, 0]
            for t in range(g):
                # non-speculative rows repeat their pending token as a
                # junk tail; only their column-0 logits are read
                chunk[i, t + 1] = float(props[i, t]) if i in spec \
                    else toks[i, 0]
        args = dict(self._gen._params)
        args["data"] = jnp.asarray(chunk)
        args["positions"] = jnp.asarray(
            pos0[:, None] + np.arange(g + 1, dtype=np.float32)[None])
        args["cache_pos"] = jnp.asarray(pos0)
        outs, self._aux = self._step_fn(args, self._aux, self._rng0)
        logits = np.asarray(outs[0].astype(jnp.float32))  # (B,g+1,V)
        self._steps += 1
        self._c_steps.inc()
        self._verify_steps += 1
        self._c_vsteps.inc()
        self._spec_rounds += 1
        self._c_srounds.inc()
        self._h_slotfill.observe(len(active))
        self._g_active.set(len(active))

        # -- per-row acceptance walk -----------------------------------
        accepted = proposed = 0
        full = []          # rows needing the draft catch-up feed
        for i in active:
            req = self._slots[i]
            if i not in spec:
                # the _step() math, read off the verify forward
                req.n_cached += 1
                tok = req._pick(logits[i, 0])
                self._emit(req, tok)
                self._maybe_finish(i, tok)
                continue
            proposed += g
            acc = 0
            for j in range(g + 1):
                req.n_cached += 1
                tok = req._pick(logits[i, j])
                self._emit(req, tok)
                matched = j < g and int(props[i, j]) == tok
                if matched:
                    acc += 1
                self._maybe_finish(i, tok)
                if self._slots[i] is None or not matched:
                    break
            accepted += acc
            self._h_accept.observe(acc / g)
            if acc == g and self._slots[i] is not None:
                full.append(i)
        self._spec_proposed += proposed
        self._spec_accepted += accepted
        self._c_proposed.inc(proposed)
        self._c_accepted.inc(accepted)

        if full:
            # full acceptance: the draft never ingested its last
            # proposal's k/v (its loop stops after computing it) —
            # one conditional catch-up step fills the hole at the
            # row's old pos0+γ. Rows that were speculative this round
            # but are not catching up write JUNK at their own pos0+γ
            # (inside the γ headroom, past their valid prefix, and
            # overwritten by a later feed before any correctly-
            # conditioned query can attend it — NEVER at position 0,
            # which holds their live prompt k/v); non-speculative and
            # empty rows write at their garbage rows' position 0
            for i in range(self._B):
                if i in full:
                    cur[i, 0] = float(props[i, g - 1])
                    dpos[i] = pos0[i] + g
                elif i in spec:
                    cur[i, 0] = 0.0
                    dpos[i] = pos0[i] + g
                else:
                    cur[i, 0] = 0.0
                    dpos[i] = 0.0
            self._draft_forward(cur, dpos)
        return {"rows": len(spec), "proposed": proposed,
                "accepted": accepted}

    def _chunk_step(self):
        """Feed ONE chunk of the in-progress chunked prefill — called
        once per loop iteration between admission and the (B, 1) step,
        so active sessions pay one chunk-width forward per token
        instead of the whole prompt at once. Chunk forwards ride the
        Generator's ordinary shared-position graph at the pool's
        BOTTOM RUNG of rows (:func:`_row_rungs`: a chunk is one
        prompt's, so one row wherever no mesh splits the batch), on
        the prompt's own state of that many rows, which the final
        merge installs from: one XLA program per chunk width at that
        rung (the ragged final chunk adds at most one more) and none
        at the pool's width; the per-row (B, 1) step's jit cache never
        moves. The math is bit-identical to the monolithic prefill:
        every forward attends the full masked cache buffer, so
        splitting the query axis changes no reduction a kept position
        sees, and rows are independent."""
        ch = self._chunking
        if ch is None:
            return
        req, slot = ch["req"], ch["slot"]
        P = len(req.prompt)
        lo = ch["pos"]
        hi = min(lo + prefill_chunk(), P)
        rows = self._group_rows([req.prompt[lo:hi]])
        with _trace.phase("serve.decode.prefill_chunk", parent=req.tc,
                          slot=slot, lo=lo, hi=hi, run=len(rows)):
            try:
                logits, ch["aux"] = self._gen._forward(
                    ch["aux"], rows, lo)
                if "daux" in ch:
                    _, ch["daux"] = self._draft._forward(
                        ch["daux"], rows, lo)
            except Exception as exc:      # noqa: BLE001 — the future
                # is this sequence's one response; a failed chunk must
                # not kill the decode loop for every other slot
                self._chunking = None
                self._reserved.discard(slot)
                req._fail(exc)
                return
        self._prefill_rows += len(rows)
        self._chunks += 1
        self._chunk_rows += len(rows)
        ch["pos"] = hi
        self._c_chunks.inc()
        if hi < P:
            return
        # final chunk: merge the fully-prefilled row into the pool
        # (the monolithic path's compiled merge) and emit the first
        # token
        self._aux = self._merge_rows(self._aux, ch["aux"], [slot])
        if "daux" in ch:
            self._daux = self._merge_rows(self._daux, ch["daux"],
                                          [slot], draft=True)
            self._draft_prefills += 1
            self._c_dprefills.inc()
        self._prefills += 1
        last = _last_rows(logits)
        self._chunking = None
        self._reserved.discard(slot)
        self._slots[slot] = req
        req.t_admit = _telemetry.now_ms()
        req.n_cached = P
        self._publish_pool_gauges()
        tok = req._pick(last[0])
        self._emit(req, tok)
        self._maybe_finish(slot, tok)

    def _speculating(self):
        return self._draft is not None and any(
            s is not None and s.speculative for s in self._slots)

    def _nothing_to_do(self):
        return not self._queue and \
            not self._evac_waiters and \
            not self._evac_flag and \
            self._chunking is None and \
            self._inflight is None and \
            all(s is None for s in self._slots)

    def _loop(self):
        # the hoisted handle: the one place this thread resolves
        # MXNET_TRACE — every phase below reads the module flag only
        _trace.tracer()
        while True:
            with self._cond:
                if self._nothing_to_do():
                    # one phase per idle period, the drained exit
                    # included — so every decoder that closes shows
                    # the name at least once (a gate fingerprint must
                    # not depend on who won the race to the queue)
                    with _trace.phase("serve.decode.idle"):
                        while self._nothing_to_do() and \
                                not self._draining:
                            self._cond.wait(0.05)
                if self._draining and self._nothing_to_do():
                    break
            if self._evac_waiters or self._evac_flag:
                self._do_evacuate()
                self._hand_off()
                continue
            self._admit()
            self._hand_off()
            self._chunk_step()
            self._hand_off()
            try:
                if self._speculating():
                    # a round's inputs are the host's: the step in
                    # flight is read first (and may end the row that
                    # asked for the round)
                    self._read_inflight()
                if self._speculating():
                    with _trace.phase("serve.spec.round") as ph:
                        ph.note(**self._spec_round())
                else:
                    # draft-less pools and rounds with no speculative
                    # participant run the ordinary (B, 1) step — a
                    # mixed-traffic pool flips between the two
                    # compiled target programs, never compiles a third
                    self._step()
            except Exception as exc:      # noqa: BLE001 — the loop
                # serves every later request; see _step_failed
                self._step_failed(exc)
            self._hand_off()
        self._handoffs.put(None)
        self._g_active.set(0)
        _telemetry.journal_event("serve.decode.stop")

    # -- migration ----------------------------------------------------------
    def evacuate(self, timeout=30.0):
        """Export every active session off the pool: each in-flight
        generate's future fails with :class:`SessionEvacuated`
        carrying its :meth:`export_session` state (the wire handler
        turns that into an ``evacuated`` reply the fleet router
        resumes on a survivor); queued-but-unadmitted requests fail
        with ``EngineClosed`` and replay from scratch. The export runs
        on the decode loop thread (the pool's one aux mutator); this
        call blocks until it completes and returns the number of
        sessions exported. The pool itself stays OPEN — a
        config-reload recycle re-warms and readmits this replica —
        so a migrating recycle is bounded by export+import cost, not
        by its longest sequence."""
        ev = threading.Event()
        out = []
        with self._cond:
            if self._closed:
                raise EngineClosed("decoder is closed")
            self._evac_waiters.append((ev, out))
            self._cond.notify_all()
        if not ev.wait(timeout):
            raise RequestTimeout(
                "evacuation still pending after %.3fs" % timeout)
        return out[0]

    def _request_evacuate(self):
        # SIGTERM-handler context (guardrail.GracefulShutdown): set
        # the flag only — no locks, no telemetry, no XLA. The decode
        # loop notices within one 0.05s cond-wait tick.
        self._evac_flag = True

    def _do_evacuate(self):
        """Runs ON the decode loop thread: export + fail every active
        slot, reject the queue, wake the evacuate() waiters. A SIGTERM
        evacuation (``_evac_flag``) also drains the pool — the process
        is ending, so there is nothing to readmit for."""
        t0 = _telemetry.now_ms()
        with self._cond:
            waiters, self._evac_waiters = self._evac_waiters, []
            sig, self._evac_flag = self._evac_flag, False
            if sig:
                self._draining = True
            queued = list(self._queue)
            self._queue.clear()
        if not self._diff:
            try:
                # what is exported is what the host has read
                self._read_inflight()
            except Exception as exc:      # noqa: BLE001 — as in _loop
                self._step_failed(exc)
        n = 0
        for slot in range(self._B):
            req = self._slots[slot]
            if req is None:
                continue
            try:
                state = self.export_session(slot)
            except Exception as exc:      # noqa: BLE001 — the future
                # is this sequence's one response; a failed export
                # must surface there, not kill the loop
                self._slots[slot] = None
                req._fail(exc)
                continue
            self._slots[slot] = None
            req._fail(SessionEvacuated(state))
            n += 1
        ch, self._chunking = self._chunking, None
        if ch is not None:
            # a half-prefilled prompt has no portable session yet
            # (no emitted token, partial cache) — prefill is pure, so
            # it replays from scratch exactly like a queued request
            self._reserved.discard(ch["slot"])
            queued.append(ch["req"])
        for req in queued:
            req._fail(EngineClosed(
                "evacuated before admission — replay the request on "
                "another replica"))
        if self._diff:
            # every row was let go: the device's rows go with them,
            # and the step in flight is theirs alone
            self._inflight = None
            self._bstate = _fresh_block_state(
                self._B, self._diff["block_length"])
        self._evacuated += n
        if n:
            self._c_evacuated.inc(n)
        self._g_active.set(0)
        self._publish_pool_gauges()
        _telemetry.journal_event(
            "serve.decode.evacuate", sessions=n, queued=len(queued),
            sigterm=bool(sig),
            ms=round(_telemetry.now_ms() - t0, 3))
        for ev, out in waiters:
            out.append(n)
            ev.set()

    # -- lifecycle ----------------------------------------------------------
    @property
    def draining(self):
        return self._draining or self._closed

    def close(self, timeout=None):
        """Drain: admitted sequences decode to completion, new
        submissions raise EngineClosed, then the loop thread exits.
        ``timeout=None`` reads ``MXNET_DECODE_DRAIN_TIMEOUT`` (the
        router's recycle of a decode replica budgets its drain from
        the same knob — one drain clock, not a hardcoded 60 here and
        a knob everywhere else)."""
        if timeout is None:
            timeout = drain_timeout()
        with self._cond:
            already = self._closed
            self._draining = True
            pending = len(self._queue)
            self._cond.notify_all()
        if not already:
            _telemetry.journal_event("serve.decode.drain",
                                     pending=pending)
        self._thread.join(timeout)
        if not self._thread.is_alive():
            # the loop's last hand-off is the relay's last
            self._relay_thread.join(timeout)
        self._closed = True
        if self._shutdown is not None:
            self._shutdown.uninstall()
            self._shutdown = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def stats(self):
        return {"admitted": self._admitted, "finished": self._finished,
                "shed": self._shed,
                "steps": self._steps, "prefills": self._prefills,
                "admit_rounds": self._admit_rounds,
                "prefill_rows": self._prefill_rows,
                "merges": self._merges,
                "chunks": self._chunks,
                "chunk_rows": self._chunk_rows,
                "step_failures": self._step_failures,
                "forwards": self._forwards,
                # blocks stored, each by the first denoising forward
                # of the block after it; no forward of this pool is a
                # commit and nothing else (the key stays for its
                # readers)
                "commit_forwards": 0,
                "fused_commits": self._fused_commits,
                "blocks_committed": self._fused_commits,
                "tokens_unmasked": self._tokens_unmasked,
                "steps_ahead": self._steps_ahead,
                # prefill programs of the pool's generator (and its
                # draft's) in which _attend split a kv head's rows
                "attend_split_programs": sum(
                    g.attend_split_programs
                    for g in (self._gen, self._draft) if g is not None),
                "idle_forwards": self._idle_forwards,
                "moe_assignments": self._moe_assignments,
                "moe_pairs_here": self._moe_pairs_here,
                "moe_experts_hit": self._moe_experts_hit,
                "moe_max_load": self._moe_max_load,
                "dsa_keys_visible": self._dsa_keys_visible,
                "dsa_keys_selected": self._dsa_keys_selected,
                "dsa_keys_computed": self._dsa_keys_computed,
                "merge_programs": sum(
                    fn._cache_size() for fn in
                    (self._merge_fn, self._dmerge_fn)
                    if fn is not None),
                "imported": self._imported, "resumed": self._resumed,
                "evacuated": self._evacuated,
                "deduped": self._deduped,
                "streams": self._streams,
                # times the loop handed noted tokens to the relay, and
                # frames the relay left to a stream's own thread
                # because its connection did not take a write whole
                "stream_handoffs": self._stream_handoffs,
                "stream_frames_late": self._stream_frames_late,
                "spec_rounds": self._spec_rounds,
                "draft_steps": self._draft_steps,
                "spec_proposed": self._spec_proposed,
                "spec_accepted": self._spec_accepted,
                "draft_prefills": self._draft_prefills,
                "active": sum(s is not None for s in self._slots),
                "queued": len(self._queue),
                "bytes_per_slot": dict(self._bytes_by_kind)}

    def introspect(self):
        """Live state for the ``stats`` introspection frame
        (serve/net.py answers it for ANY engine-like object): slot
        headroom and queue depth. ``decode_free_slots`` is the signal
        the fleet router's session placement consumes — a new decode
        session goes to the replica with the most free slots
        (serve/router.py)."""
        out = self.stats()
        out["queue_depth"] = out.pop("queued")
        out["in_flight"] = out["active"] + out["queue_depth"]
        out["decode_free_slots"] = (self._B - out["active"]
                                    - len(self._reserved))
        out["slots"] = self._B
        out["streams_in_flight"] = self._streams_inflight
        out["speculative"] = self._draft is not None
        out["draining"] = self.draining
        return out
