"""Autoregressive generation — KV-cache decoding for the transformer LM.

New TPU-native capability: the 2017 reference's incremental-inference
story was RNNCell step-wise unrolling (`rnn/rnn_cell.py` begin_state /
__call__ chains); the transformer analogue is a KV cache threaded as
auxiliary state through `models.transformer.get_decode_symbol`'s graph
(`ops/attention.py` `_contrib_CachedAttention`).

Design: two jit specializations, bucketing-style — one for the prefill
chunk (B, P) and one for the single-token step (B, 1) — each a whole
-graph XLA program with the caches as aux arrays kept on device
between steps. Sampling (greedy / temperature / top-k) runs on device
too; only the chosen token ids come back to the host.

What is donated where: this module's ``generator_step`` takes its
caches UNDONATED (several loops here read ``aux`` after the call, and
a prefill runs on a fresh pool of its own). The serving pool of
``serve/decode.py`` is donated to every program that updates it —
``decode_step`` / ``draft_step``, ``cache_merge`` and the import
scatter — which write it in place; the decode loop rebinds it at once.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import telemetry as _telemetry
from .executor import _graph_eval_fn
from .models import transformer
from .ops import attention as _attention

__all__ = ["Generator", "kv_blob_nbytes", "replay_key",
           "canon_diffusion", "unmask_choice", "block_picks"]

REMASKING = ("sequential", "low_confidence_static",
             "low_confidence_dynamic")


def canon_diffusion(diffusion):
    """``Generator(diffusion=...)`` as a plain dict with every key:
    ``block_length`` L and ``mask_id`` (required), ``steps`` T a block
    (default L: one token a forward; L must be a multiple of it),
    ``remasking`` (one of REMASKING, default "low_confidence_static"),
    ``threshold`` (0.9; "low_confidence_dynamic" reads it). None stays
    None."""
    if diffusion is None:
        return None
    d = dict(diffusion)
    unknown = set(d) - {"block_length", "mask_id", "steps", "remasking",
                        "threshold"}
    if unknown or "block_length" not in d or "mask_id" not in d:
        raise ValueError(
            "diffusion=dict(block_length=, mask_id=[, steps=, "
            "remasking=, threshold=]), got %r" % (diffusion,))
    L = int(d["block_length"])
    T = int(d.get("steps") or L)
    rule = d.get("remasking", "low_confidence_static")
    if L < 1 or T < 1 or L % T:
        raise ValueError("diffusion: block_length (%d) must be a "
                         "positive multiple of steps (%d)" % (L, T))
    if rule not in REMASKING:
        raise ValueError("diffusion: remasking must be one of %r, got "
                         "%r" % (REMASKING, rule))
    return {"block_length": L, "mask_id": int(d["mask_id"]), "steps": T,
            "remasking": rule,
            "threshold": float(d.get("threshold", 0.9))}


def block_picks(logits):
    """What a denoising forward hands back in place of its logits:
    each position's best id (int32) and that id's probability,
    ``max softmax`` in float32 — on the device, so a step returns
    2 x (B, L) numbers and not (B, L, V)."""
    lf = logits.astype(jnp.float32)
    top = lf.max(axis=-1)
    conf = 1.0 / jnp.exp(lf - top[..., None]).sum(axis=-1)
    return jnp.argmax(lf, axis=-1).astype(jnp.int32), conf


def unmask_choice(masked, conf, diffusion, xp=np):
    """The positions of a block to unmask after a denoising forward:
    masked (..., L) bool, conf (..., L) float -> (..., L) bool, a
    subset of ``masked`` (none of a block with no mask left).
    "sequential": the leftmost L / T masked positions.
    "low_confidence_static": the L / T of highest confidence (a tie
    goes to the left). "low_confidence_dynamic": every one whose
    confidence is over the threshold, and the most confident one where
    none is. Fewer remain than L / T: all of them. An unmasked
    position is never masked again.

    ONE statement of the rule for the host and the device: ``xp`` is
    ``numpy`` (``generate()``'s loop) or ``jax.numpy`` (the serving
    pool's ``block_step``, every row at once), so the shapes never
    follow the data; on float32 confidences the two agree bit for
    bit."""
    masked = xp.asarray(masked, bool)
    conf = xp.where(masked, xp.asarray(conf), -xp.inf)
    per_step = diffusion["block_length"] // diffusion["steps"]
    rule = diffusion["remasking"]
    at = xp.arange(masked.shape[-1])
    if rule == "sequential":
        ahead = xp.cumsum(masked, axis=-1) - masked    # masked, to the left
    elif rule == "low_confidence_static":
        # positions that go first: a higher confidence, or the same
        # one further left
        ci, cj = conf[..., :, None], conf[..., None, :]
        ahead = ((cj > ci) | ((cj == ci) & (at < at[:, None]))).sum(-1)
    else:
        over = masked & (conf > diffusion["threshold"])
        best = masked & (at == xp.argmax(conf, axis=-1)[..., None])
        return xp.where(over.any(-1, keepdims=True), over, best)
    return masked & (ahead < per_step)


def kv_blob_nbytes(blob):
    """Payload bytes of an :meth:`Generator.export_kv_rows` blob — the
    cache-row arrays only (framing/pickle overhead excluded), the
    figure the ``serve.prefill.blob_bytes`` histogram and the disagg
    bench's int8-vs-bf16 ratio report."""
    return sum(int(a.nbytes) for a in blob["rows"].values())


class Generator:
    """Drives `transformer.get_decode_symbol` with params from a trained
    `transformer.get_symbol` checkpoint (same parameter names).

    Parameters
    ----------
    arg_params : dict name -> array-like (NDArray, np or jnp)
        Trained parameters (e.g. `Module.get_params()[0]` or
        `load_checkpoint`'s arg_params).
    vocab_size, num_layers, num_heads, dim, ffn_hidden :
        Architecture — must match the training symbol.
    max_len : int
        KV-cache capacity (prompt + generated tokens must fit). With
        SSM layers (block_type) the state itself is O(1), but max_len
        still bounds total sequence length — it sizes the attention
        layers of a mixed stack and the learned position table.
    block_type : "attention" (default), "ssm", "mamba2", or per-layer
        sequence — SSM layers hold one (num_heads, head_dim, head_dim)
        f32 state blob per slot instead of (max_len, head_dim) KV rows
        (see ops/ssm.py and models/transformer.get_decode_symbol for
        knob composition rules). "mamba2" layers (ops/mamba2.py), sized
        by ``mamba2=dict(num_heads=, head_dim=, d_state=[, d_conv=,
        chunk=])``, hold TWO blobs per slot: a (d_conv-1, conv_dim)
        convolution window in the cache dtype and a
        (heads, head_dim, d_state) f32 scan state.
    norm, norm_eps, ffn, use_bias, tie_embeddings,
    embedding_multiplier, residual_multiplier, logits_scaling,
    attention_scale :
        Architecture, as get_decode_symbol documents them; the
        defaults are the OPT-style block. With ``tie_embeddings`` the
        head is ``tok_embed_weight`` itself: one array on the device.
    batch_size : int
    dtype : optional compute dtype for params/caches (e.g. "bfloat16").
    mesh : optional jax.sharding.Mesh for multi-chip serving. Params
        place by the TP rule (`parallel.sharding.param_sharding`:
        Megatron column-parallel weights over a 'model' axis, experts
        over 'expert'), KV caches shard heads over 'model' and batch
        over 'data'; GSPMD inserts the collectives.
    num_experts, experts_per_token, expert_hidden, norm_topk_prob,
    head_dim, qk_norm, rope_base :
        Architecture, as get_decode_symbol documents them: routed
        expert layers (top-k, nothing dropped), a head size apart from
        dim / num_heads, per-head RMS norm of q and k, the rotary base.
    layer_kinds : optional per-layer sequence spelling the stack one
        sublayer a layer ("attention" | "ssm" | "mamba2" | "shortconv"
        | "experts" | "mlp"; get_decode_symbol); ``num_layers`` is then
        its length. A layer of the last two kinds owns no decode state:
        a slot's bytes count the layers that hold some. A "shortconv"
        layer (ops/shortconv.py, ``shortconv_kernel`` taps) holds ONE
        blob per slot, a (shortconv_kernel - 1, dim) window of gated
        rows in the cache dtype, and nothing with a length axis. An
        "mla" layer (ops/mla.py: latent attention over a learned
        selection of keys), sized by ``mla=dict(q_lora_rank=,
        kv_lora_rank=, qk_nope_head_dim=, qk_rope_head_dim=,
        v_head_dim=, index_heads=, index_head_dim=, index_topk=)``,
        holds TWO arrays of rows per slot, each with a width of its
        own: latent rows (max_len, kv_lora_rank + qk_rope_head_dim)
        and index-key rows (max_len, index_head_dim), in the cache
        dtype: a third kind of rows beside k/v rows and their rolling
        twins, exported, imported and merged as those are.
    expert_scoring, norm_topk_eps, routed_scaling_factor,
    expert_latent, shared_expert_hidden, experts_held :
        The expert layers' routing ("softmax" | "sigmoid" with a
        choosing bias, kept in float32, and what its renormalisation
        adds to the sum it divides by), the weights' scale, latent
        experts between one down- and one up-projection, a shared
        expert, and the chip's share ``(first, count)`` of the
        ``num_experts`` routed over — as get_decode_symbol documents
        them.
    diffusion : optional dict — generation by diffusion over blocks.
        ``dict(block_length=L, mask_id=, steps=T, remasking=,
        threshold=)`` (:func:`canon_diffusion`). Attention takes the
        block mask (position i sees j iff floor(j/L) <= floor(i/L)),
        for the prompt too. :meth:`generate` then prefills the
        prompt's whole blocks and fills one block of L positions at a
        time: the block starts as the prompt's remainder followed by
        ``mask_id``; each denoising forward over it predicts every
        masked position's OWN token (no shift) and unmasks some by the
        ``remasking`` rule (:func:`unmask_choice`); when no mask is
        left one commit forward over the clean block stores its keys
        and values — T + 1 forwards a block, the commit apart — and
        the next block begins (the plain path: the serving decoder's
        step carries the clean block beside the next one, T forwards a
        block, and is held to these rows token for token). Greedy
        only; the sampling, beam, speculative and scoring entry points
        refuse it.
    """

    def __init__(self, arg_params, vocab_size, max_len, num_layers=2,
                 num_heads=4, dim=128, ffn_hidden=None, batch_size=1,
                 dtype=None, num_experts=0, mesh=None, quantize=None,
                 pos_encoding="learned", attention_window=0,
                 rolling_cache=False, num_kv_heads=None,
                 quantize_kv=False, block_type="attention",
                 norm="layer", norm_eps=1e-5, ffn="relu", use_bias=True,
                 tie_embeddings=False, embedding_multiplier=1.0,
                 residual_multiplier=1.0, logits_scaling=1.0,
                 attention_scale=None, mamba2=None,
                 experts_per_token=1, expert_hidden=None,
                 norm_topk_prob=False, head_dim=None, qk_norm=False,
                 rope_base=None, diffusion=None, layer_kinds=None,
                 expert_scoring="softmax", routed_scaling_factor=1.0,
                 expert_latent=0, shared_expert_hidden=0,
                 experts_held=None, shortconv_kernel=3,
                 norm_topk_eps=None, attention_layers=None,
                 parallel_block=False, mla=None):
        from .parallel import sharding as shd

        if quantize not in (None, "int8"):
            raise ValueError("quantize must be None or 'int8', got %r"
                             % (quantize,))
        if quantize_kv and rolling_cache:
            raise ValueError("quantize_kv is not supported with "
                             "rolling_cache")
        self.vocab_size = int(vocab_size)
        if self.vocab_size > 2 ** 24:
            # token ids ride the float32 "data" input convention;
            # integers past 2^24 stop being exactly representable and
            # would silently alias (positions get the same guard in
            # _forward)
            raise ValueError(
                "vocab_size=%d exceeds the float32-exact id range "
                "(2^24); larger vocabularies need integer id plumbing"
                % self.vocab_size)
        self.max_len = int(max_len)
        self.batch_size = int(batch_size)
        self.num_layers = int(num_layers)
        self.mesh = mesh
        self._window = int(attention_window or 0)
        self._rolling = bool(rolling_cache)
        head_dim = int(head_dim or dim // num_heads)
        kv_heads = int(num_kv_heads or num_heads)
        self._diffusion = canon_diffusion(diffusion)
        if self._diffusion and (rolling_cache or quantize_kv or
                                attention_window):
            raise ValueError("diffusion is built for the plain cache "
                             "(no rolling_cache, quantize_kv or "
                             "attention_window)")
        # block_type validation happens in get_decode_symbol below;
        # the flags steer slot-state accounting and the serving-layer
        # compatibility refusals (speculative drafts, prefill grouping)
        if layer_kinds is not None:
            layer_kinds = tuple(layer_kinds)
            num_layers = self.num_layers = len(layer_kinds)
            self._btypes = transformer._mixer_kinds(layer_kinds)
        else:
            self._btypes = transformer._canon_block_types(block_type,
                                                          num_layers)
        mamba2 = transformer._canon_mamba2(mamba2, self._btypes)
        mla = transformer._canon_mla(mla, self._btypes)
        if mla and self._diffusion:
            raise ValueError("diffusion is built for the plain cache "
                             "(an 'mla' layer takes no block mask)")
        # latent attention over selected keys: what speculation is not
        # yet held to (_refuse_latent)
        self._latent = bool(mla)
        # attention that differs by layer: a rolling layer's circular
        # buffer is sized here where the caller left it to us, and
        # _rings keeps each one's (rows, window) by its aux prefix
        self._rings = {}
        if attention_layers is not None:
            attention_layers, self._rings = self._size_rings(
                attention_layers, layer_kinds or self._btypes)
        # any circular cache, however spelled: what speculation and a
        # padded remote prefill refuse
        self._wraps = self._rolling or bool(self._rings)
        # "ssm" here means RECURRENT: any layer whose state has no
        # per-position entries (gated linear attention, Mamba-2 or a
        # gated short convolution's window) — what speculation cannot
        # roll back and a padded prefill would absorb, so every
        # refusal and split keyed on it covers them all
        self._has_ssm = bool(set(transformer._RECURRENT) &
                             set(self._btypes))
        # kept for twin-symbol builders (serve/decode.py rebuilds this
        # graph with per_row_pos=True against the SAME parameters)
        self._decode_opts = dict(
            vocab_size=vocab_size, max_len=max_len,
            num_layers=num_layers, num_heads=num_heads, dim=dim,
            ffn_hidden=ffn_hidden, num_experts=num_experts,
            quantized=quantize is not None,
            compute_dtype=str(dtype) if dtype else None,
            pos_encoding=pos_encoding,
            attention_window=attention_window,
            rolling_cache=rolling_cache, num_kv_heads=num_kv_heads,
            kv_quantize=quantize_kv, block_type=block_type,
            norm=norm, norm_eps=norm_eps, ffn=ffn, use_bias=use_bias,
            tie_embeddings=tie_embeddings,
            embedding_multiplier=embedding_multiplier,
            residual_multiplier=residual_multiplier,
            logits_scaling=logits_scaling,
            attention_scale=attention_scale, mamba2=mamba2,
            experts_per_token=experts_per_token,
            expert_hidden=expert_hidden, norm_topk_prob=norm_topk_prob,
            head_dim=head_dim, qk_norm=qk_norm, rope_base=rope_base,
            attention_block=self._diffusion["block_length"]
            if self._diffusion else 0, layer_kinds=layer_kinds,
            expert_scoring=expert_scoring,
            routed_scaling_factor=routed_scaling_factor,
            expert_latent=expert_latent,
            shared_expert_hidden=shared_expert_hidden,
            experts_held=experts_held,
            shortconv_kernel=shortconv_kernel,
            norm_topk_eps=norm_topk_eps,
            attention_layers=attention_layers,
            parallel_block=parallel_block, mla=mla)
        sym = transformer.get_decode_symbol(**self._decode_opts)
        if quantize:
            arg_params = _quantize_weights(
                arg_params, sym.list_arguments())
        self._sym = sym
        graph_fn = _graph_eval_fn(sym, mesh=mesh)
        # forwards (one a compiled prefill program) in whose trace
        # _attend took blocks of rows inside a kv head
        self.attend_split_programs = 0

        def eval_fn(args, aux, rng, train):
            # runs where a program is traced, never where one is called
            before = _attention.split_traces()
            out = graph_fn(args, aux, rng, train)
            self.attend_split_programs += \
                _attention.split_traces() > before
            return out

        self._eval_fn = eval_fn

        def generator_step(args, aux, rng):
            # named, not a lambda: PjitFunction(generator_step) and the
            # device's module name say which program ran
            return eval_fn(args, aux, rng, False)

        self._step_fn = jax.jit(generator_step)
        self._loop_cache = {}

        def block_prefill(args, aux, rng):
            # a diffusion prefill reads no logits (the first tokens
            # come from the first block's denoising forward): only the
            # caches come back, so the final norm and the head, dead
            # code here, are never computed
            return eval_fn(args, aux, rng, False)[1]

        self._prefill_fn = jax.jit(block_prefill)

        def _raw(name, v):
            arr = jnp.asarray(getattr(v, "_data", v))
            # int8 weights and their f32 scales keep their dtypes (the
            # whole point of quantize= is the int8 HBM footprint), and
            # so does a router's score-correction bias: it decides
            # between near-tied float32 scores
            if dtype and jnp.issubdtype(arr.dtype, jnp.floating) and \
                    not name.endswith(("_scale", "_score_bias")):
                arr = arr.astype(dtype)
            if mesh is not None:
                arr = jax.device_put(
                    arr, shd.param_sharding(mesh, name, arr.shape))
            return arr

        wanted = set(sym.list_arguments())
        self._params = {k: _raw(k, v) for k, v in arg_params.items()
                        if k in wanted}
        # cache placement: batch over 'data', heads over 'model' —
        # a row holds its kv heads side by side, so splitting the row
        # axis (Hkv*hd, or Hkv for the int8 scales) splits the heads
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            spec = [None, None, None]
            if "data" in mesh.axis_names and \
                    batch_size % mesh.shape["data"] == 0:
                spec[0] = "data"
            if "model" in mesh.axis_names and \
                    kv_heads % mesh.shape["model"] == 0:
                spec[2] = "model"
            self._cache_sharding = NamedSharding(mesh, P(*spec))
        else:
            self._cache_sharding = None
        missing = wanted - set(self._params) - {
            "data", "positions", "cache_pos"}
        if missing:
            raise ValueError("Generator missing parameters: %s"
                             % sorted(missing))
        self._pos_rows = None
        if pos_encoding == "learned":
            pos_rows = self._params["pos_embed_weight"].shape[0]
            self._pos_rows = int(pos_rows)
            if not self._rolling and pos_rows < self.max_len:
                # the decode symbol's position lookup is
                # take(mode='clip'); without this check, positions past
                # the trained table would silently reuse its last row
                raise ValueError(
                    "max_len=%d exceeds the trained position table "
                    "(%d rows) — generation past it would silently "
                    "clip" % (self.max_len, pos_rows))
        # cache dtype follows the FLOAT params — under quantize="int8"
        # the dict also holds int8 weights, and an int8 cache would
        # silently truncate k/v (cached_attention casts to cache dtype)
        cache_dtype = jnp.dtype(dtype) if dtype else next(
            v.dtype for v in self._params.values()
            if jnp.issubdtype(v.dtype, jnp.floating))
        # GQA: caches hold only the kv heads (the memory win), one
        # token's heads side by side in one row (ops/attention.py::
        # cached_attention); the int8 scales are a value a head
        self._kv_heads = kv_heads
        self._cache_shape = (self.batch_size, self.max_len,
                             kv_heads * head_dim)
        self._cache_dtype = cache_dtype
        # SSM layers: one (B, H, hd, hd) recurrent-state blob each,
        # ALWAYS f32 regardless of compute dtype — the bit-identical-
        # state rule (ops/ssm.py) is stated in f32, and the blob is so
        # small (no length axis) that a bf16 diet would save ~nothing
        self._state_shape = (self.batch_size, int(num_heads),
                             head_dim, head_dim)
        # Mamba-2 layers: a convolution window in the cache dtype (it
        # holds projection outputs as computed) and an f32 scan state
        # (a running sum over the whole sequence: ops/mamba2.py)
        self._conv_shape = self._scan_shape = None
        if mamba2:
            H, P, N = (mamba2[k] for k in ("num_heads", "head_dim",
                                           "d_state"))
            self._conv_shape = (self.batch_size, mamba2["d_conv"] - 1,
                                H * P + 2 * mamba2["n_groups"] * N)
            self._scan_shape = (self.batch_size, H, P, N)
        # gated short convolutions: a window of the last gated rows in
        # the cache dtype, and nothing else (ops/shortconv.py)
        self._short_shape = (self.batch_size, int(shortconv_kernel) - 1,
                             int(dim)) \
            if "shortconv" in self._btypes else None
        # latent attention over selected keys: a position's latent and
        # its shared rotary key in one row, and the indexer's key in
        # another (ops/mla.py): rows like k/v rows, each array with a
        # width of its own
        self._row_widths = {}
        if mla:
            self._row_widths = {
                "_latent_cache": mla["kv_lora_rank"] +
                mla["qk_rope_head_dim"],
                "_index_cache": mla["index_head_dim"]}
        # quantize_kv: k/v live int8 with per-token f32 scale caches —
        # halves decode's dominant HBM stream (the cache is re-read
        # every step; each weight only once)
        self._quantize_kv = bool(quantize_kv)
        # static sizing gauge: bytes of decode state one batch row
        # (= one serving slot) owns across the whole aux pytree,
        # whatever its kind — KV rows, int8 KV + scales, or SSM state
        # blobs. ContinuousDecoder re-publishes the same gauge from
        # its live pool, and the MXNET_DECODE_SLOTS sizing hint
        # divides an HBM budget by it (shape math only, no allocation)
        _telemetry.gauge("serve.decode.kv_bytes_per_slot").set(
            self.state_bytes_per_slot())

    def _aux_spec(self, name):
        """(shape, dtype) of one decode-state aux — THE single
        classification _fresh_aux (allocation), kv_cache_bytes
        (sizing) and _aux_row_shape (export/import) read, so the
        gauge/slot math can never drift from what is actually
        allocated."""
        width = self._own_width(name)
        if width:
            # latent or index-key rows: max_len of them, their own width
            return (self._cache_shape[:2] + (width,),
                    jnp.dtype(self._cache_dtype))
        if name.endswith("_conv_state"):
            # a convolution window (Mamba-2's over x|B|C, or a gated
            # short convolution's over dim): fixed size, served dtype
            return (self._short_shape
                    if name.endswith("_shortconv_conv_state")
                    else self._conv_shape), jnp.dtype(self._cache_dtype)
        if name.endswith("_scan_state"):
            # Mamba-2 scan state: fixed size, always f32
            return self._scan_shape, jnp.dtype(jnp.float32)
        if name.endswith("_state"):
            # SSM recurrent state: fixed-size blob, no length axis
            return self._state_shape, jnp.dtype(jnp.float32)
        if name.endswith(("_k_scale", "_v_scale")):
            # per-token dequant scales for the int8 caches
            return (self._cache_shape[:2] + (self._kv_heads,),
                    jnp.dtype(jnp.float32))
        if self._quantize_kv:
            return self._cache_shape, jnp.dtype(jnp.int8)
        ring = self._ring_of(name)
        if ring:
            # a rolling layer's circular buffer: its own row count
            B, _, width = self._cache_shape
            return (B, ring[0], width), jnp.dtype(self._cache_dtype)
        return self._cache_shape, jnp.dtype(self._cache_dtype)

    def _own_width(self, name):
        """The row width of an aux that keeps rows of its own width
        (an "mla" layer's latent and index-key rows), else None."""
        return next((w for suffix, w in self._row_widths.items()
                     if name.endswith(suffix)), None)

    def _wire_heads(self, name):
        """How many heads a row of aux ``name`` holds side by side, for
        the head-major wire format: the kv heads, or ONE for rows that
        every head shares (latent and index-key rows)."""
        return 1 if self._own_width(name) else self._kv_heads

    def _size_rings(self, attention_layers, kinds):
        """(attention_layers with every rolling entry's ``rows``
        filled in, {aux prefix: (rows, window)}). A buffer the caller
        did not size holds one window and the widest forward a prompt
        is fed by: ``window + MXNET_PREFILL_CHUNK - 1`` rows rounded up
        to 8, at most ``max_len``; with chunked prefill off a prompt
        arrives whole, and only ``max_len`` rows hold that."""
        from . import config as _config
        chunk = int(_config.get("MXNET_PREFILL_CHUNK") or 0)
        layers = [i for i, k in enumerate(kinds) if k == "attention"]
        out, rings = [], {}
        for i, entry in zip(layers, attention_layers):
            entry = dict(entry)
            if entry.get("cache") == "rolling":
                w = int(entry.get("window") or 0)
                if not entry.get("rows"):
                    entry["rows"] = self.max_len if chunk < 1 else min(
                        self.max_len, -(-(w + chunk - 1) // 8) * 8)
                rings["layer%d_" % i] = (int(entry["rows"]), w)
            out.append(entry)
        return tuple(out), rings

    def _ring_of(self, name):
        """(rows, window) of the circular buffer the aux ``name``
        belongs to, or None: a full layer's rows, or no cache rows."""
        return self._rings.get(name.split("_", 1)[0] + "_") \
            if name.endswith(("_k_cache", "_v_cache")) else None

    @property
    def ring_feed(self):
        """The widest forward (new rows at one call) that is safe at
        any depth: the smallest circular buffer less its window plus
        one. None without a rolling layer."""
        return min((rows - w + 1 for rows, w in self._rings.values()),
                   default=None)

    def check_feed(self, P, feed, what="prompt"):
        """Raise unless a sequence of ``P`` positions fed ``feed`` new
        rows at a call keeps every window whole in every circular
        buffer: either nothing wraps (P fits the buffer) or a call's
        new rows never overwrite a slot one of them still attends."""
        for prefix, (rows, w) in self._rings.items():
            if feed > rows - w + 1 and P > rows:
                raise ValueError(
                    "%s of %d positions fed %d rows at a time would "
                    "overwrite live slots of %sattn's circular cache "
                    "(%d rows, window %d): feed at most %d rows a "
                    "call (MXNET_PREFILL_CHUNK) or size the buffer "
                    "for it" % (what, P, feed, prefix, rows, w,
                                rows - w + 1))

    def _aux_row_shape(self, name, pos):
        """Shape of ONE batch row's exported state for aux ``name`` at
        sequence position ``pos``, ON THE WIRE: length-indexed caches
        ship their first ``pos`` tokens head-major, ``(Hkv, pos, hd)``
        (the int8 scales ``(Hkv, pos)``), which is blob format ``"v":
        1`` whatever layout the device keeps (:meth:`_wire_rows`); SSM
        state blobs have no length axis and ship whole (the
        O(1)-handoff property — blob bytes constant in prompt length).
        Shared by export_kv_rows and the serving side's import
        validation so the two ends of a handoff can never disagree."""
        shape, _ = self._aux_spec(name)
        if name.endswith("_state"):
            return shape[1:]
        if name.endswith(("_k_scale", "_v_scale")):
            return (self._kv_heads, pos)
        # a circular buffer ships its slots as they lie: the first
        # ``pos`` while nothing has wrapped, all of them after (slot s
        # holds the newest position congruent to s, which the importer
        # reads back from ``pos`` alone)
        heads = self._wire_heads(name)
        return (heads, min(pos, shape[1]), shape[2] // heads)

    def _wire_rows(self, name, rows, to_wire):
        """One sequence's state between the device's layout and the
        wire's (see _aux_row_shape): a length-indexed cache's
        ``(pos, Hkv*hd)`` token rows become ``(Hkv, pos, hd)`` and
        back (the scales' ``(pos, Hkv)`` become ``(Hkv, pos)``); state
        blobs pass as they are. The one transpose of a handoff: it
        sits in the export program and in the import scatter."""
        if name.endswith("_state"):
            return rows
        heads = self._wire_heads(name)
        if to_wire:
            return jnp.moveaxis(
                rows.reshape(rows.shape[0], heads, -1), 0, 1
            ).reshape(self._aux_row_shape(name, rows.shape[0]))
        pos = rows.shape[1]
        return jnp.moveaxis(rows.reshape(heads, pos, -1),
                            0, 1).reshape(pos, -1)

    def kv_cache_bytes(self):
        """Total bytes of the decode-state aux pytree (every layer's
        k/v caches plus their per-token f32 scale caches under
        quantize_kv, and/or SSM state blobs) at this Generator's
        (batch_size, max_len) — computed from shapes/dtypes alone."""
        return self.batch_size * sum(self.state_bytes_by_kind().values())

    @staticmethod
    def _aux_kind(name):
        """Which kind of decode state an aux name is, for the sizing
        reports: "scan_state" (Mamba-2), "conv_window" (Mamba-2's or
        a gated short convolution's), "ssm_state" (gated linear
        attention), "latent_rows" and "index_rows" (latent attention's
        two arrays of rows) or "kv_rows" (k/v rows and their int8
        scales: everything else with a length axis)."""
        if name.endswith("_latent_cache"):
            return "latent_rows"
        if name.endswith("_index_cache"):
            return "index_rows"
        if name.endswith("_scan_state"):
            return "scan_state"
        if name.endswith("_conv_state"):
            return "conv_window"
        return "ssm_state" if name.endswith("_state") else "kv_rows"

    def state_bytes_by_kind(self):
        """Bytes of decode state one slot owns, by kind of state (see
        _aux_kind, and "kv_window" for a rolling layer's circular
        rows, which do not grow with max_len); only the kinds this
        model has. Sums to state_bytes_per_slot()."""
        out = {}
        for name in self._sym.list_auxiliary_states():
            shape, dtype = self._aux_spec(name)
            n = dtype.itemsize
            for d in shape[1:]:
                n *= int(d)
            kind = "kv_window" if self._ring_of(name) \
                else self._aux_kind(name)
            out[kind] = out.get(kind, 0) + n
        return out

    def state_bytes_per_slot(self):
        """Bytes of decode state ONE batch row (= one serving slot)
        owns — the state-agnostic number behind the
        ``serve.decode.kv_bytes_per_slot`` gauge (name kept for
        dashboard compatibility), ``describe(hbm_budget=)`` and
        ``MXNET_DECODE_SLOTS=auto`` slot sizing, and
        tools/telemetry_report.py's bytes/slot line. O(max_len) for
        attention layers; O(1) for SSM layers."""
        return self.kv_cache_bytes() // self.batch_size

    def export_kv_rows(self, aux, row, pos):
        """Serialize ONE sequence's decode state out of an aux
        pytree — the portable decode state of the prefill/decode
        disaggregation handoff (docs/serving.md §disaggregated
        prefill; the arXiv 2603.09555 "portable O(1) cache" enabler).

        ``aux``: a state pytree this Generator produced (typically the
        prefill output); ``row``: which batch row to export; ``pos``:
        how many tokens of state that row holds. Length-indexed caches
        contribute their ``[row, :pos]`` prefix, head-major on the
        wire (:meth:`_aux_row_shape`) — the int8 k/v rows AND their
        per-token f32 scale rows under ``quantize_kv``, or the
        bf16/f32 rows otherwise; SSM state blobs contribute
        ``[row]`` WHOLE (no length axis — the blob's bytes are
        constant in ``pos``, which is what makes an SSM handoff O(1)
        on the wire). Everything ships as numpy with the device dtype
        preserved bit-for-bit, so a remote
        :meth:`ContinuousDecoder.import_kv_rows` scatter is
        device-roundtrip-exact. Cache entries past ``pos`` never ship:
        they are unattended garbage by the cache-position mask, and
        the blob is what moves over the wire.

        Returns ``{"v": 1, "pos": pos, "rows": {name: np.ndarray}}``.
        """
        if self._rolling:
            raise ValueError(
                "export_kv_rows does not support rolling caches (a "
                "circular buffer's rows are not position-aligned, so "
                "a prefix slice is not the sequence's state)")
        row, pos = int(row), int(pos)
        if not 0 <= row < self.batch_size:
            raise ValueError("row %d out of range for batch_size=%d"
                             % (row, self.batch_size))
        if not 1 <= pos <= self.max_len:
            raise ValueError("pos %d out of range for max_len=%d"
                             % (pos, self.max_len))
        wanted = set(self._sym.list_auxiliary_states())
        if set(aux) != wanted:
            raise ValueError(
                "aux pytree names %s do not match this Generator's "
                "caches %s" % (sorted(aux), sorted(wanted)))
        # ONE fused slice program per pos (row rides as a traced
        # scalar), then one device_get for the whole pytree — the
        # handoff's export half is a single dispatch, not 2x-per-layer
        # eager slices (measured ~3x cheaper; the handoff budget is
        # docs/serving.md's <=15%-of-one-prefill)
        fn = self._loop_cache.get(("export", pos))
        if fn is None:
            def _one(a, r, n):
                # SSM state blobs have no length axis: ship whole
                full = jax.lax.dynamic_index_in_dim(
                    a[n], r, axis=0, keepdims=False)
                return full if n.endswith("_state") else \
                    self._wire_rows(n, full[:pos], True)   # a ring
                #                     shorter than pos ships whole
            fn = jax.jit(lambda a, r: {n: _one(a, r, n) for n in a})
            self._loop_cache[("export", pos)] = fn
        host = jax.device_get(fn(aux, jnp.int32(row)))
        rows = {}
        for name in sorted(wanted):
            _, dtype = self._aux_spec(name)
            want = self._aux_row_shape(name, pos)
            arr = np.asarray(host[name])
            if arr.dtype != dtype or arr.shape != want:
                raise ValueError(
                    "cache %r is %s%r, expected %s%r — the aux pytree "
                    "does not belong to this Generator"
                    % (name, arr.dtype, arr.shape, dtype, want))
            rows[name] = arr
        return {"v": 1, "pos": pos, "rows": rows}

    @staticmethod
    def _check_sampling(temperature, top_k, top_p):
        """top_k/top_p only act on the sampled path; at temperature<=0
        decoding is greedy and they would be silently ignored — make
        that contract explicit instead."""
        if (top_k or top_p) and not (temperature
                                     and float(temperature) > 0):
            raise ValueError(
                "top_k/top_p require temperature > 0 (temperature<=0 "
                "decodes greedily and would silently ignore them)")

    def _check_prompt(self, prompt, max_new_tokens):
        prompt = np.asarray(prompt)
        if prompt.ndim != 2 or prompt.shape[0] != self.batch_size:
            raise ValueError("prompt must be (batch_size, P), got %r"
                             % (prompt.shape,))
        P = prompt.shape[1]
        if self._rolling:
            # circular cache: generation length is unbounded up to
            # the float32-exact position range, 2^24 (pair with RoPE);
            # the capacity only has to fit one window plus the prefill
            # chunk's in-flight overwrites
            if self._window + P - 1 > self.max_len:
                raise ValueError(
                    "rolling cache capacity max_len=%d must be >= "
                    "window (%d) + prompt (%d) - 1"
                    % (self.max_len, self._window, P))
            if self._pos_rows is not None and \
                    P + max_new_tokens > self._pos_rows:
                raise ValueError(
                    "learned positions cap total length at the table "
                    "(%d rows); use pos_encoding='rope' for unbounded "
                    "rolling generation" % self._pos_rows)
        elif self.block_span(P, max_new_tokens) > self.max_len:
            raise ValueError(
                "prompt (%d) + max_new_tokens (%d) exceeds the cache "
                "capacity max_len=%d" % (P, max_new_tokens,
                                         self.max_len))
        # the Generator's own loops prefill a prompt in one forward
        self.check_feed(P, P)
        return prompt, P

    def block_span(self, P, max_new_tokens):
        """Positions a request touches: P + max_new_tokens, rounded up
        to whole blocks under ``diffusion`` (the last block is run
        whole, though the row keeps max_new_tokens of it)."""
        total = int(P) + int(max_new_tokens)
        if self._diffusion:
            L = self._diffusion["block_length"]
            total = -(-total // L) * L
        return total

    def _refuse_latent(self, draft):
        """Speculation over latent and index-key rows is not held to
        anything yet, the target's or the draft's: refused."""
        if self._latent or getattr(draft, "_latent", False):
            raise ValueError(
                "speculative decoding is not supported with 'mla' "
                "layers (latent rows whose keys an indexer selects: no "
                "test holds a verify forward over them yet)")

    def _refuse_diffusion(self, what):
        if self._diffusion:
            raise ValueError(
                "%s is not supported with diffusion= (generation by "
                "diffusion fills whole blocks, greedily, through "
                "generate() or the serving decoder)" % what)

    def _aux_shardings(self):
        """Placement of every decode-state aux under a mesh (the int8
        scales split like the rows they scale), None without one — what
        _fresh_aux allocates with and what a program that donates a
        pool must hand back (serve/decode.py's cache merge)."""
        if self._cache_sharding is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec

        def place(name):
            shape = self._aux_spec(name)[0]
            if name.endswith(("_conv_state", "_scan_state")) or \
                    self._own_width(name):
                # Mamba-2 states and a short convolution's window:
                # batch over 'data' only (the window axis is d_conv-1
                # long, x|B|C share the last one, and the mixer's
                # heads need not divide the 'model' axis); latent and
                # index-key rows, which every head shares, likewise
                return NamedSharding(self.mesh, PartitionSpec(
                    self._cache_sharding.spec[0],
                    *([None] * (len(shape) - 1))))
            return self._cache_sharding

        return {name: place(name)
                for name in self._sym.list_auxiliary_states()}

    def _fresh_aux(self, rows=None):
        """A zeroed decode-state pytree: ONE compiled program for the
        whole pytree (a dispatch per call, not an eager zeros +
        device_put per cache array — 2 x num_layers of them).
        ``rows`` batch rows in place of ``batch_size``: the state of a
        prefill that runs fewer rows than the pool is wide (a program
        a row count; under a mesh the ``data`` axis must divide it)."""
        rows = self.batch_size if rows is None else int(rows)
        key = "fresh_aux" if rows == self.batch_size else \
            ("fresh_aux", rows)
        fn = self._loop_cache.get(key)
        if fn is None:
            specs = {}
            for name in self._sym.list_auxiliary_states():
                shape, dtype = self._aux_spec(name)
                specs[name] = ((rows,) + shape[1:], dtype)

            def fresh_aux():
                return {name: jnp.zeros(shape, dtype)
                        for name, (shape, dtype) in specs.items()}

            fn = jax.jit(fresh_aux, out_shardings=self._aux_shardings())
            self._loop_cache[key] = fn
        return fn()

    def _forward(self, aux, tokens, pos):
        """tokens: (B, Tnew) int array; pos: python int."""
        tn = tokens.shape[1]
        if pos + tn > 2 ** 24:
            # positions ride the float32 input convention; past 2^24
            # consecutive integers stop being representable (RoPE
            # angles and circular-slot indices would silently corrupt)
            raise ValueError(
                "position %d exceeds the float32-exact range (2^24); "
                "longer rolling generation needs integer position "
                "plumbing" % (pos + tn))
        args = dict(self._params)
        args["data"] = jnp.asarray(tokens, jnp.float32)
        args["positions"] = jnp.arange(pos, pos + tn, dtype=jnp.float32)
        args["cache_pos"] = jnp.full((1,), pos, jnp.float32)
        outs, new_aux = self._step_fn(args, aux, jax.random.PRNGKey(0))
        return outs[0], new_aux     # logits (B, Tnew, V)

    def log_likelihood(self, tokens):
        """Teacher-forcing score: per-row sum of log P(t_{i+1} | t_<=i)
        over the sequence, via one prefill pass. tokens: (B, Tseq) with
        Tseq <= max_len; returns (B,) float64. The serving-side eval
        utility (perplexity = exp(-ll / (Tseq - 1)))."""
        self._refuse_diffusion("log_likelihood")
        tokens = np.asarray(tokens)
        if tokens.ndim != 2 or tokens.shape[0] != self.batch_size:
            raise ValueError("tokens must be (batch_size, T), got %r"
                             % (tokens.shape,))
        if tokens.shape[1] > self.max_len:
            raise ValueError("sequence length %d exceeds max_len=%d"
                             % (tokens.shape[1], self.max_len))
        if self._pos_rows is not None and \
                tokens.shape[1] > self._pos_rows:
            raise ValueError(
                "sequence length %d exceeds the trained position "
                "table (%d rows) — scoring would silently clip"
                % (tokens.shape[1], self._pos_rows))
        logits, _ = self._forward(self._fresh_aux(), tokens, 0)
        logp = np.asarray(jax.nn.log_softmax(
            logits.astype(jnp.float32), axis=-1))     # (B, T, V)
        nxt = tokens[:, 1:].astype(np.int64)
        rows = np.arange(self.batch_size)[:, None]
        cols = np.arange(tokens.shape[1] - 1)[None, :]
        return logp[rows, cols, nxt].sum(axis=1).astype(np.float64)

    def beam_search(self, prompt, max_new_tokens, beam_size=4,
                    length_penalty=0.0, eos_id=None):
        """Beam decoding over the same KV-cache graph.

        Beams fold into the batch dimension (caches run at B*W); after
        each step the caches are reordered by the surviving beams'
        parent indices (a gather on the cache batch axis). Returns
        (B, P + n) ids — the highest-scoring beam per row, scores
        normalized by (generated length) ** length_penalty.

        eos_id: a beam that emits eos is frozen (only eos continues it,
        at no score change); search stops early when every beam of
        every row is frozen."""
        self._refuse_diffusion("beam_search")
        prompt, P = self._check_prompt(prompt, max_new_tokens)
        B, W, V = self.batch_size, int(beam_size), self.vocab_size
        if W < 1:
            raise ValueError("beam_size must be >= 1")

        # prefill ONCE at batch B, then tile caches/logits to the
        # B*W beam batch — the prompt forward is the expensive part
        # and all beams share it
        aux = self._fresh_aux()
        logits, aux = self._forward(aux, prompt, 0)
        aux = {k: jnp.repeat(v, W, axis=0) for k, v in aux.items()}
        last = np.repeat(np.asarray(jax.nn.log_softmax(
            logits[:, -1].astype(jnp.float32), axis=-1)), W, axis=0)

        # duplicate beams would tie forever: start all but beam 0 at
        # -inf so step 1 picks W DISTINCT first tokens
        scores = np.full((B, W), -np.inf)
        scores[:, 0] = 0.0
        tokens = np.zeros((B, W, 0), np.int64)
        frozen = np.zeros((B, W), bool)

        for t in range(max_new_tokens):
            logp = last.reshape(B, W, V).copy()
            if eos_id is not None:
                # frozen beams: only eos continues, for free
                logp[frozen] = -np.inf
                logp[frozen, eos_id] = 0.0
            cand = scores[:, :, None] + logp           # (B, W, V)
            flat = cand.reshape(B, W * V)
            top = np.argsort(-flat, axis=1)[:, :W]     # (B, W)
            parent = top // V
            tok = top % V
            scores = np.take_along_axis(flat, top, axis=1)
            tokens = np.concatenate(
                [np.take_along_axis(
                    tokens, parent[:, :, None], axis=1),
                 tok[:, :, None]], axis=2)
            if eos_id is not None:
                frozen = np.take_along_axis(frozen, parent, axis=1) \
                    | (tok == eos_id)
                if frozen.all():
                    break
            if t + 1 == max_new_tokens:
                break
            # reorder caches to the surviving beams' parents and feed
            # the chosen tokens
            flat_idx = (np.arange(B)[:, None] * W + parent).reshape(-1)
            idx_dev = jnp.asarray(flat_idx)
            aux = {k: jnp.take(v, idx_dev, axis=0)
                   for k, v in aux.items()}
            logits, aux = self._forward(aux, tok.reshape(-1, 1), P + t)
            last = np.asarray(jax.nn.log_softmax(
                logits[:, -1].astype(jnp.float32), axis=-1))

        gen_len = tokens.shape[2]
        if length_penalty:
            # per-beam effective length: up to the first eos (frozen
            # beams pad with free eos tokens that must not count)
            lens = np.full((B, W), gen_len, np.float64)
            if eos_id is not None:
                is_eos = tokens == eos_id              # (B, W, t)
                has = is_eos.any(axis=2)
                lens[has] = is_eos.argmax(axis=2)[has] + 1
            norm = scores / np.maximum(1.0,
                                       lens) ** float(length_penalty)
        else:
            norm = scores
        best = norm.argmax(axis=1)                     # (B,)
        out = tokens[np.arange(B), best]               # (B, gen_len)
        return np.concatenate([prompt.astype(np.int64), out], axis=1)

    def beam_search_on_device(self, prompt, max_new_tokens,
                              beam_size=4, length_penalty=0.0,
                              eos_id=None):
        """beam_search compiled into ONE device program: prefill + a
        lax.scan whose body does the (W*V) top-k, reorders the token
        history AND the KV caches by the surviving beams' parent
        indices (a batch-axis gather), and runs the next forward — no
        per-token host round-trips (the host-loop beam_search pays one
        dispatch per step, which through a remote link is RTT-bound).

        Same selection semantics as beam_search; fixed trip count (eos
        freezes beams — they extend with free eos tokens — but cannot
        early-exit a scan, so the output is always P + n long where the
        host loop may return shorter once every beam froze). Each
        distinct
        (prompt_len, max_new_tokens, beam_size, eos_id) compiles once.
        Returns (B, P + n) ids."""
        self._refuse_diffusion("beam_search_on_device")
        prompt, P = self._check_prompt(prompt, max_new_tokens)
        B, W = self.batch_size, int(beam_size)
        if W < 1:
            raise ValueError("beam_size must be >= 1")
        n = int(max_new_tokens)
        if n == 0:
            return np.asarray(prompt, np.int64)
        fn = self._beam_loop(P, n, W,
                             -1 if eos_id is None else int(eos_id))
        tokens, scores = fn(self._params,
                            jnp.asarray(prompt, jnp.float32))
        tokens = np.asarray(tokens)            # (B, W, n)
        scores = np.asarray(scores)            # (B, W)

        # length-penalty + best-beam selection on host, sharing the
        # host beam_search's exact formulation
        if length_penalty:
            lens = np.full((B, W), n, np.float64)
            if eos_id is not None:
                is_eos = tokens == eos_id
                has = is_eos.any(axis=2)
                lens[has] = is_eos.argmax(axis=2)[has] + 1
            norm = scores / np.maximum(1.0,
                                       lens) ** float(length_penalty)
        else:
            norm = scores
        best = norm.argmax(axis=1)
        out = tokens[np.arange(B), best].astype(np.int64)
        return np.concatenate([prompt.astype(np.int64), out], axis=1)

    def _beam_loop(self, P, n, W, eos):
        key_ = ("beam", P, n, W, eos)
        cached = self._loop_cache.get(key_)
        if cached is not None:
            return cached
        eval_fn = self._eval_fn
        B, V = self.batch_size, self.vocab_size

        # params as jit arguments, not closures (see _device_loop)
        def fwd(params, aux, data, pos):
            args = dict(params)
            args["data"] = data.astype(jnp.float32)
            args["positions"] = jnp.full((1,), pos, jnp.float32)
            args["cache_pos"] = jnp.full((1,), pos, jnp.float32)
            outs, aux = eval_fn(args, aux, jax.random.PRNGKey(0),
                                False)
            return jax.nn.log_softmax(
                outs[0][:, -1].astype(jnp.float32), axis=-1), aux

        def select(logp, scores, tokens, frozen, i):
            """One beam step: (W*V) top-k + history reorder."""
            if eos >= 0:
                free = jnp.full((V,), -jnp.inf).at[eos].set(0.0)
                logp = jnp.where(frozen[:, :, None], free[None, None],
                                 logp)
            flat = (scores[:, :, None] + logp).reshape(B, W * V)
            top_scores, top_idx = jax.lax.top_k(flat, W)
            parent = top_idx // V
            tok = top_idx % V
            tokens = jnp.take_along_axis(tokens, parent[:, :, None],
                                         axis=1)
            tokens = tokens.at[:, :, i].set(tok.astype(jnp.int32))
            if eos >= 0:
                frozen = jnp.take_along_axis(frozen, parent, axis=1) \
                    | (tok == eos)
            return top_scores, tokens, frozen, parent, tok

        def run(params, prompt):
            aux = self._fresh_aux()
            args = dict(params)
            args["data"] = prompt
            args["positions"] = jnp.arange(P, dtype=jnp.float32)
            args["cache_pos"] = jnp.zeros((1,), jnp.float32)
            outs, aux = eval_fn(args, aux, jax.random.PRNGKey(0),
                                False)
            logp = jax.nn.log_softmax(
                outs[0][:, -1].astype(jnp.float32), axis=-1)  # (B, V)
            # beams fold into batch: caches at B*W, all sharing the
            # prefill; duplicate beams start at -inf so step 1 picks W
            # distinct first tokens (host beam_search's trick)
            aux = {k: jnp.repeat(v, W, axis=0) for k, v in aux.items()}
            logp = jnp.repeat(logp, W, axis=0).reshape(B, W, V)
            scores = jnp.where(jnp.arange(W) == 0, 0.0,
                               -jnp.inf)[None, :].repeat(B, axis=0)
            tokens = jnp.zeros((B, W, n), jnp.int32)
            frozen = jnp.zeros((B, W), bool)

            def body(carry, i):
                aux, logp, scores, tokens, frozen = carry
                scores, tokens, frozen, parent, tok = select(
                    logp, scores, tokens, frozen, i)
                flat_idx = (jnp.arange(B)[:, None] * W
                            + parent).reshape(-1)
                aux = {k: jnp.take(v, flat_idx, axis=0)
                       for k, v in aux.items()}
                logp, aux = fwd(params, aux, tok.reshape(-1, 1),
                                P + i)
                logp = logp.reshape(B, W, V)
                return (aux, logp, scores, tokens, frozen), None

            # final step needs no forward (host beam_search breaks
            # before its last forward the same way)
            (aux, logp, scores, tokens, frozen), _ = jax.lax.scan(
                body, (aux, logp, scores, tokens, frozen),
                jnp.arange(n - 1))
            scores, tokens, frozen, _, _ = select(
                logp, scores, tokens, frozen, n - 1)
            return tokens, scores

        fn = jax.jit(run)
        self._loop_cache[key_] = fn
        return fn

    def generate_speculative(self, draft, prompt, max_new_tokens,
                             lookahead=4, temperature=0.0, top_k=None,
                             top_p=None, seed=0):
        """Speculative decoding: a small `draft` Generator proposes
        `lookahead` tokens per round; this (target) model verifies
        them in ONE forward and keeps the longest matching prefix plus
        its own next token. Output is EXACTLY this model's own
        ``generate`` continuation for the same sampling args — the
        draft only changes how many target forwards it takes.

        Sampling uses common-random-numbers verification, a
        deterministic specialisation of speculative rejection
        sampling: the token at emission index j is ALWAYS
        ``_pick_token(target_logits_j, sub_j)`` where ``sub_j`` is the
        (j+1)-th split of ``PRNGKey(seed)`` — the exact key discipline
        of ``generate``'s loop (``replay_key``). The draft proposes
        with the SAME ``sub_j`` on its own logits, so a proposal is
        accepted exactly when it equals the target's pick under shared
        noise; acceptance rate tracks how closely the draft's filtered
        distribution matches the target's. Output is therefore
        byte-identical to ``generate(seed=...)`` — trivially
        distribution-exact, and replayable token-for-token (the
        serving fleet's failover contract rides on this).

        Cache rollback is free by construction: `_contrib_
        CachedAttention` writes at `cache_pos` and masks columns
        beyond `pos + row`, so rejected speculative entries are simply
        overwritten by the next append and can never be attended.

        Exactness caveat: "exactly generate()" holds up to XLA kernel
        numerics — the chunked verify forward (Tnew = lookahead+1) and
        the one-token decode forward may differ at the last ulp, so a
        near-exact logit TIE can in principle resolve differently than
        generate() would. Irrelevant for real sampling temperatures
        and not observed in tests; noted for bit-exactness audits.

        draft: a Generator with the same vocab/batch (typically fewer
        layers/dims — :meth:`truncated_draft`). Returns
        (B, P + max_new_tokens) ids. Batch rows advance in lockstep
        (the accepted length each round is the minimum across rows) —
        the serving decoder's per-slot rounds lift that restriction;
        B=1 is the classic setting here."""
        self._refuse_diffusion("speculative decoding")
        if draft.vocab_size != self.vocab_size or \
                draft.batch_size != self.batch_size:
            raise ValueError("draft must share vocab_size/batch_size "
                             "with the target")
        if self._wraps or getattr(draft, "_wraps", False):
            # rejected speculative slots could alias older positions in
            # a circular buffer (p_s mis-attribution) — not supported
            raise ValueError("speculative decoding is not supported "
                             "with rolling caches")
        self._refuse_latent(draft)
        if self._has_ssm or getattr(draft, "_has_ssm", False):
            # the recurrent state is mutated by EVERY fed token and
            # has no per-position rows — rejected speculative tokens
            # cannot be rolled back out of it
            raise ValueError(
                "speculative decoding is not supported with ssm "
                "blocks: the recurrent state has no per-position "
                "entries to overwrite, so rejected proposals would "
                "corrupt it (use attention blocks for speculative "
                "serving)")
        self._check_sampling(temperature, top_k, top_p)
        prompt, P = self._check_prompt(prompt, max_new_tokens)
        if P + max_new_tokens > draft.max_len:
            raise ValueError("draft max_len=%d too small for %d tokens"
                             % (draft.max_len, P + max_new_tokens))
        gamma = max(1, int(lookahead))
        sampled = bool(temperature and float(temperature) > 0)
        key = jax.random.PRNGKey(int(seed or 0)) if sampled else None

        # invariant: before each round, both caches hold a VALID prefix
        # covering [0, len(out) - 1) — every round's feeds start at
        # position len(out) - 1 and overwrite any stale speculative
        # entries beyond the accepted boundary
        t_aux = self._fresh_aux()
        d_aux = draft._fresh_aux()
        if P > 1:
            _, t_aux = self._forward(t_aux, prompt[:, :P - 1], 0)
            _, d_aux = draft._forward(d_aux, prompt[:, :P - 1], 0)
        out = prompt.astype(np.int64)

        while out.shape[1] - P < max_new_tokens:
            pos = out.shape[1]
            budget = max_new_tokens - (pos - P)
            g = min(gamma, budget - 1)      # leave room for the bonus
            # peek this round's subs WITHOUT advancing the stream: the
            # draft proposes with the same sub the target will verify
            # with, and the key only advances by what is emitted
            subs, k = [], key
            if sampled:
                for _ in range(g + 1):
                    k, sub = jax.random.split(k)
                    subs.append(sub)
            # draft proposes g tokens, continuing from the last emitted
            cur = out[:, -1]
            props = []
            for i in range(g):
                dl, d_aux = draft._forward(d_aux, cur[:, None],
                                           pos - 1 + i)
                cur = np.asarray(_pick_token(
                    dl[:, -1], temperature, top_k,
                    subs[i] if sampled else None, top_p))
                props.append(cur)
            # ONE target forward scores last_emitted + all proposals:
            # tokens at positions pos-1 .. pos+g-1, logits predicting
            # positions pos .. pos+g
            chunk = np.concatenate(
                [out[:, -1:]] + [p[:, None] for p in props], axis=1)
            tl, t_aux = self._forward(t_aux, chunk, pos - 1)
            picks = np.stack(
                [np.asarray(_pick_token(
                    tl[:, c], temperature, top_k,
                    subs[c] if sampled else None, top_p))
                 for c in range(g + 1)], axis=1)          # (B, g+1)
            # accept while the draft token at pos+i matches the target
            # pick for pos+i; lockstep across the batch
            acc = 0
            while acc < g and bool(
                    (props[acc] == picks[:, acc]).all()):
                acc += 1
            # emit the accepted tokens + the target's own next token
            # (correctly conditioned: its inputs are the accepted
            # prefix — accepted proposals ARE the target's picks, so
            # every emitted token is exactly what generate() picks)
            out = np.concatenate([out, picks[:, :acc + 1]], axis=1)
            if sampled:
                # one split per EMITTED token, whatever path drew it
                for _ in range(acc + 1):
                    key, _ = jax.random.split(key)
            if acc == g and g > 0 and \
                    out.shape[1] - P < max_new_tokens:
                # full acceptance: the draft never ingested its own
                # last proposal's k/v (its loop stops after computing
                # it) — feed it so the invariant holds next round
                # (skipped when the budget is exhausted: one whole
                # dispatch saved on the final round)
                _, d_aux = draft._forward(d_aux, props[-1][:, None],
                                          pos + g - 1)
        return out[:, :P + max_new_tokens]

    def truncated_draft(self, num_layers=1, batch_size=None,
                        max_len=None):
        """A draft Generator that runs only the FIRST ``num_layers``
        transformer blocks of THIS model, sharing its weights — the
        zero-extra-checkpoint speculative draft. Works because
        Generator filters ``arg_params`` down to what its own symbol
        lists: a shallower decode symbol's argument names are a strict
        subset of the full stack's (layer0..k-1 + embed/head), so the
        truncated model is literally the full model with the late
        blocks skipped. Residual connections make that a coarse but
        real approximation; acceptance rate measures how much the
        dropped layers change the pick.

        ``batch_size``/``max_len`` default to this model's (the
        serving decoder wants the same slot-pool shape; give the draft
        a larger max_len only if you need extra lookahead headroom)."""
        self._refuse_diffusion("truncated_draft")
        o = self._decode_opts
        if o["quantized"]:
            raise ValueError(
                "truncated_draft is not supported on a quantize='int8' "
                "Generator (its stored weights are already int8; build "
                "the draft from the float checkpoint instead)")
        if self._wraps:
            raise ValueError("truncated_draft is not supported with "
                             "rolling caches (speculative decoding "
                             "rejects rolling models outright)")
        if o["layer_kinds"] is not None:
            raise ValueError(
                "truncated_draft is not supported with layer_kinds (a "
                "draft of the first sublayers is not a model)")
        if self._has_ssm:
            raise ValueError(
                "truncated_draft is not supported with ssm blocks "
                "(speculative decoding rejects SSM models outright — "
                "the recurrent state has no rollback)")
        nl = int(num_layers)
        if not 1 <= nl <= self.num_layers:
            raise ValueError(
                "truncated_draft num_layers=%d out of range 1..%d"
                % (nl, self.num_layers))
        return Generator(
            self._params, o["vocab_size"],
            int(max_len) if max_len else o["max_len"],
            num_layers=nl, num_heads=o["num_heads"], dim=o["dim"],
            ffn_hidden=o["ffn_hidden"],
            batch_size=int(batch_size) if batch_size
            else self.batch_size,
            dtype=o["compute_dtype"], num_experts=o["num_experts"],
            mesh=self.mesh, pos_encoding=o["pos_encoding"],
            attention_window=o["attention_window"],
            num_kv_heads=o["num_kv_heads"],
            quantize_kv=o["kv_quantize"])

    def generate_speculative_on_device(self, draft, prompt,
                                       max_new_tokens, lookahead=4,
                                       return_rounds=False,
                                       temperature=0.0, top_k=None,
                                       top_p=None, seed=0):
        """generate_speculative compiled into ONE device program: a
        lax.while_loop whose body runs the draft's propose scan, the
        target's single verify forward, the acceptance rule, and the
        emit — both models' parameters and caches live in one XLA
        program, no host dispatches per round. Output is exactly the
        target's own generate() continuation for the same sampling
        args (same common-random-numbers rule as the host loop; pinned
        against it in tests).

        Static-shape discipline: every round proposes the FULL
        `lookahead` and emissions are clamped to the remaining budget,
        so both caches need headroom — max_len >= P + max_new_tokens +
        lookahead on target AND draft (validated here)."""
        self._refuse_diffusion("speculative decoding")
        if draft.vocab_size != self.vocab_size or \
                draft.batch_size != self.batch_size:
            raise ValueError("draft must share vocab_size/batch_size "
                             "with the target")
        if self._wraps or getattr(draft, "_wraps", False):
            raise ValueError("speculative decoding is not supported "
                             "with rolling caches")
        self._refuse_latent(draft)
        if self._has_ssm or getattr(draft, "_has_ssm", False):
            raise ValueError(
                "speculative decoding is not supported with ssm "
                "blocks: the recurrent state has no per-position "
                "entries to overwrite, so rejected proposals would "
                "corrupt it (use attention blocks for speculative "
                "serving)")
        self._check_sampling(temperature, top_k, top_p)
        prompt, P = self._check_prompt(prompt, max_new_tokens)
        n = int(max_new_tokens)
        if n == 0:
            toks = np.asarray(prompt, np.int64)
            return (toks, 0) if return_rounds else toks
        g = max(1, int(lookahead))
        need = P + n + g
        for which, who in (("target", self), ("draft", draft)):
            if need > who.max_len:
                raise ValueError(
                    "%s max_len=%d too small: on-device speculative "
                    "needs prompt (%d) + max_new_tokens (%d) + "
                    "lookahead (%d) headroom (fixed-shape rounds may "
                    "overrun the budget by up to lookahead)"
                    % (which, who.max_len, P, n, g))
        temp = float(temperature or 0.0)
        tk = int(top_k) if top_k else 0
        tp = float(top_p) if top_p else 0.0
        key_ = ("spec", P, n, g, temp, tk, tp, id(draft))
        cached = self._loop_cache.get(key_)
        if cached is None:
            fn = self._spec_loop(draft, P, n, g, temp, tk, tp)
            self._loop_cache[key_] = (fn, draft)   # pin draft alive
        else:
            fn = cached[0]
        out, rounds = fn(self._params, draft._params,
                         jnp.asarray(prompt, jnp.float32),
                         jax.random.PRNGKey(int(seed or 0)))
        toks = np.asarray(out[:, :P + n], np.int64)
        if return_rounds:
            # rounds -> acceptance: each round emits acc+1 tokens, so
            # mean accepted draft tokens per round = n/rounds - 1
            return toks, int(rounds)
        return toks

    def _spec_loop(self, draft, P, n, g, temp=0.0, tk=0, tp=0.0):
        B = self.batch_size
        t_eval, d_eval = self._eval_fn, draft._eval_fn
        rng0 = jax.random.PRNGKey(0)
        sampled = temp > 0
        top_k = tk or None
        top_p = tp or None

        def fwd(eval_fn, params, aux, tokens, pos, tn):
            """tokens (B, tn) int32, pos scalar int32."""
            args = dict(params)
            args["data"] = tokens.astype(jnp.float32)
            args["positions"] = (pos + jnp.arange(tn)).astype(
                jnp.float32)
            args["cache_pos"] = pos.astype(jnp.float32)[None]
            outs, aux = eval_fn(args, aux, rng0, False)
            return outs[0], aux

        # both models' params as jit arguments (see _device_loop)
        def run(t_params, d_params, prompt, key):
            t_aux = self._fresh_aux()
            d_aux = draft._fresh_aux()
            prompt_i = prompt.astype(jnp.int32)
            if P > 1:
                _, t_aux = fwd(t_eval, t_params, t_aux,
                               prompt_i[:, :P - 1], jnp.int32(0),
                               P - 1)
                _, d_aux = fwd(d_eval, d_params, d_aux,
                               prompt_i[:, :P - 1], jnp.int32(0),
                               P - 1)
            buf = jnp.zeros((B, P + n + g + 1), jnp.int32)
            buf = buf.at[:, :P].set(prompt_i)
            emitted = jnp.int32(0)

            def cond(carry):
                return carry[3] < n

            def body(carry):
                t_aux, d_aux, buf, emitted, rounds, key = carry
                pos = P + emitted
                last = jnp.take_along_axis(
                    buf, (pos - 1)[None].repeat(B)[:, None],
                    axis=1)[:, 0]                       # (B,)

                # peek the round's g+1 subs without committing: sub_j
                # is the split generate() would use for emission index
                # emitted+j, and keys_after[t] is the key after t
                # emissions — the carry key only advances by `take`
                if sampled:
                    ks, subs, k = [key], [], key
                    for _ in range(g + 1):
                        k, s = jax.random.split(k)
                        ks.append(k)
                        subs.append(s)
                    subs = jnp.stack(subs)          # (g+1, 2)
                    keys_after = jnp.stack(ks)      # (g+2, 2)

                # draft proposes g tokens (ingesting each as it goes;
                # round 1's first step also ingests the prompt's last
                # token, which the prefill deliberately left out)
                def d_step(dc, i):
                    d_aux, cur = dc
                    dl, d_aux = fwd(d_eval, d_params, d_aux,
                                    cur[:, None], pos - 1 + i, 1)
                    if sampled:
                        # common random numbers: the SAME sub the
                        # target will verify emission emitted+i with
                        nxt = _pick_token(
                            dl[:, -1], temp, top_k,
                            jnp.take(subs, i, axis=0),
                            top_p).astype(jnp.int32)
                    else:
                        nxt = jnp.argmax(dl[:, -1], axis=-1).astype(
                            jnp.int32)
                    return (d_aux, nxt), nxt

                (d_aux, _), props = jax.lax.scan(
                    d_step, (d_aux, last), jnp.arange(g))   # (g, B)
                props_t = props.T                            # (B, g)

                # ONE target forward scores last + proposals
                chunk = jnp.concatenate([last[:, None], props_t],
                                        axis=1)              # (B, g+1)
                tl, t_aux = fwd(t_eval, t_params, t_aux, chunk,
                                pos - 1, g + 1)
                if sampled:
                    picks = jnp.stack(
                        [_pick_token(tl[:, c], temp, top_k, subs[c],
                                     top_p)
                         for c in range(g + 1)],
                        axis=1).astype(jnp.int32)            # (B, g+1)
                else:
                    picks = jnp.argmax(tl, axis=-1).astype(
                        jnp.int32)                           # (B, g+1)

                # lockstep acceptance: leading i with batch-unanimous
                # draft/target agreement (under shared noise when
                # sampling, so agreement == the target's own pick)
                match = (props_t == picks[:, :g]).all(axis=0)   # (g,)
                acc = jnp.cumprod(match.astype(jnp.int32)).sum()
                take = jnp.minimum(acc + 1, n - emitted)
                # emit the picks directly: columns < acc equal the
                # accepted proposals, column acc is the target's own
                # next token, columns past `take` hold junk but land
                # in the headroom region or are overwritten by the
                # next round (which starts at pos + take)
                buf = jax.lax.dynamic_update_slice(
                    buf, picks, (0, pos))
                if sampled:
                    # advance one split per EMITTED token
                    key = jnp.take(keys_after, take, axis=0)
                return (t_aux, d_aux, buf, emitted + take,
                        rounds + 1, key)

            _, _, buf, _, rounds, _ = jax.lax.while_loop(
                cond, body, (t_aux, d_aux, buf, emitted,
                             jnp.int32(0), key))
            return buf, rounds

        return jax.jit(run)

    def generate_on_device(self, prompt, max_new_tokens,
                           temperature=0.0, top_k=None, top_p=None,
                           eos_id=None, seed=0):
        """Whole-generation-on-device: prefill + a compiled decode loop
        in ONE XLA program — a single dispatch instead of one per token
        (the production-serving shape; the per-token loop pays a host
        round trip per token).

        Same sampling semantics as generate(). Without eos_id the loop
        is a lax.scan with a static trip count. With eos_id it becomes
        a lax.while_loop that EXITS as soon as every row has emitted
        eos — the serving early-stop, still in one program; the output
        keeps the static (B, P + max_new_tokens) shape with finished
        rows padded by eos (the host generate() truncates instead —
        same tokens, different tail). Each distinct
        (prompt_len, max_new_tokens, temperature, top_k, top_p,
        eos_id) tuple compiles once."""
        self._refuse_diffusion("generate_on_device")
        self._check_sampling(temperature, top_k, top_p)
        prompt, P = self._check_prompt(prompt, max_new_tokens)
        if int(max_new_tokens) == 0:
            return np.asarray(prompt, np.int64)
        toks = self._device_loop(P, int(max_new_tokens),
                                 float(temperature),
                                 int(top_k) if top_k else 0,
                                 float(top_p) if top_p else 0.0,
                                 None if eos_id is None
                                 else int(eos_id))(
            self._params,
            jnp.asarray(prompt, jnp.float32),
            jax.random.PRNGKey(seed))
        return np.concatenate([prompt.astype(np.int64),
                               np.asarray(toks)], axis=1)

    def _device_loop(self, P, n_steps, temperature, top_k, top_p=0.0,
                     eos_id=None):
        key_ = (P, n_steps, temperature, top_k, top_p, eos_id)
        cached = self._loop_cache.get(key_)
        if cached is not None:
            return cached
        eval_fn = self._eval_fn
        B = self.batch_size

        # params flow through as jit ARGUMENTS, never closures: a
        # closed-over weight dict would be baked into the lowered
        # program as dense constants — a fresh compile per checkpoint,
        # and a serialized module the size of the model
        def decode_fwd(params, aux, tok, i, sub):
            args = dict(params)
            args["data"] = tok[:, None].astype(jnp.float32)
            args["positions"] = jnp.full((1,), P + i, jnp.float32)
            args["cache_pos"] = jnp.full((1,), P + i, jnp.float32)
            outs, aux = eval_fn(args, aux, sub, False)
            return outs[0][:, -1], aux

        def prefill(params, prompt, key):
            aux = self._fresh_aux()
            args = dict(params)
            args["data"] = prompt
            args["positions"] = jnp.arange(P, dtype=jnp.float32)
            args["cache_pos"] = jnp.zeros((1,), jnp.float32)
            outs, aux = eval_fn(args, aux, key, False)
            return outs[0][:, -1], aux

        def run_scan(params, prompt, key):
            last, aux = prefill(params, prompt, key)

            def body(carry, i):
                aux, last, key = carry
                key, sub = jax.random.split(key)
                tok = _pick_token(last, temperature, top_k, sub,
                                  top_p)
                last, aux = decode_fwd(params, aux, tok, i, sub)
                return (aux, last, key), tok

            # the scan body samples token i from the PREVIOUS step's
            # logits and then runs a forward — so the n-th token needs
            # only n-1 forwards: run n-1 bodies and sample the final
            # token from the last carry outside the scan (same rng
            # split pattern, one decode forward saved per call)
            (_, last, key), toks = jax.lax.scan(
                body, (aux, last, key), jnp.arange(n_steps - 1))
            _, sub = jax.random.split(key)
            tok_f = _pick_token(last, temperature, top_k, sub, top_p)
            toks = jnp.concatenate([toks, tok_f[None]], axis=0)
            return toks.T                        # (B, n_steps)

        def run_eos(params, prompt, key):
            last, aux = prefill(params, prompt, key)
            buf = jnp.full((B, n_steps), eos_id, jnp.int32)

            def cond(c):
                _aux, _last, _key, _buf, i, done = c
                return (i < n_steps) & ~jnp.all(done)

            def body(c):
                aux, last, key, buf, i, done = c
                key, sub = jax.random.split(key)
                tok = _pick_token(last, temperature, top_k, sub,
                                  top_p).astype(jnp.int32)
                # same emit rule as the host generate(): finished rows
                # keep emitting eos
                tok = jnp.where(done, eos_id, tok)
                done = done | (tok == eos_id)
                buf = jax.lax.dynamic_update_slice(
                    buf, tok[:, None], (0, i))
                # the final iteration's forward is wasted work (its
                # logits are never sampled) — the price of the dynamic
                # exit; everything SKIPPED after all-eos is the win
                last, aux = decode_fwd(params, aux, tok, i, sub)
                return (aux, last, key, buf, i + 1, done)

            c = (aux, last, key, buf, jnp.int32(0),
                 jnp.zeros((B,), bool))
            return jax.lax.while_loop(cond, body, c)[3]

        fn = jax.jit(run_scan if eos_id is None else run_eos)
        self._loop_cache[key_] = fn
        return fn

    def serving_decoder(self, **kwargs):
        """A continuous-batching decoder over this model's weights: a
        fixed slot pool (one slot per batch row) over the on-device KV
        cache, admitting queued prompts the step after a sequence
        finishes (mxnet_tpu/serve/decode.py has the semantics).
        kwargs forward to :class:`~mxnet_tpu.serve.ContinuousDecoder`."""
        from .serve.decode import ContinuousDecoder
        return ContinuousDecoder(self, **kwargs)

    def _prefill(self, aux, tokens):
        """The caches after ``tokens`` (R, P0) from position 0, and
        nothing else: a diffusion prefill reads no logits. ``aux`` is
        an R-row state (``_fresh_aux(R)``): ``batch_size`` rows for
        generate(), fewer where a pool admits fewer
        (serve/decode.py's ``_prefill_group``), each R a shape of the
        one program."""
        args = dict(self._params)
        args["data"] = jnp.asarray(tokens, jnp.float32)
        args["positions"] = jnp.arange(tokens.shape[1],
                                       dtype=jnp.float32)
        args["cache_pos"] = jnp.zeros((1,), jnp.float32)
        return self._prefill_fn(args, aux, jax.random.PRNGKey(0))

    def _generate_blocks(self, prompt, P, n, on_token, on_block_logits):
        """generate() under ``diffusion``: T + 1 forwards a block (T
        denoising, one commit; the last block needs no commit). Rows
        share their positions, so one row may ride a forward it has
        no mask left for while another still denoises."""
        d = self._diffusion
        L, mask_id = d["block_length"], d["mask_id"]
        B = self.batch_size
        P0 = P // L * L
        aux = self._fresh_aux()
        if P0:
            aux = self._prefill(aux, prompt[:, :P0])
        out = np.concatenate(
            [prompt.astype(np.int64),
             np.full((B, self.block_span(P, n) - P), mask_id, np.int64)],
            axis=1)
        known = np.zeros(out.shape, bool)
        known[:, :P] = True
        for pos in range(P0, out.shape[1], L):
            ids, masked = out[:, pos:pos + L], ~known[:, pos:pos + L]
            while masked.any():
                logits, _ = self._forward(aux, ids, pos)
                best, conf = (np.asarray(a) for a in
                              block_picks(logits))
                if on_block_logits is not None:
                    on_block_logits(pos, ids.copy(), masked.copy(),
                                    np.asarray(logits, np.float32))
                take = unmask_choice(masked, conf, d)
                ids[take] = best[take]
                masked &= ~take
            if on_token is not None:
                for p in range(max(pos, P), min(pos + L, P + n)):
                    on_token(out[:, p].copy())
            if pos + L < out.shape[1]:
                _, aux = self._forward(aux, ids, pos)     # commit
        return out[:, :P + n]

    def generate(self, prompt, max_new_tokens, temperature=0.0,
                 top_k=None, top_p=None, eos_id=None, seed=0,
                 on_token=None, on_block_logits=None):
        """Greedy (temperature 0) or sampled continuation.

        prompt: (B, P) int token ids. Returns (B, P + n) ids as numpy
        (n <= max_new_tokens; generation stops early only when every
        row has emitted eos_id). ``on_token``, when given, is called
        with each round's (B,) numpy token array as soon as it is
        picked — the local twin of the serve path's streamed frames
        (the returned rows are exactly the concatenation the callback
        saw, so callers can cross-check stream against one-shot).

        Under ``diffusion`` the continuation is filled a block at a
        time (see the class docstring): always (B, P + max_new_tokens)
        ids, greedy; ``on_token`` sees each position's (B,) ids, in
        order, as each block completes; ``on_block_logits(pos, ids,
        masked, logits)`` sees every denoising forward: the block's
        first position, its (B, L) input ids (``mask_id`` where
        masked), the (B, L) mask and the float32 (B, L, V) logits."""
        self._check_sampling(temperature, top_k, top_p)
        prompt, P = self._check_prompt(prompt, max_new_tokens)
        if self._diffusion:
            if (temperature and float(temperature) > 0) or \
                    eos_id is not None:
                raise ValueError("diffusion generate() is greedy and "
                                 "of fixed length: no temperature, no "
                                 "eos_id")
            if int(max_new_tokens) == 0:
                return np.asarray(prompt, np.int64)
            return self._generate_blocks(prompt, P, int(max_new_tokens),
                                         on_token, on_block_logits)
        if on_block_logits is not None:
            raise ValueError("on_block_logits needs diffusion=")
        key = jax.random.PRNGKey(seed)
        aux = self._fresh_aux()
        logits, aux = self._forward(aux, prompt, 0)
        ids = [prompt]
        done = np.zeros((self.batch_size,), bool)
        last = logits[:, -1]
        for i in range(max_new_tokens):
            key, sub = jax.random.split(key)
            nxt = np.asarray(_pick_token(last, temperature, top_k,
                                         sub, top_p))
            if eos_id is not None:
                nxt = np.where(done, eos_id, nxt)
                done |= nxt == eos_id
            ids.append(nxt[:, None])
            if on_token is not None:
                on_token(nxt.copy())
            if eos_id is not None and done.all():
                break
            if i + 1 < max_new_tokens:
                logits, aux = self._forward(aux, nxt[:, None], P + i)
                last = logits[:, -1]
        return np.concatenate(ids, axis=1)


def _quantize_weights(arg_params, decode_args):
    """Weight-only int8: for every quantized layer in the decode graph
    (marked by its "<name>_scale" argument), replace the float
    "<name>_weight" with per-output-channel symmetric int8 + f32 scale.
    Other params (embeddings, norms, biases) pass through."""
    out = {k: v for k, v in arg_params.items()}
    for arg in decode_args:
        if not arg.endswith("_scale"):
            continue
        wname = arg[:-len("_scale")] + "_weight"
        if wname not in out:
            continue
        w = np.asarray(getattr(out[wname], "_data", out[wname]),
                       np.float32)
        scale = np.maximum(np.abs(w).max(axis=1), 1e-12) / 127.0
        out[wname] = np.clip(np.rint(w / scale[:, None]),
                             -127, 127).astype(np.int8)
        out[arg] = scale.astype(np.float32)
    return out


def replay_key(seed, picks):
    """The per-request PRNG key after ``picks`` tokens have been drawn
    from the stream seeded by ``seed``.

    Every sampling path in this module and in the serving decoder
    follows one discipline: ``key = PRNGKey(seed)`` then exactly one
    ``key, sub = split(key)`` per drawn token (a disaggregated
    handoff's remote first token consumed the first split, so
    local-pick and handoff admissions alike sit at ``len(emitted)``
    splits after k emitted tokens). That makes PRNG progress derivable
    state: a migrated session (``ContinuousDecoder.export_session`` /
    ``submit(resume=...)``) re-derives its key here and the resumed
    stream continues bit-exactly."""
    key = jax.random.PRNGKey(int(seed or 0))
    for _ in range(int(picks)):
        key, _ = jax.random.split(key)
    return key


def _pick_token(logits, temperature, top_k, key, top_p=None):
    """logits (B, V) -> (B,) int32, on device."""
    logits = logits.astype(jnp.float32)
    if temperature and float(temperature) > 0:
        logits = logits / float(temperature)
        if top_k:
            # kth-largest threshold via top_k, not a full V-sort — this
            # sits on the per-token decode hot path
            kth = jax.lax.top_k(logits, int(top_k))[0][:, -1:]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        if top_p and float(top_p) < 1.0:
            # nucleus: keep the smallest prefix of descending-prob
            # tokens whose mass reaches top_p (the first token past the
            # threshold is included, per the standard formulation)
            srt = jnp.sort(logits, axis=-1)[:, ::-1]
            probs = jax.nn.softmax(srt, axis=-1)
            mass = jnp.cumsum(probs, axis=-1)
            keep = mass - probs < float(top_p)       # (B, V) on sorted
            cut = jnp.where(keep, srt, jnp.inf).min(axis=-1,
                                                    keepdims=True)
            logits = jnp.where(logits < cut, -jnp.inf, logits)
        return jax.random.categorical(key, logits, axis=-1)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)
