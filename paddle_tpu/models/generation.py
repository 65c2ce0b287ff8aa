"""Text generation — greedy/sampling decode with KV cache.

Analog of the reference's generation path (the fused_multi_transformer /
masked_multihead_attention decode kernels,
paddle/phi/kernels/fusion/gpu/fused_multi_transformer_op.cu, plus
PaddleNLP's generate loop). TPU-natively: prefill is ONE compiled program
and the whole decode loop is a SECOND compiled program — model forward
over donated KV-cache buffers plus sampling, scanned over the new tokens
inside one executable (the decoder-inference-loop-in-one-program shape of
fused_multi_transformer_op.cu), so serving pays one dispatch per generate
call instead of hundreds per token. ``use_jit=False`` keeps the per-token
eager loop (each op served from the cached-executable dispatch).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core import autograd, random as _random
from ..core.tensor import Tensor

__all__ = ["generate", "build_serve_fn"]


def _sample_with_key(logits, key, temperature, top_k, top_p, greedy):
    """Pure sampling rule — traceable; ``key`` is a PRNG key (ignored when
    greedy)."""
    if greedy:
        return jnp.argmax(logits, axis=-1)
    logits = logits / max(temperature, 1e-5)
    if top_k is not None and top_k > 0:
        kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = jnp.where(logits < kth, -1e30, logits)
    if top_p is not None and 0.0 < top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -1e30, logits)
    return jax.random.categorical(key, logits, axis=-1)


def _sample(logits, temperature, top_k, top_p, greedy):
    key = None if greedy else _random.next_key()
    return _sample_with_key(logits, key, temperature, top_k, top_p, greedy)


def _sample_rows(logits, keys, temperature, top_k, top_p, greedy):
    """Per-row sampling: row i of ``logits`` (N, V) is drawn with ITS OWN
    key from ``keys`` ((N,) + key-data shape) — the batched form the
    serving engine uses for per-request key streams, so a row's tokens
    never depend on who it was batched with. Greedy ignores the keys
    entirely (callers pass cached zeros)."""
    if greedy:
        return jnp.argmax(logits, axis=-1)
    typed = jax.random.wrap_key_data(keys)
    return jax.vmap(
        lambda lg, k: _sample_with_key(lg, k, temperature, top_k, top_p,
                                       False))(logits, typed)


def _make_static_cache(k, v, length):
    from .llama import StaticCache

    c = StaticCache.__new__(StaticCache)
    c.k, c.v, c.length = k, v, length
    return c


def sequence_keeps(model):
    """What one sequence of ``model`` keeps, a LAYER: the ONE question every
    cache is sized from. A tuple with an entry a layer. ``("pages", k_shape,
    v_shape)``: something a token, as the trailing shapes of the layer's two
    cache buffers after (pages, page) or (batch, max_len). ``("state",
    (s_shape, s_dtype), (z_shape, z_dtype))``: one fixed-size state a
    sequence whatever its length, as the shapes after the slot dimension.
    ``None``: nothing (a layer that mixes channels alone). The answer is the
    model's own where it has a say, a layer (``layer_keeps()``: layers of
    several kinds in one stack) or once for all of them
    (``sequence_state()``: a recurrent layer; ``kv_page_shapes()``: a latent
    cache keeps no per-head keys), else (kv heads, head size) twice, from
    its config."""
    def entry(keep):
        if keep is None:
            return None
        kind, k, v = keep
        if kind == "state":
            return kind, (tuple(k[0]), k[1]), (tuple(v[0]), v[1])
        return kind, tuple(k), tuple(v)

    own = getattr(model, "layer_keeps", None)
    if own is not None:
        return tuple(entry(keep) for keep in own())
    cfg = model.config
    if hasattr(model, "sequence_state"):
        one = ("state", *model.sequence_state())
    elif hasattr(model, "kv_page_shapes"):
        one = ("pages", *model.kv_page_shapes())
    else:
        kv = getattr(cfg, "num_key_value_heads", cfg.num_attention_heads)
        one = ("pages", (kv, cfg.head_dim), (kv, cfg.head_dim))
    return (entry(one),) * cfg.num_hidden_layers


def kv_page_shapes(model):
    """The case of :func:`sequence_keeps` in which every layer keeps the
    same pages, for the callers that build one cache a token a layer
    (``generate``, ``build_serve_fn``); any other model is served through
    ``ContinuousBatchingEngine``."""
    keeps = set(sequence_keeps(model))
    if len(keeps) != 1 or next(iter(keeps)) is None \
            or next(iter(keeps))[0] != "pages":
        raise NotImplementedError(
            f"{type(model).__name__} keeps a recurrent state a sequence in "
            "some layer, or not the same in every layer: generate() builds "
            "keys and values a token for every layer alike; serve it "
            "through ContinuousBatchingEngine (ROADMAP M4)")
    _, k_shape, v_shape = next(iter(keeps))
    return k_shape, v_shape


def _make_paged_cache(kp, vp, tables, page_size, length,
                      aligned_bases=False, attn_pages=None, live=None):
    from .llama import PagedKVCache

    c = PagedKVCache.__new__(PagedKVCache)
    c.k_pages, c.v_pages, c.tables = kp, vp, tables
    c.page_size, c.length = page_size, length
    c.aligned_bases = aligned_bases
    # serving tables carry trailing write-scratch columns past max_len;
    # attn_pages caps how many table columns attention READS (the
    # ragged paged-attention kernel's pages-per-sequence bound)
    c.attn_pages = attn_pages
    c.live = live      # (B,) rows that hold a sequence, or None: all
    c.stats = None     # what a layer counted this step, if it counts
    return c


class StateCache:
    """What the serving engine hands a recurrent layer: the layer's whole
    state arrays ``s`` / ``z`` (a row a slot, the last the scratch slot),
    each batch row's slot (``rows``), how many tokens the row already holds
    (``length``: a static 0, or (B,)), how many of the new ones are real
    (``true_lens`` (B,), or None: all) and, in a decode step, which rows
    hold a sequence (``live``). ``stats`` is what the layer counted."""

    __slots__ = ("s", "z", "rows", "length", "true_lens", "live", "stats")

    def __init__(self, s, z, rows, length=0, true_lens=None, live=None):
        self.s, self.z, self.rows = s, z, rows
        self.length = length
        self.true_lens = true_lens
        self.live = live
        self.stats = None


class LayerPass:
    """What the serving engine hands a layer that keeps nothing: which rows
    hold a sequence (``live``, in a decode step) in, and what the layer
    counted (``stats``) out."""

    __slots__ = ("live", "stats")

    def __init__(self, live=None):
        self.live = live
        self.stats = None


class SequenceStore:
    """The serving engine's per-layer device arrays, two a layer that keeps
    something, for whatever :func:`sequence_keeps` says of each layer: pools
    of pages addressed through the engine's page table (something a TOKEN),
    or a row a slot and a last row, the scratch slot, that an admission
    group's padding rows write (ONE state a sequence). All on ONE table: a
    slot's row names its pages and, where some layer keeps a state, in its
    last column the slot's state row. The engine asks the store for the
    arrays, for the caches a forward runs over, for the arrays a forward
    leaves behind and for the logits it samples a first token from, and
    never looks inside a cache itself; what it may do with a slot follows
    from ``has_pages`` (a pool to plan and grow) and ``has_state`` (granted
    slots are reset; no prefix cache, no page moves)."""

    def __init__(self, keeps, dtype, page_size, attn_pages, chunk_aligned):
        self._keeps, self._dtype = tuple(keeps), dtype
        self._page = page_size
        self._attn_pages, self._aligned = attn_pages, chunk_aligned
        held = [k for k in self._keeps if k is not None]
        self.has_pages = any(k[0] == "pages" for k in held)
        self.has_state = any(k[0] == "state" for k in held)
        # which of the held arrays are a state's (``reset``, ``states``)
        self._is_state = [k[0] == "state" for k in held]
        self.bytes_per_token = sum(
            math.prod(sh) * np.dtype(dtype).itemsize
            for k in held if k[0] == "pages" for sh in k[1:])
        self.bytes_per_slot = sum(
            math.prod(sh) * np.dtype(dt).itemsize
            for k in held if k[0] == "state" for sh, dt in k[1:])
        self.reset = self._reset if self.has_state else None

    def pool_pages(self, asked, max_slots, per_seq):
        """Allocatable pages: by default one full-length sequence a slot;
        none where no layer keeps pages (a granted slot owns its state)."""
        if not self.has_pages:
            return 0
        n = max_slots * per_seq if asked is None else int(asked)
        if n < per_seq:
            raise ValueError(
                f"pool_pages {n} cannot hold one full-length sequence "
                f"({per_seq} pages of {self._page} tokens)")
        return n

    def allocate(self, n_pages, max_slots):
        def one(keep, i):
            if keep[0] == "state":
                shape, dt = keep[i]
                return jnp.zeros((max_slots + 1,) + shape, dt)
            return jnp.zeros((n_pages, self._page) + keep[i], self._dtype)

        return tuple([one(k, i) for k in self._keeps if k is not None]
                     for i in (1, 2))

    def tables(self, max_slots, total_cols, dump_page, scratch_ids):
        """The host page table: a row a slot, every cell on the dump page
        until the allocator grants it, and the scratch row; behind them,
        where a layer keeps a state, the column that names each row's state
        row (the last: the scratch slot's), for good."""
        slot = np.arange(max_slots + 1, dtype=np.int32)[:, None]
        if not self.has_pages:
            return slot              # ``lengths`` keeps positions and budgets
        tables = np.full((max_slots + 1, total_cols), dump_page, np.int32)
        tables[max_slots] = scratch_ids
        return np.hstack([tables, slot]) if self.has_state else tables

    def set_row(self, row, pages, dump_page):
        """A slot's granted pages into its table row; the tail aliases the
        dump page."""
        if self.has_pages:
            end = row.size - self.has_state
            row[:len(pages)] = pages
            row[len(pages):end] = dump_page

    def caches(self, ks, vs, tables, length, aligned=None, live=None,
               true_lens=None):
        # chunked-prefill bases are chunk multiples: page-aligned (the
        # bulk-write opt-in) exactly when the chunk is a page multiple;
        # the prefix-RESUME path passes aligned=False -- its bases start
        # at the first divergent token, which may sit mid-page.
        # ``true_lens`` is a state's concern alone: a page layer never
        # reads what padding wrote
        if aligned is None:
            aligned = self._aligned
        rows = tables[:, -1] if self.has_state else None
        if self.has_state and self.has_pages:
            tables = tables[:, :-1]
        out, held = [], iter(zip(ks, vs))
        for keep in self._keeps:
            if keep is None:
                out.append(LayerPass(live))
            elif keep[0] == "state":
                out.append(StateCache(*next(held), rows, length, true_lens,
                                      live))
            else:
                out.append(_make_paged_cache(
                    *next(held), tables, self._page, length,
                    aligned_bases=aligned, attn_pages=self._attn_pages,
                    live=live))
        return out

    @staticmethod
    def pools(caches):
        pairs = [(c.s, c.z) if isinstance(c, StateCache)
                 else (c.k_pages, c.v_pages)
                 for c in caches if not isinstance(c, LayerPass)]
        return [p[0] for p in pairs], [p[1] for p in pairs]

    def last_logits(self, logits, true_lens):
        if self.has_state:
            # the model was told the true lengths and took its head at that
            # position alone: (N, 1, V)
            return logits[:, 0]
        # each row's TRUE last position (padding rows are never read)
        idx = (true_lens - 1).astype(jnp.int32)[:, None, None]
        return jnp.take_along_axis(
            logits, jnp.broadcast_to(
                idx, (logits.shape[0], 1, logits.shape[-1])), axis=1)[:, 0]

    def _reset(self, ks, vs, rows):
        # a granted slot starts from the zero state (padding lanes zero
        # the scratch slot); its pages need no zeroing
        return tuple([a.at[rows].set(0) if state else a
                      for a, state in zip(arrays, self._is_state)]
                     for arrays in (ks, vs))

    def states(self, ks, vs):
        """The state layers' pairs of arrays, in layer order."""
        return [(k, v) for k, v, state in zip(ks, vs, self._is_state)
                if state]


def sequence_store(model, dtype, page_size, attn_pages, chunk_aligned):
    """The store for what :func:`sequence_keeps` says ``model`` keeps."""
    return SequenceStore(sequence_keeps(model), dtype, page_size, attn_pages,
                         chunk_aligned)


def _generate_jit(model, ids, max_new_tokens, do_sample, temperature,
                  top_k, top_p, eos_token_id, paged, empty):
    """Compiled serving path: prefill program + ONE scanned decode program
    with donated cache buffers. Token/RNG semantics match the eager loop
    (same host-stream key per sampled token), except that generation never
    stops early — finished rows are eos-padded to the full length."""
    from ..jit import _FunctionalModel

    b, s = ids.shape
    n_layers = len(empty)
    functional = _FunctionalModel(model)
    params = {k: p._value for k, p in model.named_parameters()}
    buffers = {k: bu._value for k, bu in model.named_buffers()}
    zero_key = jax.random.key_data(jax.random.PRNGKey(0))
    if paged:
        tables = empty[0].tables
        page_size = empty[0].page_size

        # tables ride as a PROGRAM OPERAND (never a closure constant): the
        # cached programs must serve any batch/prompt shape, keyed by jit's
        # own shape specialization
        def rebuild(ks, vs, length, tbl):
            return [_make_paged_cache(ks[i], vs[i], tbl, page_size, length)
                    for i in range(n_layers)]

        cache_ks = [c.k_pages for c in empty]
        cache_vs = [c.v_pages for c in empty]
    else:
        tables = None
        page_size = None

        def rebuild(ks, vs, length, tbl):
            return [_make_static_cache(ks[i], vs[i], length)
                    for i in range(n_layers)]

        cache_ks = [c.k for c in empty]
        cache_vs = [c.v for c in empty]

    # programs cached on the model instance; jax.jit specializes by shape.
    # Everything ELSE baked into the trace must be in this key.
    progs = model.__dict__.setdefault("_generation_programs", {})
    prog_key = (paged, page_size, do_sample, temperature, top_k, top_p,
                eos_token_id)
    if prog_key not in progs:

        def prefill(params, buffers, ids, ks, vs, tbl):
            caches = rebuild(ks, vs, 0, tbl)
            (logits, caches2), _ = functional(
                params, buffers, (ids,), {"caches": caches}, zero_key)
            if paged:
                return (logits[:, -1, :], [c.k_pages for c in caches2],
                        [c.v_pages for c in caches2])
            return (logits[:, -1, :], [c.k for c in caches2],
                    [c.v for c in caches2])

        def decode(params, buffers, ks, vs, tbl, length0, tok0, fin0, keys):
            def body(carry, key_i):
                tok, ks, vs, length, fin = carry
                caches = rebuild(ks, vs, length, tbl)
                (logits, caches2), _ = functional(
                    params, buffers, (tok[:, None],), {"caches": caches},
                    zero_key)
                nxt = _sample_with_key(
                    logits[:, -1, :], jax.random.wrap_key_data(key_i),
                    temperature, top_k, top_p, not do_sample)
                nxt = nxt.astype(tok.dtype)
                if eos_token_id is not None:
                    nxt = jnp.where(fin, eos_token_id, nxt)
                    fin = fin | (nxt == eos_token_id)
                if paged:
                    new_ks = [c.k_pages for c in caches2]
                    new_vs = [c.v_pages for c in caches2]
                else:
                    new_ks = [c.k for c in caches2]
                    new_vs = [c.v for c in caches2]
                return (nxt, new_ks, new_vs, caches2[0].length, fin), nxt

            (tok, ks, vs, length, fin), toks = jax.lax.scan(
                body, (tok0, ks, vs, length0, fin0), keys)
            # final cache buffers ride out so the donated inputs alias the
            # outputs (and a caller could continue decoding from them)
            return toks, ks, vs  # toks: (steps, B)

        progs[prog_key] = (jax.jit(prefill),
                           jax.jit(decode, donate_argnums=(2, 3)))
    prefill_p, decode_p = progs[prog_key]

    last_logits, cache_ks, cache_vs = prefill_p(
        params, buffers, ids, cache_ks, cache_vs, tables)
    # token 0 sampled host-side from the prefill logits — consumes the host
    # RNG stream exactly like the eager loop's first _sample
    tok0 = _sample(last_logits, temperature, top_k, top_p, not do_sample)
    tok0 = tok0.astype(ids.dtype)
    fin0 = jnp.zeros((b,), bool)
    if eos_token_id is not None:
        fin0 = fin0 | (tok0 == eos_token_id)
    steps = max_new_tokens - 1
    if steps > 0:
        if do_sample:
            keys = jnp.stack([jax.random.key_data(_random.next_key())
                              for _ in range(steps)])
        else:
            keys = jnp.zeros((steps,) + zero_key.shape, zero_key.dtype)
        toks, cache_ks, cache_vs = decode_p(
            params, buffers, cache_ks, cache_vs, tables,
            jnp.asarray(s, jnp.int32), tok0, fin0, keys)
        out = jnp.concatenate([ids, tok0[:, None], toks.T], axis=1)
    else:
        out = jnp.concatenate([ids, tok0[:, None]], axis=1)
    return Tensor._from_value(out)


def build_serve_fn(model, max_new_tokens, do_sample=False, temperature=1.0,
                   top_k=None, top_p=None, eos_token_id=None, cache="paged"):
    """Pure ``serve(params, ids, keys) -> (B, S + max_new_tokens) ids`` for
    EXPORT (jit.save_generate): prefill + the scanned decode loop + sampling
    in ONE program, with the KV caches allocated inside so the artifact has
    no cross-call state (the deployment shape of the reference's
    fused_multi_transformer serving path; analysis_predictor.h:105 loads
    the equivalent frozen program). ``keys`` is a (max_new_tokens, ...)
    stack of PRNG key data — ignored (but still an operand, so one artifact
    serves any seed) when sampling is off."""
    from ..jit import _FunctionalModel
    from .llama import PagedKVCache, StaticCache

    cfg = model.config
    shapes = kv_page_shapes(model)
    n_layers = cfg.num_hidden_layers
    functional = _FunctionalModel(model)
    buffers = {k: bu._value for k, bu in model.named_buffers()}
    zero_key = jax.random.key_data(jax.random.PRNGKey(0))
    paged = cache == "paged"
    try:
        cache_dtype = next(iter(model.parameters()))._value.dtype
    except StopIteration:
        cache_dtype = jnp.float32

    def serve(params, ids, keys):
        b, s = ids.shape
        max_len = s + max_new_tokens
        if paged:
            page = 128
            padded = ((max_len + page - 1) // page) * page
            empty = [PagedKVCache(b, padded, page_size=page,
                                  dtype=cache_dtype, shapes=shapes)
                     for _ in range(n_layers)]
            tables = empty[0].tables
            page_size = empty[0].page_size

            def rebuild(ks, vs, length):
                return [_make_paged_cache(ks[i], vs[i], tables, page_size,
                                          length) for i in range(n_layers)]

            ks0 = [c.k_pages for c in empty]
            vs0 = [c.v_pages for c in empty]
        else:
            empty = [StaticCache(b, max_len, dtype=cache_dtype,
                                 shapes=shapes) for _ in range(n_layers)]

            def rebuild(ks, vs, length):
                return [_make_static_cache(ks[i], vs[i], length)
                        for i in range(n_layers)]

            ks0 = [c.k for c in empty]
            vs0 = [c.v for c in empty]

        def unpack(caches):
            if paged:
                return ([c.k_pages for c in caches],
                        [c.v_pages for c in caches])
            return [c.k for c in caches], [c.v for c in caches]

        (logits, caches), _ = functional(
            params, buffers, (ids,), {"caches": rebuild(ks0, vs0, 0)},
            zero_key)
        ks, vs = unpack(caches)
        tok0 = _sample_with_key(
            logits[:, -1, :], jax.random.wrap_key_data(keys[0]),
            temperature, top_k, top_p, not do_sample).astype(ids.dtype)
        fin0 = jnp.zeros((b,), bool)
        if eos_token_id is not None:
            fin0 = fin0 | (tok0 == eos_token_id)

        def body(carry, key_i):
            tok, ks, vs, length, fin = carry
            (logits, caches2), _ = functional(
                params, buffers, (tok[:, None],),
                {"caches": rebuild(ks, vs, length)}, zero_key)
            nxt = _sample_with_key(
                logits[:, -1, :], jax.random.wrap_key_data(key_i),
                temperature, top_k, top_p, not do_sample).astype(tok.dtype)
            if eos_token_id is not None:
                nxt = jnp.where(fin, eos_token_id, nxt)
                fin = fin | (nxt == eos_token_id)
            ks2, vs2 = unpack(caches2)
            return (nxt, ks2, vs2, caches2[0].length, fin), nxt

        if max_new_tokens > 1:
            _, toks = jax.lax.scan(
                body, (tok0, ks, vs, jnp.asarray(s, jnp.int32), fin0),
                keys[1:])
            return jnp.concatenate([ids, tok0[:, None], toks.T], axis=1)
        return jnp.concatenate([ids, tok0[:, None]], axis=1)

    return serve


def generate(model, input_ids, max_new_tokens=20, do_sample=False,
             temperature=1.0, top_k=None, top_p=None, eos_token_id=None,
             cache="static", use_jit=True):
    """Decode ``max_new_tokens`` continuations of ``input_ids`` (B, S).

    The model must support ``forward(ids, attn_mask=None, caches=...)``
    returning (logits, caches) — models.LlamaForCausalLM / GPT-style.
    ``cache``: "static" = fixed-size per-sequence buffers
    (masked_multihead_attention semantics); "paged" = block-table paged
    pool served by the Pallas paged_attention kernel
    (block_multi_head_attention semantics). Returns (B, S + new) ids.

    ``use_jit=True`` (default) compiles prefill + the whole decode loop
    into two XLA programs (fused_multi_transformer decode-loop semantics);
    with an ``eos_token_id`` the output is always eos-padded to the full
    ``S + max_new_tokens`` width. ``use_jit=False`` decodes token-by-token
    eagerly and stops early once every row has finished.
    """
    ids = input_ids._value if isinstance(input_ids, Tensor) else jnp.asarray(input_ids)
    if max_new_tokens < 0:
        raise ValueError(f"max_new_tokens must be >= 0, got {max_new_tokens}")
    if max_new_tokens == 0:  # nothing to generate: (B, S + 0) = the input
        return Tensor._from_value(ids)
    b, s = ids.shape
    cfg = model.config
    shapes = kv_page_shapes(model)
    max_len = s + max_new_tokens
    maxp = getattr(cfg, "max_position_embeddings", None)
    # the FINAL sampled token is appended but never fed back, so with
    # max_new_tokens >= 1 (the 0 case returned above) the highest embedded
    # position is max_len - 2; beyond the position table the gather would
    # silently clamp (repeating the last learned position / rope row) —
    # refuse loudly, BEFORE touching train mode
    if maxp is not None and max_len - 1 > maxp:
        raise ValueError(
            f"prompt ({s}) + max_new_tokens ({max_new_tokens}) would embed "
            f"position {max_len - 2} beyond "
            f"max_position_embeddings ({maxp})")
    was_training = getattr(model, "training", False)
    model.eval()
    from .llama import PagedKVCache, StaticCache

    # cache in the model's compute dtype (bf16 models keep a bf16 KV cache)
    try:
        cache_dtype = next(iter(model.parameters()))._value.dtype
    except StopIteration:
        cache_dtype = jnp.float32
    if cache == "paged":
        page = 128
        padded = ((max_len + page - 1) // page) * page
        empty = [PagedKVCache(b, padded, page_size=page, dtype=cache_dtype,
                              shapes=shapes)
                 for _ in range(cfg.num_hidden_layers)]
    else:
        empty = [StaticCache(b, max_len, dtype=cache_dtype, shapes=shapes)
                 for _ in range(cfg.num_hidden_layers)]

    if use_jit:
        try:
            with autograd.no_grad():
                return _generate_jit(model, ids, max_new_tokens, do_sample,
                                     temperature, top_k, top_p, eos_token_id,
                                     cache == "paged", empty)
        finally:
            if was_training:
                model.train()

    try:
        with autograd.no_grad():
            logits, caches = model(Tensor._from_value(ids), caches=empty)
            next_tok = _sample(logits._value[:, -1, :], temperature, top_k,
                               top_p, not do_sample)
            finished = jnp.zeros((b,), bool)
            if eos_token_id is not None:
                finished = finished | (next_tok == eos_token_id)
            out = [ids, next_tok[:, None]]
            for step in range(max_new_tokens - 1):
                # static cache: every decode step has identical shapes -> the
                # per-op executable cache serves each op from one compiled
                # program (masked_multihead_attention decode-loop behavior)
                logits, caches = model(
                    Tensor._from_value(next_tok[:, None]), caches=caches)
                next_tok = _sample(logits._value[:, -1, :], temperature,
                                   top_k, top_p, not do_sample)
                if eos_token_id is not None:
                    finished = finished | (next_tok == eos_token_id)
                    next_tok = jnp.where(finished, eos_token_id, next_tok)
                out.append(next_tok[:, None])
                if eos_token_id is not None and bool(finished.all()):
                    break
            return Tensor._from_value(jnp.concatenate(out, axis=1))
    finally:
        if was_training:
            model.train()
