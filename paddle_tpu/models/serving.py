"""Continuous batching over the paged KV cache — the serving scheduler.

Goes beyond the reference's in-tree serving (its kernel-level anchor is the
block/paged cache of paddle/phi/kernels/fusion/gpu/
block_multi_head_attention_kernel.cu; the scheduler itself lives out of
tree in PaddleNLP's serving stack): requests of mixed lengths are admitted
into fixed SLOTS of a shared page pool, decode runs as compiled
multi-token SEGMENTS over all slots at PER-SLOT depths, and slots retire
and readmit between segments — so the chip never drains to serve one
straggler.

TPU-native shape: everything device-side is a fixed-shape compiled
program. Prefill programs per (prompt-length bucket x admission group
width) write new requests' KV into their slots' pages (power-of-two
widths, donated pools — a single admission pays a width-1 forward, not a
``max_slots``-wide one). ONE decode program scans a segment of steps over
the full slot batch, with per-slot lengths driving paged attention,
per-slot rope positions, and an active mask freezing finished slots.

The host loop is an OVERLAPPED scheduler (the ragged-paged-attention
serving discipline): segment N+1 is dispatched from segment N's DEVICE
outputs (token/lengths/active carry — no host round trip) while the host
consumes N's results, so the chip stays busy through host bookkeeping.
Whenever the host changes the slot mask in a way the device cannot see
(admission, abort, deadline retirement), the pipeline drains and the next
dispatch is a synchronous turn from host state. Sampling uses PER-REQUEST
key streams — a pure function of (engine seed, rid, token index) — so a
speculatively dispatched segment, a bisection replay, and the serial
schedule all emit bit-identical tokens. ``pipeline=False`` selects the
serial one-segment-at-a-time loop.

``warmup()`` AOT-compiles (``jit(...).lower().compile()``) every declared
(bucket x group-width) prefill shape plus the chunked-prefill and
decode-segment programs (``jit.enable_compilation_cache()`` keeps them
across process restarts), so first-request latency and ``stats()``
throughput stop absorbing compile time.

KV memory is a DYNAMIC PAGE POOL (``models/kv_pool.py``), not a frozen
slot->page map: a slot is granted pages for its prompt at admission and
grows lazily as decode crosses page boundaries; retirement frees them.
Admission is bounded by *available pages* — many short requests can be
in flight where one long one fit before — with
``serving.kv_pool_exhausted`` backpressure (the queue head defers, a
running decode never fails: if growth outruns the pool the youngest slot
is PREEMPTED back to the queue and later resumes bit-identically via its
per-request key stream). Prompt prefixes are shared COPY-ON-WRITE: full
prompt pages are content-hashed into a :class:`kv_pool.PrefixCache`, a
new request maps already-computed pages read-only and prefills only from
the first divergent token (a mid-page divergence pays one device page
copy), and refcounts keep shared pages alive across the owners'
retirements. Page-table CONTENTS change at grant time; traced shapes
never do — the zero-post-warmup-compile invariant holds through the
allocator path.
"""
from __future__ import annotations

import contextlib
import logging
import time
import uuid
import zlib
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from ..core import perfwatch, telemetry
from ..core.resilience import (
    Deadline,
    InjectedFault,
    ServingUnavailable,
    bump_counter,
    inject,
)
from ..core.tensor import Tensor
from ..profiler import annotate, note_program, record_span
from .generation import _sample_rows, sequence_store
from .kv_pool import PagePool, PrefixCache

__all__ = ["ContinuousBatchingEngine", "Request", "TERMINAL_STATES"]

logger = logging.getLogger("paddle_tpu.serving")

# Every terminal status the engine can stamp on a Request (the frontend
# adds admission-level "rejected"/"unavailable" on top). The router's
# retirement switch is CI-gated against this set
# (tests/test_no_bare_except.py): a new terminal state added here without
# a router handler fails the guard, not production traffic.
TERMINAL_STATES = frozenset({"ok", "timed_out", "failed", "cancelled"})

# serving-path metrics (module-level handles: registry reset zeroes them
# in place, so caching here is safe and keeps the hot-path cost at one
# lock). Names are documented in README "Observability" and CI-gated
# against orphaning (tests/test_telemetry_guard.py).
_M_TTFT = telemetry.histogram(
    "serving.ttft_s", "submit -> first token (queue wait included; "
    "fresh attempts only — token_base>0 failover continuations are "
    "excluded)")
_M_TOK = telemetry.histogram(
    "serving.token_latency_s", "mean per-token decode latency, observed "
    "once per retired request over its post-first-token stream")
_M_ENGINE_WAIT = telemetry.histogram(
    "serving.engine_wait_s", "engine.submit -> the admission that grants "
    "the request its slot: the wait in the engine's own queue, which "
    "serving.queue_wait_s (the frontend's queue) ends before")
_M_ADMIT_FIRST = telemetry.histogram(
    "serving.admit_to_first_s", "slot granted -> first token fetched: "
    "the request's prefill as it waited for it (host preparation, the "
    "dispatches, the blocking fetch; co-admitted groups ahead of it)")
_M_STALL_S = telemetry.counter(
    "serving.decode_stall_s_total", "seconds between the end of a "
    "consume and the next SYNC segment dispatch while slots were "
    "decoding (the serving.decode_stall spans): decode time lost to an "
    "admission's prefill, a dirty mask or a replay")
_M_TOKENS = telemetry.counter(
    "serving.tokens_total", "tokens emitted by the engine scheduler")
_M_REQS = telemetry.counter(
    "serving.requests_total", "terminal request verdicts, by status")
_M_ATTN_LIVE = telemetry.counter(
    "serving.attn_pages_live_total", "pages that hold tokens at a decode "
    "segment's dispatch, sum of ceil(length / page) over every slot (idle "
    "ones park at length 1): what paged attention walks")
_M_ATTN_TABLE = telemetry.counter(
    "serving.attn_pages_table_total", "max_slots x attention-visible "
    "table columns per decode segment dispatched; live over table is the "
    "share of the page table that held tokens")
_M_PREFILL_LIVE = telemetry.counter(
    "serving.prefill_attn_cols_live_total", "cache columns a prefill over "
    "a cache has to score, sum of base + chunk over the real rows of every "
    "chunk, final-chunk and prefix-resume dispatch (the host's own bases): "
    "what the paged flash forward walks")
_M_PREFILL_TABLE = telemetry.counter(
    "serving.prefill_attn_cols_table_total", "real rows x attention-"
    "visible table columns x page size per such dispatch: what a table-"
    "wide masked composition scores; live over table is the share of it "
    "that held anything to attend")
_M_STATE_TOKENS = telemetry.counter(
    "serving.state_prefill_tokens_total", "real prompt tokens a state "
    "model's prefill dispatches (prefill, chunk, final chunk) fed through "
    "the recurrence: the host's own true lengths")
_M_STATE_PADDED = telemetry.counter(
    "serving.state_prefill_padded_total", "positions of those dispatches "
    "that were masked out of the state update (bucket padding past a row's "
    "true length, padding rows of an admission group)")
# KV-occupancy accounting (perfwatch): the measurement side of the
# paged-KV roadmap item — logical occupancy of the preallocated page
# pool, not PJRT allocator bytes (the pool is allocated up front; the
# watchdog gauges device.* cover the allocator).
_M_KV_BYTES = telemetry.gauge(
    "serving.kv_bytes_in_use", "KV bytes physically occupied by active "
    "slots (whole pages; a prefix-shared page counts ONCE, so the gauge "
    "never exceeds the pool)")
_M_KV_OCC = telemetry.gauge(
    "serving.kv_slot_occupancy", "active slots / total slots")
_M_KV_FRAG = telemetry.gauge(
    "serving.kv_fragmentation_pct", "allocated-but-unused tail of the "
    "pages GRANTED to active slots: 100 * (1 - used tokens / granted "
    "page capacity) — the waste the dynamic allocator bounds to less "
    "than one page per slot (the static slot map wasted the whole "
    "unreached slot tail)")
_M_KV_PAGES_FREE = telemetry.gauge(
    "serving.kv_pages_free", "KV pool pages on the free list (grantable "
    "to admissions and decode growth right now)")
_M_KV_PAGES_TOTAL = telemetry.gauge(
    "serving.kv_pages_total", "total allocatable KV pool pages (scratch "
    "pages excluded)")
_M_KV_SLOT_PAGES = telemetry.gauge(
    "serving.kv_slot_pages", "pages currently granted to one slot, by "
    "{slot=} — the per-slot view `obs kv` renders")
_M_PREFIX_HIT = telemetry.gauge(
    "serving.prefix_hit_rate", "prompt tokens served from the prefix "
    "cache / prompt tokens admitted, over the session")
_M_PREFIX_SAVED = telemetry.counter(
    "serving.prefix_tokens_saved", "prompt tokens whose prefill was "
    "skipped because a cached prefix page already held their KV")
_M_KV_REQ = telemetry.histogram(
    "serving.kv_request_bytes", "per-request KV footprint at retirement "
    "(prompt + emitted tokens, page-rounded)",
    buckets=tuple(float(2 ** p) for p in range(10, 31, 2)))
_M_KV_PINNED = telemetry.gauge(
    "serving.kv_pages_pinned_export", "pool pages pinned for KV export "
    "(prefill handoff holds, live transfer tickets, and partially "
    "imported chunks) — granted but invisible to the slot table, so "
    "`obs kv` pool-pressure readings stay honest")


_cwd = None


def _compile_watchdog():
    """Lazy jit-layer import (the jit package imports heavy deps)."""
    global _cwd
    if _cwd is None:
        from ..jit.compile_watch import compile_watchdog

        _cwd = compile_watchdog()
    return _cwd


class Request:
    """One in-flight generation request inside the engine scheduler.

    ``status`` lifecycle: ``pending`` → (``ok`` | ``timed_out`` |
    ``failed`` | ``cancelled``). ``tokens`` accumulates generated ids;
    ``poisoned`` is the sticky poison mark set when the
    ``serving.engine_fault`` injection site fires for this request, so
    bisection retries fail deterministically on the same offender.

    ``token_base`` is the request's sampling-stream offset: a FAILOVER
    RESUME (router resubmitting a request stranded on a dead replica)
    submits ``original prompt + the k tokens already emitted`` as the
    prompt with ``token_base=k``, so the first token sampled here is
    stream index ``k`` — bit-identical to the continuation the
    uninterrupted run would have produced.

    ``trace`` is the request's telemetry trace id (minted by the router
    or frontend, riding the RPC envelope across processes); dispatch
    spans and the retire event carry it so one rid's whole life — queue
    wait, prefill, every decode segment, failover hops — stitches into
    one timeline. ``t_submit``/``t_first`` anchor the TTFT and per-token
    latency histograms (monotonic; ``t_submit`` is overwritten by the
    frontend with its own admission stamp so queue wait counts).
    ``t_queued`` is when the ENGINE's queue took the request (again after
    a preemption) and ``t_admit`` when an admission granted its slot:
    ``serving.engine_wait`` lies between them, ``serving.admit_to_first_s``
    between ``t_admit`` and ``t_first``.

    ``hold_kv`` marks a disaggregated PREFILL request: on "ok"
    retirement the slot's page grants move to the engine's export hold
    table (refcounts intact) instead of the free list, awaiting an
    ``export_pages`` ticket. ``kv_import`` names a completed import ticket
    a DECODE-side request adopts at admission — the request seats
    directly onto the imported pages with the prefill's first token
    already emitted, no prefill dispatch.
    """

    __slots__ = ("rid", "prompt", "max_new_tokens", "deadline", "tokens",
                 "status", "poisoned", "poison_checked", "error",
                 "token_base", "trace", "t_submit", "t_first", "tenant",
                 "preempted", "hold_kv", "kv_import", "t_queued",
                 "t_admit", "slot")

    def __init__(self, rid, prompt, max_new_tokens, deadline=None,
                 token_base=0, trace=None, tenant=None, hold_kv=False,
                 kv_import=None):
        self.rid = rid
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.deadline = deadline or Deadline.never()
        self.tokens: list[int] = []
        self.status = "pending"
        self.poisoned = False
        self.poison_checked = False
        self.error = None
        self.token_base = int(token_base)
        self.trace = trace
        self.tenant = tenant
        self.t_submit = self.t_queued = time.monotonic()
        self.t_admit = None
        self.t_first = None
        self.slot = None          # the slot it holds, or held last
        # set when the engine pulled this request off its slot to free
        # pages (pool exhaustion): re-admission then requires coverage
        # to the request's FULL budget so it cannot thrash in and out
        self.preempted = False
        self.hold_kv = bool(hold_kv)
        self.kv_import = kv_import

    def output(self):
        return np.asarray(self.tokens[:self.max_new_tokens], np.int32)

    def __repr__(self):
        return (f"Request(rid={self.rid}, len={self.prompt.size}, "
                f"status={self.status!r})")


def _bucket(n, buckets):
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds largest bucket {buckets[-1]}")


# splitmix64 constants for the per-request key streams: a vectorized
# counter-based hash (pure uint64 arithmetic, stable across numpy
# versions) instead of per-token SeedSequence objects, which would put
# O(segment x slots) Python-object work on the dispatch critical path
_SM64_A = np.uint64(0x9E3779B97F4A7C15)
_SM64_B = np.uint64(0xBF58476D1CE4E5B9)
_SM64_C = np.uint64(0x94D049BB133111EB)

# fixed operand width of the copy-on-write page-copy program: one
# compiled shape regardless of how many pages a step copies (padding
# lanes copy the dump page onto itself; larger batches loop)
_COW_WIDTH = 8

# fixed chunk width (in pages) of the KV export/import transfer
# programs: like _COW_WIDTH, one compiled shape regardless of how many
# pages a ticket moves — partial chunks pad with the dump page on both
# sides (the source gathers garbage from it, the destination scatters
# that garbage back onto its own dump page; never read)
_XFER_WIDTH = 4


def _mix64(x):
    x = (x ^ (x >> np.uint64(30))) * _SM64_B
    x = (x ^ (x >> np.uint64(27))) * _SM64_C
    return x ^ (x >> np.uint64(31))


class ContinuousBatchingEngine:
    """Mixed-length generation over ``max_slots`` concurrent sequences.

    Prompts up to the largest bucket admit in one padded prefill; LONGER
    prompts admit via CHUNKED PREFILL — full largest-bucket-wide chunks
    written at per-slot offsets (requires ``max_len`` to be a multiple of
    the largest bucket), so long-context requests stream in without a
    dedicated compiled shape per length.

    Usage::

        eng = ContinuousBatchingEngine(model, max_slots=8, max_len=512)
        eng.warmup(segment=16)   # optional: AOT-compile every shape
        outs, stats = eng.run(prompts, max_new_tokens=64, segment=16)

    ``pipeline=False`` selects the serial scheduler (dispatch, wait,
    consume, one segment at a time): the reference the overlapped one is
    held token-identical to.
    """

    def __init__(self, model, max_slots, max_len, page_size=128,
                 do_sample=False, temperature=1.0, top_k=None, top_p=None,
                 eos_token_id=None, prompt_buckets=(16, 32, 64, 128),
                 seed=0, pipeline=True, pool_pages=None, prefix_cache=True):
        from ..jit import _FunctionalModel, _swap_lock

        model.eval()
        cfg = model.config
        self.model = model
        self.cfg = cfg
        self.max_slots = int(max_slots)
        page_size = min(page_size, max_len)
        if max_len % page_size:
            rounded = -(-max_len // page_size) * page_size
            # the round-up changes the caller's budget (prompt+max_new
            # validation runs against the EFFECTIVE capacity): say so
            # once and surface it in stats()["kv"]["max_len"]
            logger.warning(
                "ContinuousBatchingEngine: max_len %d rounded up to %d "
                "(a multiple of page_size %d); stats()['kv'] reports "
                "the effective value", max_len, rounded, page_size)
            self._max_len_rounded_from = int(max_len)
            max_len = rounded
        else:
            self._max_len_rounded_from = None
        self.max_len = int(max_len)
        self.page_size = int(page_size)
        self.do_sample = bool(do_sample)
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.eos_token_id = eos_token_id
        self.prompt_buckets = tuple(sorted(prompt_buckets))
        self._pipeline = bool(pipeline)
        try:
            dtype = next(iter(model.parameters()))._value.dtype
        except StopIteration:
            dtype = jnp.float32
        per_seq = self.max_len // self.page_size
        self._cols = per_seq  # attention-visible table columns
        # what a sequence keeps in EACH layer (``generation.sequence_store``):
        # pages a token -- the model's own page shapes where it has a say
        # (a latent cache), else (kv heads, head size) twice -- ONE
        # fixed-size state a slot (a recurrent layer), or nothing. The
        # store owns the per-layer device arrays' format, and its two facts
        # are the engine's rules: ``_paged`` (some layer keeps pages: a pool
        # to plan, grow and account) and ``_state`` (some layer keeps a
        # state: granted slots are reset, a failed window is re-admitted,
        # and there is no prefix cache and no page to move)
        self._keep = sequence_store(
            model, dtype, self.page_size, per_seq,
            self.prompt_buckets[-1] % self.page_size == 0)
        self._state, self._paged = self._keep.has_state, self._keep.has_pages
        if self._state and self._paged and pool_pages is not None \
                and int(pool_pages) < self.max_slots * per_seq:
            raise NotImplementedError(
                f"{type(model).__name__} keeps a state beside pages: a pool "
                f"of {pool_pages} pages (under one full-length sequence a "
                "slot) would preempt, and a preempted row's state is not "
                "rebuilt yet (re-prefill over a reset state: ROADMAP M4)")
        # DYNAMIC POOL: ``pool_pages`` allocatable pages shared by every
        # slot (default: the historical budget of one full-length
        # sequence per slot, so the device arrays are byte-identical to
        # the static layout) + SCRATCH pages: admission groups are
        # padded to a fixed power-of-two batch width (one compiled
        # prefill shape per bucket x width, not one per group size) and
        # padding rows write into scratch, never into a live slot's
        # pages. Padding rows write at most chunk_w tokens (base 0), so
        # scratch holds chunk_w/page pages.
        chunk_w = self.prompt_buckets[-1]
        scratch_np = max(chunk_w // self.page_size, 1)
        n_real = self._keep.pool_pages(pool_pages, self.max_slots, per_seq)
        self._pool_pages = n_real
        n_pages = n_real + scratch_np
        # table rows carry EXTRA trailing scratch-aliased columns: a
        # prefix-resume prefill writes a padded bucket at an arbitrary
        # base, so its (masked, never-read) padding tail can spill up to
        # chunk_w tokens past max_len — those positions must map to a
        # scratch page, not clamp onto a live one
        self._extra_cols = -(-chunk_w // self.page_size)
        total_cols = per_seq + self._extra_cols
        self._nl = cfg.num_hidden_layers
        # the two per-layer device arrays (page pools, or a state row a
        # slot and the scratch slot's): donated and updated in place
        # through the same programs either way
        self._ks, self._vs = self._keep.allocate(n_pages, self.max_slots)
        # any table cell not backed by a granted page aliases the DUMP
        # page (the last scratch page): writes there are garbage by
        # construction and reads never reach it (attention masks by
        # length < max_len)
        self._dump_page = n_real + scratch_np - 1
        scratch_ids = n_real + np.minimum(
            np.arange(total_cols, dtype=np.int32), scratch_np - 1)
        # host page table: slot rows are rebuilt from the allocator's
        # grants (_set_table_row); row ``max_slots`` is the scratch row.
        # Kept NUMPY-side for prefill row gathers — the post-warmup hot
        # path must not trigger a single compilation; the device copy
        # (_tables_device) is re-uploaded on grant, never re-traced.
        self._tables_np = self._keep.tables(
            self.max_slots, total_cols, self._dump_page, scratch_ids)
        if self._state:
            if prefix_cache:
                logger.info(
                    "ContinuousBatchingEngine: %s keeps a state a slot; the "
                    "prefix cache is off (a snapshot a boundary: ROADMAP M4)",
                    type(model).__name__)
            prefix_cache = False
        # per-segment invariants hoisted out of the dispatch loop: the
        # device table/limits copies change only at grant/admission and
        # are invalidated there
        self._tables_active = None
        self._limits_dev = None
        self._pool = PagePool(n_real)
        self._prefix = (PrefixCache(self._pool, self.page_size,
                                    self._recycle)
                        if prefix_cache else None)
        self._slot_pages: list[list] = [[] for _ in range(self.max_slots)]
        # quarantine for freed pages that a dispatched-but-unconsumed
        # program may still write (see _recycle/_mark_executed)
        self._quarantine: list = []
        self._disp_n = 0
        self._exec_floor = 0
        self._functional = _FunctionalModel(model)
        # param/buffer snapshots must not race another engine's trace-time
        # param swap on a SHARED model (tracers would leak into the
        # snapshot and outlive their trace) — serialize on the swap lock
        self._swap_lock = _swap_lock
        with _swap_lock:
            self._buffers = {k: b._value for k, b in model.named_buffers()}
        self._zero_key = jax.random.key_data(jax.random.PRNGKey(0))
        self._key_shape = tuple(self._zero_key.shape)
        self._key_size = int(np.prod(self._key_shape))
        # sampling keys are fabricated HOST-side as PER-REQUEST streams:
        # key(rid, t) is a pure function of (seed, rid, token index), so
        # token streams never depend on batching, bisection replays, or
        # pipeline speculation — and cost no device dispatches
        self._seed = int(seed)
        self._zeros_cache: dict[tuple, jnp.ndarray] = {}
        self._aot: dict[tuple, object] = {}
        # KV accounting invariants (perfwatch): bytes one token's K+V
        # rows cost across all layers, at the cache dtype (0 for a state),
        # and bytes a slot's state holds (0 for pages)
        self._kv_bytes_per_token = int(self._keep.bytes_per_token)
        self._state_bytes_per_slot = int(self._keep.bytes_per_slot)
        # a model that counts (sparse experts: expert load) names what
        # its layers' per-step statistics are; the decode segment carries
        # their sum out and ``_consume`` feeds ``serving.<name>_total``
        self._stat_names = tuple(getattr(model, "step_stat_names", ()))
        self._stat_counters = [
            telemetry.counter(f"serving.{name}_total",
                              f"{name}, summed over the decode steps and "
                              "the counting layers of every segment")
            for name in self._stat_names]
        self._warmed = False
        self._prefill_p = None
        self._segment_p = None
        self._build_programs()

    # -------------------------------------------- page recycling safety
    #
    # A freed page may still be WRITTEN by a program that was dispatched
    # before the free (every dispatched segment writes every slot's
    # current cell, frozen slots included). Device programs execute in
    # dispatch order, so a page is safe to re-grant once every program
    # dispatched before the free has provably executed — which a
    # blocking fetch of any LATER (or the same) program's outputs
    # proves. ``_disp_n`` counts dispatches; ``_exec_floor`` is the
    # highest dispatch index proven executed; frees tagged above the
    # floor wait in quarantine.

    def _mark_dispatch(self) -> int:
        self._disp_n += 1
        return self._disp_n

    def _mark_executed(self, d):
        if d <= self._exec_floor:
            return
        self._exec_floor = d
        if self._quarantine:
            keep = []
            for tag, pages in self._quarantine:
                if tag <= self._exec_floor:
                    self._pool.recycle(pages)
                else:
                    keep.append((tag, pages))
            self._quarantine = keep

    def _recycle(self, pages):
        """Zero-ref pages back to the free list — immediately when no
        possibly-unexecuted program can write them, else quarantined."""
        if not pages:
            return
        if self._exec_floor >= self._disp_n:
            self._pool.recycle(pages)
        else:
            self._quarantine.append((self._disp_n, pages))

    # --------------------------------------------------- page-table state

    def _set_table_row(self, slot):
        """Mirror the slot's granted pages into its host table row (tail
        columns alias the dump page) and invalidate the device copy —
        contents change, the traced shape never does."""
        self._keep.set_row(self._tables_np[slot], self._slot_pages[slot],
                           self._dump_page)
        self._tables_active = None

    def _tables_device(self):
        """Device copy of the active slot rows, rebuilt after any page
        grant (a host->device upload, never a compilation). The TP
        engine overrides this to commit the upload mesh-replicated."""
        if self._tables_active is None:
            self._tables_active = jnp.asarray(
                self._tables_np[:self.max_slots])
        return self._tables_active

    def _free_slot_pages(self, slot):
        """Release the slot's page grants (shared pages just drop one
        reference; cache-held prefix pages survive for future hits)."""
        pages, self._slot_pages[slot] = self._slot_pages[slot], []
        if pages:
            self._recycle(self._pool.decref(pages))
            self._set_table_row(slot)

    # ------------------------------------------------------------ programs

    def _build_programs(self):
        functional = self._functional
        buffers = self._buffers
        zero_key = self._zero_key
        temperature, top_k, top_p = self.temperature, self.top_k, self.top_p
        greedy = not self.do_sample
        eos = self.eos_token_id

        def run_model(params, tokens, caches):
            # every program's model forward: traced inside the engine's
            # kernel scope (the TP engine's mesh — see _kernel_scope)
            with self._kernel_scope():
                return functional(params, buffers, (tokens,),
                                  {"caches": caches}, zero_key)

        def sample_batch(last, keys):
            # per-row key streams: row i is drawn with ITS OWN key, so a
            # row's tokens are independent of who it was batched with
            with jax.named_scope("sample"):
                return _sample_rows(last, keys, temperature, top_k, top_p,
                                    greedy).astype(jnp.int32)

        # the store builds the caches a forward runs over and names the
        # two per-layer arrays it leaves behind
        make_caches, pools = self._keep.caches, self._keep.pools

        def sample_true_last(logits, true_lens, keys):
            # first token from each row's TRUE last position (padding
            # rows are never read — causal)
            return sample_batch(self._keep.last_logits(logits, true_lens),
                                keys)

        def write_prompts(params, ks, vs, prompts, table_rows, base,
                          true_lens=None):
            # run the model over (N, L) prompt rows writing each row's
            # slot pages at ``base`` (0 = fresh slots, (N,) array =
            # chunked-prefill offsets); returns (logits, pools). A state
            # model continues each row's state instead, and is told how
            # many of the row's tokens are real: padding must not enter a
            # recurrence (a page model never reads what padding wrote)
            caches = make_caches(ks, vs, table_rows, base,
                                 true_lens=true_lens)
            (logits, caches2), _ = run_model(params, prompts, caches)
            return (logits, *pools(caches2))

        def prefill(params, ks, vs, prompts, table_rows, true_lens, keys):
            # N same-bucket admissions in ONE dispatch (static zero base:
            # the fast causal prefill path)
            logits, ks2, vs2 = write_prompts(
                params, ks, vs, prompts, table_rows, 0, true_lens)
            return sample_true_last(logits, true_lens, keys), ks2, vs2

        def chunk_step(params, ks, vs, chunk, table_rows, bases):
            # CHUNKED PREFILL body: write one full chunk of a long prompt
            # at per-row base offsets (rows attend causally to everything
            # already in their slot) — no sampling, pools out
            _, ks2, vs2 = write_prompts(
                params, ks, vs, chunk, table_rows, bases)
            return ks2, vs2

        def final_chunk(params, ks, vs, chunk, table_rows, bases, true_lens,
                        keys):
            # last (padded) chunk of a long prompt: write + sample
            logits, ks2, vs2 = write_prompts(
                params, ks, vs, chunk, table_rows, bases, true_lens)
            return sample_true_last(logits, true_lens, keys), ks2, vs2

        def resume_final(params, ks, vs, chunk, table_rows, bases,
                         true_lens, keys):
            # PREFIX-RESUME prefill: the divergent tail of a prompt whose
            # head was served from the prefix cache — written at per-row
            # bases that may sit MID-PAGE (unaligned scatter path; the
            # CoW page copy ran first), sampling at the true last token
            caches = make_caches(ks, vs, table_rows, bases, aligned=False)
            (logits, caches2), _ = run_model(params, chunk, caches)
            return (sample_true_last(logits, true_lens, keys),
                    *pools(caches2))

        def cow_copy(params, ks, vs, src, dst):
            # copy-on-write page copy: duplicate shared pages a writer
            # must append into (params ride for dispatch uniformity —
            # XLA dead-code-eliminates them). Padding lanes copy the
            # dump page onto itself.
            ks2 = [k.at[dst].set(k[src]) for k in ks]
            vs2 = [v.at[dst].set(v[src]) for v in vs]
            return ks2, vs2

        def export_pages(params, ks, vs, idx):
            # KV page EXPORT (disaggregation handoff, source side):
            # gather one fixed-width chunk of pages from every layer
            # into a single host-fetchable (layers, W, page, kv, hd)
            # payload pair. The donated pools alias straight through
            # unmodified; params ride for dispatch uniformity.
            payk = jnp.stack([k[idx] for k in ks])
            payv = jnp.stack([v[idx] for v in vs])
            return ks, vs, payk, payv

        def import_pages(params, ks, vs, idx, payk, payv):
            # KV page IMPORT (destination side): scatter one received
            # chunk into locally granted pages. Padding lanes write the
            # dump page (source padded the payload with its own dump
            # page — garbage lands on garbage, never read).
            ks2 = [k.at[idx].set(payk[i]) for i, k in enumerate(ks)]
            vs2 = [v.at[idx].set(payv[i]) for i, v in enumerate(vs)]
            return ks2, vs2

        reset = self._keep.reset

        def reset_state(params, ks, vs, rows):
            # what the store zeroes when slots are granted (a state)
            return reset(ks, vs, rows)

        def segment(params, ks, vs, tables, lengths, toks, active, limits,
                    keys):
            def body(carry, key):
                tok, ks, vs, lengths, active = carry
                caches = make_caches(ks, vs, tables, lengths, live=active)
                (logits, caches2), _ = run_model(params, tok[:, None],
                                                 caches)
                # a counting model's layers leave their step statistics
                # on their caches; a dense model leaves none, and its
                # program has no such output
                counted = [c.stats for c in caches2 if c.stats is not None]
                nxt = sample_batch(logits[:, -1, :], key)
                nxt = jnp.where(active, nxt, tok)  # frozen slots emit noise
                new_lengths = jnp.where(active, lengths + 1, lengths)
                # deactivate at the per-slot token budget: a slot must
                # never advance past its validated capacity mid-segment
                # (the paged kernel's lengths contract; frozen slots
                # re-write their own frozen cell, never another slot's)
                new_active = active & (new_lengths < limits)
                if eos is not None:
                    new_active = new_active & (nxt != eos)
                ks2, vs2 = pools(caches2)
                return ((nxt, ks2, vs2, new_lengths, new_active),
                        (nxt, active, sum(counted) if counted else None))

            (tok, ks, vs, lengths, active), (emitted, was_active, counts) = \
                jax.lax.scan(body, (toks, ks, vs, lengths, active), keys)
            if counts is None:
                return emitted, was_active, tok, lengths, active, ks, vs
            return (emitted, was_active, tok, lengths, active, ks, vs,
                    jnp.sum(counts, axis=0))

        self._prefill_p = jax.jit(prefill, donate_argnums=(1, 2))
        self._chunk_p = jax.jit(chunk_step, donate_argnums=(1, 2))
        self._final_chunk_p = jax.jit(final_chunk, donate_argnums=(1, 2))
        self._resume_p = jax.jit(resume_final, donate_argnums=(1, 2))
        self._cow_p = jax.jit(cow_copy, donate_argnums=(1, 2))
        self._export_p = jax.jit(export_pages, donate_argnums=(1, 2))
        self._import_p = jax.jit(import_pages, donate_argnums=(1, 2))
        self._segment_p = jax.jit(segment, donate_argnums=(1, 2))
        self._reset_p = (jax.jit(reset_state, donate_argnums=(1, 2))
                         if reset is not None else None)

    # --------------------------------------------------- program dispatch

    def _call(self, key, fallback, *args):
        """Dispatch through the AOT-compiled executable when ``warmup()``
        built one for this shape, else through the lazily-compiling jitted
        program (``fallback`` is looked up at call time so tests can
        monkeypatch ``_segment_p``/``_chunk_p``/...).

        On a WARMED engine the fallback path is itself the anomaly —
        this shape was not in the warmup set — so it runs inside the
        compile watchdog's dispatch context: if XLA compiles in there,
        the watchdog counts ``xla.compiles_total{phase=serving}`` and
        dumps a flight record naming ``key`` and the operand shapes."""
        exe = self._aot.get(key)
        if exe is not None:
            return exe(*args)
        if self._warmed and telemetry.enabled():
            # operand shapes: skip params/ks/vs (their shapes are
            # engine-static); the trailing args carry the traced shape
            # that missed the warmup set
            shapes = [list(a.shape) for a in args[3:]
                      if hasattr(a, "shape")]
            with _compile_watchdog().dispatch_context(key, shapes=shapes):
                return fallback(*args)
        return fallback(*args)

    def _group_width(self, n):
        """Smallest power-of-two admission batch width >= n, capped at
        ``max_slots`` — the compiled prefill shape this group rides."""
        w = 1
        while w < n:
            w <<= 1
        return min(w, self.max_slots)

    def group_widths(self):
        """Every compiled admission width: {1, 2, 4, ..., max_slots}."""
        out = []
        w = 1
        while w < self.max_slots:
            out.append(w)
            w <<= 1
        out.append(self.max_slots)
        return tuple(out)

    def warmup(self, segment=None):
        """AOT-compile (``jit(...).lower().compile()``) every declared
        serving shape: one prefill program per (prompt bucket x admission
        group width), the chunked-prefill chunk/final programs per width
        (when ``max_len`` admits chunking), and the decode-segment program
        at ``segment`` steps. After warmup a ``run()``/``step()`` session
        over in-bucket prompts triggers ZERO compilations — first-request
        latency and ``stats()['tokens_per_sec']`` stop absorbing compile
        time.

        ``segment`` must match the segment length later sessions use
        (defaults to the last ``start(segment=...)`` or 16). Call
        ``paddle.jit.enable_compilation_cache()`` first to have the
        compiles survive process restarts. Returns
        ``{"programs": newly compiled, "cached": already present,
        "seconds": wall}``.
        """
        t0 = time.monotonic()
        # compile watchdog: everything below is warmup-phase compilation;
        # once done, this engine's non-AOT dispatches become recompile
        # incidents (see _call)
        wd = _compile_watchdog().start()
        with annotate("serving.warmup") as sp, wd.warmup_scope():
            stats = self._warmup_compile(segment)
            sp.set(**stats)
        self._warmed = True
        wd.arm()
        stats["seconds"] = time.monotonic() - t0
        return stats

    def _kernel_scope(self):
        """Context the programs' model forwards are traced in. The TP
        engine overrides it with its mesh, so the Mosaic kernels are
        partitioned by ``shard_map`` (GSPMD cannot partition them)."""
        return contextlib.nullcontext()

    def compiled_programs(self) -> dict:
        """The executables ``warmup()`` compiled, keyed as the dispatch
        path looks them up: ``("prefill", bucket, width)``, ``("chunk",
        width)``, ``("segment", steps)``, ... (``as_text()`` /
        ``memory_analysis()`` say what serves each shape)."""
        return dict(self._aot)

    def _sds(self, x):
        """Warmup aval for an EXISTING engine array (params / KV pools).
        The TP engine (models/tp_serving.py) overrides this to carry the
        array's committed mesh sharding into the AOT lowering — an
        executable compiled without shardings refuses sharded inputs."""
        return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)

    def _op_aval(self, shape, dtype):
        """Warmup aval for an operand fabricated host-side per dispatch
        (prompts, table rows, sampling keys). The TP engine overrides
        this to pin them replicated over its mesh."""
        return jax.ShapeDtypeStruct(shape, dtype)

    def _param_snapshot(self):
        """The param dict a session (or warmup lowering) runs against.
        The TP engine overrides this to serve MESH-SHARDED copies
        without mutating the model — a collocated single-chip engine
        sharing the same model must keep seeing unsharded params."""
        return {k: p._value for k, p in self.model.named_parameters()}

    def _warmup_compile(self, segment):
        """The warmup compile loop (split out so :meth:`warmup` can
        scope it under the compile watchdog)."""
        with self._swap_lock:
            params = self._param_snapshot()
        sds = self._sds
        p_s = jax.tree_util.tree_map(sds, params)
        ks_s = [sds(k) for k in self._ks]
        vs_s = [sds(v) for v in self._vs]
        kdt = self._zero_key.dtype
        cols = self._tables_np.shape[1]
        i32 = jnp.int32
        stats = {"programs": 0, "cached": 0}

        wd = _compile_watchdog()

        def compile_(key, jitted, *avals):
            # one span per program: where a warm-up's seconds go, and
            # whether each came from this engine's own table ("aot"),
            # JAX's in-process cache ("memory": no backend compile), the
            # persistent compile cache, or the compiler ("no")
            with annotate("serving.warmup_program", key=repr(key)) as sp:
                if key in self._aot:
                    stats["cached"] += 1
                    sp.set(cached="aot")
                    return
                seen = wd.thread_compiles()[0]
                self._aot[key] = jitted.lower(
                    p_s, ks_s, vs_s, *avals).compile()
                stats["programs"] += 1
                # which scope each of its instructions came from, for
                # whoever reads a trace of it. The decode program is asked
                # about after this engine is gone (the benchmark frees the
                # engine before it reads), so its module is taken now
                # (50 ms); the admission programs' on the first question,
                # while this engine holds them (28 of them would be 1.4 s)
                note_program(key, self._aot[key], len(self._aot),
                             keep=key[0] == "segment")
                n, hit = wd.thread_compiles()
                sp.set(cached="memory" if n == seen
                       else "compile_cache" if hit else "no")

        chunk_w = self.prompt_buckets[-1]
        for g in self.group_widths():
            rows_s = self._op_aval((g, cols), i32)
            lens_s = self._op_aval((g,), i32)
            keys_s = self._op_aval((g,) + self._key_shape, kdt)
            for bucket in self.prompt_buckets:
                compile_(("prefill", bucket, g), self._prefill_p,
                         self._op_aval((g, bucket), i32),
                         rows_s, lens_s, keys_s)
                if self._prefix is not None:
                    # prefix-resume prefill: same (bucket x width) grid,
                    # plus the per-row base operand
                    compile_(("resume", bucket, g), self._resume_p,
                             self._op_aval((g, bucket), i32),
                             rows_s, self._op_aval((g,), i32),
                             lens_s, keys_s)
            if self.max_len > chunk_w and (
                    self.max_len % chunk_w == 0
                    or self._pool_pages < self.max_slots * self._cols):
                # beyond submitted long prompts (which _validate rejects
                # on non-multiple engines), a PREEMPTED request whose
                # folded prompt outgrew chunk_w re-admits through the
                # chunked path (final-chunk overflow lands in the extra
                # dump-aliased columns) — so the programs must also be
                # warmed on non-multiple engines whose RESTRICTED pool
                # can actually exhaust; the default full pool cannot
                # (every slot fits a whole sequence), so those engines
                # skip the dead compiles
                chunk_s = self._op_aval((g, chunk_w), i32)
                bases_s = self._op_aval((g,), i32)
                compile_(("chunk", g), self._chunk_p, chunk_s, rows_s,
                         bases_s)
                compile_(("final", g), self._final_chunk_p, chunk_s, rows_s,
                         bases_s, lens_s, keys_s)
        if self._prefix is not None:
            compile_(("cow", _COW_WIDTH), self._cow_p,
                     self._op_aval((_COW_WIDTH,), i32),
                     self._op_aval((_COW_WIDTH,), i32))
        # KV page transfer (prefill/decode disaggregation): the fixed-
        # width export/import chunk programs, warmed so page payloads
        # move between replicas without a single post-warmup trace
        if self._state:
            # no page moves between replicas; the one extra program zeroes
            # the slots an admission group was granted
            for g in self.group_widths():
                compile_(("reset", g), self._reset_p,
                         self._op_aval((g,), i32))
        else:
            xfer_idx_s = self._op_aval((_XFER_WIDTH,), i32)
            payk_s, payv_s = (self._op_aval(
                (len(pool), _XFER_WIDTH) + tuple(pool[0].shape[1:]),
                pool[0].dtype) for pool in (self._ks, self._vs))
            compile_(("export", _XFER_WIDTH), self._export_p, xfer_idx_s)
            compile_(("import", _XFER_WIDTH), self._import_p, xfer_idx_s,
                     payk_s, payv_s)
        seg = int(segment if segment is not None
                  else getattr(self, "_segment_len", 16))
        m = self.max_slots
        seg_avals = (self._op_aval((m, cols), i32),
                     self._op_aval((m,), i32),
                     self._op_aval((m,), i32),
                     self._op_aval((m,), jnp.bool_),
                     self._op_aval((m,), i32),
                     self._op_aval((seg, m) + self._key_shape, kdt))
        compile_(("segment", seg), self._segment_p, *seg_avals)
        return stats

    # ------------------------------------------------------- sampling keys

    def _key_zeros(self, shape):
        # greedy sampling ignores keys: serve a cached device-resident
        # zeros array (built via device_put, never a compiled fill)
        arr = self._zeros_cache.get(shape)
        if arr is None:
            arr = jnp.asarray(np.zeros(shape, np.uint32).astype(
                self._zero_key.dtype))
            self._zeros_cache[shape] = arr
        return arr

    def _rid_seed(self, rid):
        """Per-request stream root — a pure function of (engine seed,
        rid), so token streams are identical whether a token is produced
        by the serial loop, a speculative pipelined segment, or a
        bisection replay."""
        try:
            r = int(rid) & 0xFFFFFFFFFFFFFFFF
        except (TypeError, ValueError):
            r = zlib.crc32(str(rid).encode())
        # shape-(1,) operands: numpy wraps ARRAY uint64 overflow silently
        # (the intended mod-2^64 arithmetic) but warns on scalars
        return _mix64(np.asarray([self._seed], np.uint64) * _SM64_A
                      + np.asarray([r], np.uint64) * _SM64_B
                      + np.uint64(1))

    def _req_key_block(self, rid, base, n):
        """(n, key_size) uint32 key-data words for request ``rid``'s
        tokens ``base .. base+n-1`` — one vectorized hash over the
        (token index, word) grid, no per-token Python objects."""
        t = (np.uint64(base)
             + np.arange(n, dtype=np.uint64))[:, None]
        w = np.arange(1, self._key_size + 1, dtype=np.uint64)[None, :]
        h = _mix64(self._rid_seed(rid) + t * _SM64_A + w * _SM64_C)
        return (h >> np.uint64(32)).astype(np.uint32)

    def _prefill_keys(self, group, g):
        # first token of each admitted request: index ``token_base +
        # already-emitted`` of its stream (0 for fresh requests; k for a
        # failover resume that emitted k tokens elsewhere; the emitted
        # count for a PREEMPTED request re-admitting with its partial
        # output folded into the prompt)
        shape = (g,) + self._key_shape
        if not self.do_sample:
            return self._key_zeros(shape)
        bits = np.zeros(shape, np.uint32)
        for i, (_, req) in enumerate(group):
            bits[i] = self._req_key_block(
                req.rid, req.token_base + len(req.tokens),
                1).reshape(self._key_shape)
        return jnp.asarray(bits)

    def _segment_keys(self, offset):
        """Keys for one decode segment: slot s step i uses its request's
        stream at index ``len(tokens) + offset + i``. ``offset`` is the
        in-flight emission count a speculative dispatch must skip past
        (``segment_len`` when one segment is unconsumed, else 0)."""
        seg = self._segment_len
        shape = (seg, self.max_slots) + self._key_shape
        if not self.do_sample:
            return self._key_zeros(shape)
        bits = np.zeros(shape, np.uint32)
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            bits[:, slot] = self._req_key_block(
                req.rid, req.token_base + len(req.tokens) + offset,
                seg).reshape((seg,) + self._key_shape)
        return jnp.asarray(bits)

    # ----------------------------------------------------------- scheduler
    #
    # The engine is a STEPWISE scheduler: ``start()`` resets a session,
    # ``submit()`` enqueues requests (over time — the ServingFrontend
    # feeds it incrementally), ``step()`` performs one admit → decode →
    # retire turn and returns the requests that finished, ``abort()``
    # pulls a request back out. ``run()`` below is the batch convenience
    # wrapper that submits a whole list and steps to completion.

    def _validate(self, prompt, max_new_tokens):
        """Reject a request whose prefill could write outside its slot's
        pages — BEFORE any work is dispatched for it."""
        chunk_w = self.prompt_buckets[-1]
        if prompt.size + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds slot capacity {self.max_len}")
        # validate buckets UP FRONT: prefill writes the whole padded
        # bucket/chunk into the slot's pages, and an oversized bucket
        # must not surface mid-run after other requests' work
        if prompt.size <= chunk_w:
            b = _bucket(prompt.size, self.prompt_buckets)
            if b > self.max_len:
                raise ValueError(
                    f"prompt bucket {b} (for a {prompt.size}-token prompt) "
                    f"exceeds slot capacity {self.max_len}; add a "
                    f"smaller bucket or raise max_len")
        elif self.max_len % chunk_w:
            # chunked prefill pads the final chunk to chunk_w; the
            # write stays inside the slot's pages iff chunk_w divides
            # the capacity
            raise ValueError(
                f"chunked prefill (prompt {prompt.size} > largest bucket "
                f"{chunk_w}) requires max_len ({self.max_len}) to be "
                f"a multiple of the largest bucket")

    def start(self, segment=16, run_deadline=None):
        """Reset the scheduler for a new serving session: snapshot the
        parameters, clear slots/queue/counters. ``segment`` is the compiled
        decode window per ``step()``; ``run_deadline`` bounds the whole
        session (unfinished requests retire as ``timed_out`` past it)."""
        with self._swap_lock:
            self._params = self._param_snapshot()
        self._segment_len = int(segment)
        self._run_deadline = run_deadline or Deadline.never()
        self._queue: deque[Request] = deque()
        self._slot_req: list[Request | None] = [None] * self.max_slots
        # allocator session reset: every grant returns to the pool and
        # the PREFIX CACHE is cleared — the param snapshot above may
        # differ from the one the cached KV was computed under
        self._pool = PagePool(self._pool_pages)
        if self._prefix is not None:
            self._prefix = PrefixCache(self._pool, self.page_size,
                                       self._recycle)
        self._slot_pages = [[] for _ in range(self.max_slots)]
        # KV transfer state (disaggregation): holds are "ok" hold_kv
        # retirements awaiting a ticket; exports are live tickets;
        # imports are destination-side chunk landings. All pin pool
        # pages via refcounts — the fresh pool above dropped them all.
        self._kv_holds = {}
        self._exports = {}
        self._export_by_rid = {}
        self._imports = {}
        self._quarantine = []
        self._disp_n = 0
        self._exec_floor = 0
        for slot in range(self.max_slots):
            self._keep.set_row(self._tables_np[slot], (), self._dump_page)
        self._tables_active = None
        self._slot_adm = [0] * self.max_slots  # admission seq per slot
        self._adm_seq = 0
        self._resume_base = {}
        self._cow_pair = {}
        self.admission_blocked = False  # pool deferred the queue head
        self._prefix_lookup_tokens = 0
        self._prefix_hit_tokens = 0
        self._lengths = np.ones((self.max_slots,), np.int32)  # idle: len 1
        self._cur_tok = np.zeros((self.max_slots,), np.int32)
        # per-slot length budget: prompt + max_new - 1 is the final length
        # the last needed emission reaches; the segment program deactivates
        # a slot there so it never advances past validated capacity
        self._limits = np.full((self.max_slots,), self.max_len, np.int32)
        self._limits_dev = None
        self._useful = 0
        self._seg_runs = 0
        # occupancy as running sum/count: a long-lived serving session
        # must not grow a per-step list without bound
        self._occ_sum = 0.0
        self._occ_n = 0
        self._counts = {"ok": 0, "timed_out": 0, "failed": 0,
                        "cancelled": 0, "rejected": 0}
        self._auto_rid = 0
        # pipeline state: at most ONE dispatched-but-unconsumed segment;
        # ``_dirty`` marks host mask changes the device cannot see
        # (abort / deadline retirement), forcing a drain + sync turn
        self._inflight = None
        self._dirty = False
        # host-gap accounting: time from finishing one segment's host
        # bookkeeping to issuing the next dispatch
        self._gap_sum = 0.0
        self._gap_n = 0
        self._t_host0 = None
        # decode-stall accounting (serving.decode_stall): slots that were
        # decoding when the last consume ended, admissions since, and why
        # the pipeline was drained
        self._stall_live = 0
        self._admitted_since = 0
        self._stall_cause = None
        self._t0 = time.monotonic()
        return self

    def submit(self, prompt, max_new_tokens, deadline_s=None, rid=None,
               token_base=0, trace=None, tenant=None, hold_kv=False,
               kv_import=None):
        """Enqueue one request (requires a prior ``start()``); raises
        ``ValueError`` if it can never fit a slot. ``deadline_s`` is a
        per-request budget (seconds or a ``Deadline``), measured from
        submission so queue wait counts. Returns the ``Request`` handle.

        ``token_base=k`` is the FAILOVER RESUME contract: ``prompt``
        must be the original prompt plus the ``k`` tokens already
        emitted elsewhere, and ``max_new_tokens`` the REMAINING budget —
        sampling keys start at stream index ``k``, so the continuation
        is bit-identical to the uninterrupted run's (same engine seed,
        same rid). ``trace`` tags the request's dispatch spans and
        retire event with a telemetry trace id. ``tenant`` attributes
        the request's latency/token metrics to a tenant label (QoS is
        enforced ABOVE the engine — frontend quotas/WFQ, router typed
        rejections; the scheduler itself stays tenant-blind).

        ``hold_kv=True`` marks a disaggregated prefill (pages held for
        export at "ok" retirement); ``kv_import=<ticket id>`` seats the
        request onto a completed KV import at admission — see
        ``export_pages``/``import_kv_chunk``."""
        prompt = np.asarray(prompt).astype(np.int32).ravel()
        self._validate(prompt, max_new_tokens)
        if hold_kv or kv_import is not None:
            self._refuse_state("a prefill / decode handoff")
        if rid is None:
            rid = self._auto_rid
            self._auto_rid += 1
        elif isinstance(rid, int) and rid >= self._auto_rid:
            # keep auto rids strictly above every explicit rid seen, so
            # mixing the two can't alias different requests
            self._auto_rid = rid + 1
        deadline = (deadline_s if isinstance(deadline_s, Deadline)
                    else Deadline(deadline_s))
        req = Request(rid, prompt, max_new_tokens, deadline,
                      token_base=token_base, trace=trace, tenant=tenant,
                      hold_kv=hold_kv, kv_import=kv_import)
        self._queue.append(req)
        return req

    def has_work(self) -> bool:
        # the unconsumed in-flight segment counts as work: after the last
        # live request is aborted mid-pipeline the carry must still be
        # drained by one more step() — otherwise it leaks device buffers
        # and a later submit would consume a segment built on a dead mask
        return (bool(self._queue)
                or any(r is not None for r in self._slot_req)
                or getattr(self, "_inflight", None) is not None)

    def free_slots(self) -> int:
        return sum(r is None for r in self._slot_req)

    def active_requests(self) -> list:
        return [r for r in self._slot_req if r is not None]

    def queued_requests(self) -> list:
        return list(self._queue)

    def abort(self, rid, status="cancelled"):
        """Pull a request out of the queue or its slot (its partial tokens
        stay on the handle). Returns the ``Request`` or None if unknown /
        already finished."""
        for req in self._queue:
            if req.rid == rid:
                self._queue.remove(req)
                self._retire(req, status)
                return req
        for slot, req in enumerate(self._slot_req):
            if req is not None and req.rid == rid:
                self._retire(req, status, slot=slot)
                if self._inflight is not None:
                    # the in-flight segment still decodes this slot; its
                    # emissions are discarded at consume, but the next
                    # dispatch must be a sync turn from the repaired mask
                    self._dirty = True
                return req
        return None

    def _refuse_state(self, what):
        """A model with a state in any layer has no whole sequence in pages
        to pin, export or land."""
        if self._state:
            raise NotImplementedError(
                f"{type(self.model).__name__} keeps a recurrent state a "
                f"slot, not pages alone: {what} needs a snapshot of the "
                "state to "
                "move (models/transfer.py export_pages / import_pages), "
                "which is not built yet: ROADMAP M4")

    # ----------------------------------------------- failure isolation

    def _retire(self, req, status, finished=None, slot=None):
        if req.status != "pending":
            return  # already retired (e.g. timed out inside a bisected try)
        pages_held = 0
        if slot is not None:
            self._slot_req[slot] = None
            self._lengths[slot] = 1  # slot returns to the idle pool
            pages_held = len(self._slot_pages[slot])
            if status == "ok" and req.hold_kv and self._slot_pages[slot]:
                # disaggregated prefill: the slot's page grants (and
                # their refcounts) move to the export hold table instead
                # of the free list — quarantine/eviction cannot recycle
                # them while a transfer is (or may be) in flight
                pages, self._slot_pages[slot] = self._slot_pages[slot], []
                self._set_table_row(slot)
                self._kv_holds[req.rid] = {
                    "pages": pages,
                    "prefill_len": int(req.prompt.size),
                    "first_token": int(req.tokens[0]) if req.tokens
                    else None,
                }
            else:
                self._free_slot_pages(slot)
        req.status = status
        self._counts[status] = self._counts.get(status, 0) + 1
        if telemetry.enabled():
            _M_REQS.inc(status=status)
            if req.t_first is not None:
                # the request's KV footprint at the page granularity it
                # actually occupied (the pages the allocator just freed)
                # — only requests that were ADMITTED (prefilled into a
                # slot); a queue-expired request held no pages
                pages = (pages_held if pages_held else
                         -(-(req.prompt.size + len(req.tokens))
                           // self.page_size))
                _M_KV_REQ.observe(self._state_bytes_per_slot
                                  or pages * self.page_size
                                  * self._kv_bytes_per_token)
            if req.t_first is not None and len(req.tokens) > 1:
                per_tok = ((time.monotonic() - req.t_first)
                           / (len(req.tokens) - 1))
                _M_TOK.observe(per_tok)
                if req.tenant is not None:
                    _M_TOK.observe(per_tok, tenant=str(req.tenant))
            if req.tenant is not None and req.tokens:
                # tenant-attributed emission total (labeled series only;
                # the unlabeled serving.tokens_total counts at emission)
                _M_TOKENS.inc(len(req.tokens), tenant=str(req.tenant))
            telemetry.trace_event("serving.retire", trace=req.trace,
                                  rid=req.rid, status=status,
                                  tokens=len(req.tokens),
                                  slot=None if slot is None else int(slot))
        if finished is not None:
            finished.append(req)

    def _check_poison(self, items):
        """Consume the ``serving.engine_fault`` injection budget once per
        request (STICKY: the poison mark survives bisection retries so the
        same offender fails deterministically), then fail the dispatch if
        any member of this batch is poisoned."""
        for _, req in items:
            if not req.poison_checked:
                req.poison_checked = True
                try:
                    inject("serving.engine_fault")
                except InjectedFault:
                    req.poisoned = True
        bad = [req for _, req in items if req.poisoned]
        if bad:
            raise InjectedFault(
                f"injected engine fault for request {bad[0].rid}")

    def _isolate(self, group, dispatch, finished):
        """Poison-request isolation: run ``dispatch(sub)`` over the
        admission group, BISECTING on failure so one poison request cannot
        take down its co-batched peers — survivors are re-dispatched in
        smaller batches (page writes are idempotent: a replayed prefill
        rewrites the same slot pages), and the offender retires as
        ``"failed"`` (``serving.poison_request`` in the ledger) instead of
        raising out of the scheduler with every in-flight slot lost."""
        group = [it for it in group if it[1].status == "pending"]
        if not group:
            return
        try:
            self._check_poison(group)
            dispatch(group)
            return
        except Exception as e:  # isolation boundary: bisect, never crash
            if len(group) == 1:
                slot, req = group[0]
                bump_counter("serving.poison_request")
                req.error = e
                # a poison retirement is a post-mortem moment: dump the
                # flight recorder so the offender leaves forensics
                telemetry.flight_dump("poison_request", rid=req.rid,
                                      error=repr(e))
                # slot= releases the admission's page grants even though
                # the request never registered in _slot_req (the dynamic
                # pool must not leak a failed admission's pages)
                self._retire(req, "failed", finished, slot=slot)
                return
        mid = len(group) // 2
        self._isolate(group[:mid], dispatch, finished)
        self._isolate(group[mid:], dispatch, finished)

    # ------------------------------------------------------- dispatches

    @staticmethod
    def _group_trace_args(group):
        """Span args for a batched admission dispatch: the rids (and any
        trace ids) riding it, so a per-request timeline can find the
        shared prefill span. Empty when telemetry is off — the lists are
        never built on a disabled hot path."""
        if not telemetry.enabled():
            return {}
        return {"rids": [req.rid for _, req in group],
                "slots": [int(slot) for slot, _ in group],
                "traces": [req.trace for _, req in group
                           if req.trace is not None]}

    def _stamp_admit(self, slot, req):
        """An admission granted ``req`` its slot: the engine-queue wait
        ends here (``serving.engine_wait``, under the request's trace)."""
        req.t_admit = time.monotonic()
        self._admitted_since += 1
        if telemetry.enabled():
            wait = req.t_admit - req.t_queued
            _M_ENGINE_WAIT.observe(wait)
            telemetry.tracer().add_span(
                "serving.engine_wait", None, wait, t0=req.t_queued,
                trace=req.trace, rid=req.rid, slot=int(slot))

    def _mask_trace_args(self, mask):
        """Span args for a decode-segment dispatch over the slot mask."""
        if not telemetry.enabled():
            return {}
        reqs = [self._slot_req[s] for s in np.flatnonzero(mask)]
        return {"rids": [r.rid for r in reqs if r is not None],
                "traces": [r.trace for r in reqs
                           if r is not None and r.trace is not None]}

    def _limits_device(self):
        if self._limits_dev is None:
            self._limits_dev = jnp.asarray(self._limits)
        return self._limits_dev

    def _finish_admit(self, slot, req, tok, finished):
        """Shared post-prefill bookkeeping (short, chunked AND
        prefix-resume paths): register the slot, count the sampled
        token, set the per-slot budget, insert the prompt's full pages
        into the prefix cache, and retire immediately on eos /
        exhausted budget. A PREEMPTED request re-admits here with its
        partial output folded into the prompt — ``len(req.tokens)``
        already counts those emissions, so the key stream, budget, and
        limit arithmetic stay globally indexed."""
        self._slot_req[slot] = req
        req.slot = int(slot)
        fresh_first = not req.tokens
        req.tokens.append(int(tok))
        self._useful += 1  # the prefill-sampled token
        if req.t_first is None:
            req.t_first = time.monotonic()
            if telemetry.enabled():
                _M_ADMIT_FIRST.observe(req.t_first - req.t_admit)
                telemetry.trace_event("serving.first_token",
                                      trace=req.trace, rid=req.rid,
                                      slot=int(slot))
            if telemetry.enabled() and req.token_base == 0 and fresh_first:
                # FRESH attempts only: a failover continuation
                # (token_base > 0) emitted its real first token long ago
                # on another replica — an attempt-level sample here
                # would skew the fleet TTFT percentiles during exactly
                # the incidents where the SLO number matters
                _M_TTFT.observe(req.t_first - req.t_submit)
                if req.tenant is not None:
                    # per-tenant attribution SERIES (the unlabeled
                    # series above stays the total; these answer "whose
                    # latency" in fleet_metrics()['tenants'])
                    _M_TTFT.observe(req.t_first - req.t_submit,
                                    tenant=str(req.tenant))
        if telemetry.enabled():
            _M_TOKENS.inc()
        self._lengths[slot] = req.prompt.size
        self._cur_tok[slot] = int(tok)
        # final slot length: prompt + remaining emission budget - 1
        # (len(tokens) - 1 emissions happened in EARLIER attempts for a
        # preempted resume; for a fresh request this is the historical
        # prompt + max_new - 1)
        self._limits[slot] = (req.prompt.size + req.max_new_tokens
                              - len(req.tokens))
        self._limits_dev = None  # admission changed the device invariant
        if self._prefix is not None:
            # the slot's full prompt pages now hold valid KV: future
            # prompts sharing this prefix map them instead of
            # re-prefilling (refcounted — they outlive this request)
            self._prefix.insert(req.prompt, self._slot_pages[slot])
        if len(req.tokens) >= req.max_new_tokens or (
                self.eos_token_id is not None
                and req.tokens[-1] == self.eos_token_id):
            self._retire(req, "ok", finished, slot=slot)

    def _adopt_import(self, slot, req, imp, finished):
        """Seat a disaggregated-decode request directly onto imported
        prefill pages: pure host bookkeeping — page-table CONTENTS and
        scheduler state mutate, no program is traced or dispatched.

        Bit-exactness contract: the source replica sampled the prefill
        token (stream index 0 of the request's key stream, same engine
        seed + rid everywhere), so the adopted request starts with that
        token already in ``tokens`` and the next decode segment samples
        stream index ``token_base + len(tokens) == 1`` — identical to
        the colocated run's second token. TTFT was observed at the
        prefill; no attempt-level sample here."""
        meta = imp["meta"]
        plen = int(meta["prefill_len"])
        first = int(meta["first_token"])
        self._slot_pages[slot] = list(imp["pages"])
        self._set_table_row(slot)
        self._slot_adm[slot] = self._adm_seq
        self._adm_seq += 1
        self._slot_req[slot] = req
        req.slot = int(slot)
        req.tokens.append(first)
        if req.t_first is None:
            req.t_first = time.monotonic()
        self._lengths[slot] = plen
        self._cur_tok[slot] = first
        self._limits[slot] = (req.prompt.size + req.max_new_tokens
                              - len(req.tokens))
        self._limits_dev = None  # admission changed the device invariant
        if self._prefix is not None:
            self._prefix.insert(req.prompt, self._slot_pages[slot])
        bump_counter("serving.kv_import_adopted")
        if telemetry.enabled():
            telemetry.trace_event("serving.kv_adopt", trace=req.trace,
                                  rid=req.rid, pages=len(imp["pages"]))
        if len(req.tokens) >= req.max_new_tokens or (
                self.eos_token_id is not None
                and req.tokens[-1] == self.eos_token_id):
            self._retire(req, "ok", finished, slot=slot)

    def _dispatch_prefill(self, group, bucket, finished):
        # admission batch padded to the GROUP WIDTH (smallest power of two
        # >= the group, capped at max_slots): one compiled prefill shape
        # per (bucket x width), so a single admission pays a width-1
        # forward instead of a max_slots-wide one; padding rows write
        # scratch
        with annotate("serving.prefill_prep"):
            g = self._group_width(len(group))
            padded = np.zeros((g, bucket), np.int32)
            true_lens = np.ones((g,), np.int32)
            rows = np.full((g,), self.max_slots, np.int64)  # scratch
            for i, (slot, req) in enumerate(group):
                padded[i, :req.prompt.size] = req.prompt
                true_lens[i] = req.prompt.size
                rows[i] = slot
        self._reset_state(group)
        with annotate("serving.prefill", phase="prefill") as sp:
            d = self._mark_dispatch()
            sp.set(**self._group_trace_args(group))
            self._count_state_prefill(sp, true_lens[:len(group)].sum(),
                                      g * bucket)
            with annotate("serving.prefill_dispatch"):
                tok0, self._ks, self._vs = self._call(
                    ("prefill", bucket, g), self._prefill_p,
                    self._params, self._ks, self._vs, jnp.asarray(padded),
                    jnp.asarray(self._tables_np[rows]),
                    jnp.asarray(true_lens), self._prefill_keys(group, g))
            with annotate("serving.first_token_fetch"):
                tok0 = np.asarray(tok0)  # blocking fetch: the program ran
            self._mark_executed(d)
        self._admit_finish(group, tok0, finished)

    def _admit_finish(self, group, tok0, finished):
        with annotate("serving.admit_finish"):
            for i, (slot, req) in enumerate(group):
                self._finish_admit(slot, req, tok0[i], finished)

    def _reset_state(self, group):
        """Zero the state of the slots an admission group was granted (a
        state model; nothing for a page model). Inside the group's
        isolation scope and ahead of its prefill in device order, so a
        bisection replay starts its rows from the zero state again."""
        if self._reset_p is None:
            return
        g = self._group_width(len(group))
        rows = np.full((g,), self.max_slots, np.int32)   # padding: scratch
        rows[:len(group)] = [slot for slot, _ in group]
        with annotate("serving.state_reset", slots=len(group)):
            self._mark_dispatch()
            self._ks, self._vs = self._call(
                ("reset", g), self._reset_p, self._params,
                self._ks, self._vs, jnp.asarray(rows))

    def _count_state_prefill(self, sp, real, positions):
        """A state model's two prefill counters for one dispatch: the real
        tokens fed through the recurrence and the positions masked out.
        They ride on the dispatch's span too, so a reader can sum them over
        any interval."""
        if self._state and telemetry.enabled():
            real, padded = int(real), int(positions) - int(real)
            _M_STATE_TOKENS.inc(real)
            _M_STATE_PADDED.inc(padded)
            sp.set(state_tokens=real, state_padded=padded)

    def _count_prefill_cols(self, bases, width):
        """The two ``serving.prefill_attn_cols_*`` counters for one
        dispatch of ``width`` new tokens a row at its real rows' ``bases``
        (a state model attends to no column)."""
        if telemetry.enabled() and self._paged:
            _M_PREFILL_LIVE.inc(int(np.sum(bases + width)))
            _M_PREFILL_TABLE.inc(len(bases) * self._cols * self.page_size)

    def _dispatch_resume(self, group, bucket, finished):
        """PREFIX-RESUME admission dispatch: each row's shared prefix
        (``_resume_base`` tokens, keyed by request IDENTITY — rids are
        caller-supplied and may collide) is already mapped from the cache;
        only the divergent tail — padded to ``bucket`` — is written and
        the first token sampled at the true last position. Bases may sit
        mid-page (the CoW copy runs first, inside THIS isolation scope —
        a copy failure bisects like any admission failure), so the
        program uses the unaligned scatter write path."""
        with annotate("serving.prefill_prep"):
            pairs = [self._cow_pair[id(req)] for _, req in group
                     if id(req) in self._cow_pair]
            if pairs:
                self._dispatch_cow(pairs)
            g = self._group_width(len(group))
            padded = np.zeros((g, bucket), np.int32)
            bases = np.zeros((g,), np.int32)
            true_lens = np.ones((g,), np.int32)
            rows = np.full((g,), self.max_slots, np.int64)  # scratch
            for i, (slot, req) in enumerate(group):
                m = self._resume_base[id(req)]
                rem = req.prompt.size - m
                padded[i, :rem] = req.prompt[m:]
                bases[i] = m
                true_lens[i] = rem
                rows[i] = slot
        with annotate("serving.prefill", phase="prefill") as sp:
            d = self._mark_dispatch()
            sp.set(**self._group_trace_args(group))
            with annotate("serving.prefill_dispatch"):
                tok0, self._ks, self._vs = self._call(
                    ("resume", bucket, g), self._resume_p,
                    self._params, self._ks, self._vs, jnp.asarray(padded),
                    jnp.asarray(self._tables_np[rows]), jnp.asarray(bases),
                    jnp.asarray(true_lens), self._prefill_keys(group, g))
            self._count_prefill_cols(bases[:len(group)], bucket)
            with annotate("serving.first_token_fetch"):
                tok0 = np.asarray(tok0)
            self._mark_executed(d)
        self._admit_finish(group, tok0, finished)

    def _dispatch_cow(self, pairs):
        """Copy-on-write page copies, batched through the fixed-width
        ``("cow", _COW_WIDTH)`` program (padding lanes copy the dump page
        onto itself). Called from ``_dispatch_resume`` — INSIDE the
        ``_isolate`` boundary, before the group's prefill appends into
        the copies (device program order makes the copy visible) — so a
        device failure bisects like any admission failure, and a
        bisection replay harmlessly re-copies (the source is read-only
        shared content, the destination private)."""
        for i in range(0, len(pairs), _COW_WIDTH):
            batch = pairs[i:i + _COW_WIDTH]
            src = np.full((_COW_WIDTH,), self._dump_page, np.int32)
            dst = np.full((_COW_WIDTH,), self._dump_page, np.int32)
            for j, (s, t) in enumerate(batch):
                src[j] = s
                dst[j] = t
            self._mark_dispatch()
            self._ks, self._vs = self._call(
                ("cow", _COW_WIDTH), self._cow_p,
                self._params, self._ks, self._vs,
                jnp.asarray(src), jnp.asarray(dst))

    def _split_expired(self, items):
        live, expired = [], []
        for slot, req in items:
            if req.deadline.expired() or self._run_deadline.expired():
                expired.append((slot, req))
            else:
                live.append((slot, req))
        return live, expired

    def _chunked_prefill(self, group, finished):
        # CHUNKED PREFILL (long-context admission): full ``chunk_w``-token
        # chunks at per-row base offsets, then one padded final chunk that
        # also samples the first token. Rows are aligned by chunk index;
        # rows already past their full chunks ride the scratch page row.
        # A prefix-cache hit starts a row's chunks at its RESUME BASE
        # (the shared-prefix length, page-aligned for the bulk write
        # path) instead of 0 — the cached pages already hold that KV.
        # The request deadline is checked BETWEEN chunks: a long-context
        # admission whose budget expired mid-prefill retires as
        # ``timed_out`` without dispatching its remaining chunks.
        chunk_w = self.prompt_buckets[-1]
        scratch = self.max_slots
        self._reset_state(group)
        start = {id(req): self._resume_base.get(id(req), 0)
                 for _, req in group}
        n_full = {id(req): (req.prompt.size - start[id(req)] - 1) // chunk_w
                  for _, req in group}
        live = list(group)
        expired = []
        c = 0
        while live:
            live, dead = self._split_expired(live)
            expired += dead
            if not live or not any(c < n_full[id(req)] for _, req in live):
                break
            with annotate("serving.prefill_prep"):
                g = self._group_width(len(live))
                chunk_arr = np.zeros((g, chunk_w), np.int32)
                bases = np.zeros((g,), np.int32)
                rows = np.full((g,), scratch, np.int64)
                for i, (slot, req) in enumerate(live):
                    if c < n_full[id(req)]:
                        p = req.prompt
                        b0 = start[id(req)] + c * chunk_w
                        chunk_arr[i] = p[b0:b0 + chunk_w]
                        bases[i] = b0
                        rows[i] = slot
            with annotate("serving.chunked_prefill",
                          phase="chunked_prefill") as sp:
                self._mark_dispatch()  # async: no fetch proves execution
                sp.set(**self._group_trace_args(live))
                with annotate("serving.prefill_dispatch"):
                    self._ks, self._vs = self._call(
                        ("chunk", g), self._chunk_p,
                        self._params, self._ks, self._vs,
                        jnp.asarray(chunk_arr),
                        jnp.asarray(self._tables_np[rows]),
                        jnp.asarray(bases))
                self._count_prefill_cols(bases[rows != scratch], chunk_w)
                self._count_state_prefill(
                    sp, np.sum(rows != scratch) * chunk_w, g * chunk_w)
            c += 1
        if live:
            with annotate("serving.prefill_prep"):
                g = self._group_width(len(live))
                final_arr = np.zeros((g, chunk_w), np.int32)
                bases = np.zeros((g,), np.int32)
                true_rem = np.ones((g,), np.int32)
                rows = np.full((g,), scratch, np.int64)
                for i, (slot, req) in enumerate(live):
                    p = req.prompt
                    done = start[id(req)] + n_full[id(req)] * chunk_w
                    rem = p.size - done
                    final_arr[i, :rem] = p[done:]
                    bases[i] = done
                    true_rem[i] = rem
                    rows[i] = slot
            with annotate("serving.chunked_prefill",
                          phase="chunked_prefill") as sp:
                d = self._mark_dispatch()
                sp.set(**self._group_trace_args(live))
                with annotate("serving.prefill_dispatch"):
                    tok0, self._ks, self._vs = self._call(
                        ("final", g), self._final_chunk_p,
                        self._params, self._ks, self._vs,
                        jnp.asarray(final_arr),
                        jnp.asarray(self._tables_np[rows]),
                        jnp.asarray(bases), jnp.asarray(true_rem),
                        self._prefill_keys(live, g))
                self._count_prefill_cols(bases[:len(live)], chunk_w)
                self._count_state_prefill(sp, true_rem[:len(live)].sum(),
                                          g * chunk_w)
                with annotate("serving.first_token_fetch"):
                    tok0 = np.asarray(tok0)  # blocking fetch
                self._mark_executed(d)
            self._admit_finish(live, tok0, finished)
        if expired:
            with annotate("serving.admit_finish"):
                for slot, req in expired:
                    # slot= so the admission's page grants return to the
                    # pool (the request never registered in _slot_req)
                    self._retire(req, "timed_out", finished, slot=slot)

    def _dispatch_segment(self, mask, carry=None, key_offset=0):
        """Dispatch ONE compiled decode segment (async — no host wait).

        ``carry=None`` is a SYNC dispatch from host state; otherwise
        ``carry`` is the previous segment's device outputs
        ``(tok, lengths, active)`` fed straight back as operands — the
        speculative pipelined turn, which costs no host round trip.
        Returns the in-flight handle consumed later by ``_consume``."""
        # the phase is the host-side issue cost only: the call returns
        # while the device still runs (async dispatch)
        with annotate("serving.segment_dispatch",
                      phase="segment_dispatch") as sp:
            now = time.monotonic()
            if self._t_host0 is not None:
                self._note_host_gap(now, sync=carry is None)
            self._admitted_since = 0
            self._stall_cause = None
            keys = self._segment_keys(key_offset)
            if carry is None:
                toks = jnp.asarray(self._cur_tok)
                lengths = jnp.asarray(self._lengths)
                active = jnp.asarray(mask)
            else:
                toks, lengths, active = carry
            d = self._mark_dispatch()
            sp.set(**self._mask_trace_args(mask))
            with annotate("serving.segment_call"):
                emitted, was_active, tok, new_lengths, still_active, \
                    self._ks, self._vs, *stats = self._call(
                        ("segment", self._segment_len), self._segment_p,
                        self._params, self._ks, self._vs,
                        self._tables_device(),
                        lengths, toks, active, self._limits_device(), keys)
            self._seg_runs += 1
            if telemetry.enabled() and self._paged:
                _M_ATTN_LIVE.inc(int(
                    (-(-self._lengths // self.page_size)).sum()))
                _M_ATTN_TABLE.inc(self.max_slots * self._cols)
        return {"emitted": emitted, "was_active": was_active, "tok": tok,
                "lengths": new_lengths, "active": still_active,
                "stats": stats, "mask": np.asarray(mask), "disp": d}

    def _note_host_gap(self, now, sync):
        """The host gap that ends at this dispatch (``serving.host_gap``,
        from the end of the last consume) and, at a SYNC dispatch while
        slots were decoding, the same interval as ``serving.decode_stall``:
        those decodes waited for it, where a pipelined turn would have
        had their next segment on the device already."""
        gap = now - self._t_host0
        self._gap_sum += gap
        self._gap_n += 1
        record_span("serving.host_gap", self._t_host0, gap, phase="host_gap")
        if sync and self._stall_live and telemetry.enabled():
            cause = self._stall_cause or (
                "admission" if self._admitted_since else "serial")
            record_span("serving.decode_stall", self._t_host0, gap,
                        n_live=self._stall_live,
                        admitted=self._admitted_since, cause=cause)
            _M_STALL_S.inc(gap)
        self._t_host0 = None

    def _consume(self, h, finished):
        """Fetch one dispatched segment's outputs (ONE host round trip for
        all of them) and do the host bookkeeping: mirror lengths/tokens,
        append emissions, retire finished slots."""
        # the blocking fetch: device compute the pipeline did not hide
        # (plus transfer) — the device share of a decode step
        with annotate("serving.device_wait", phase="device_wait") as sp:
            emitted, was_active, cur_tok, lengths, still_active, stats = \
                jax.device_get((h["emitted"], h["was_active"], h["tok"],
                                h["lengths"], h["active"], h["stats"]))
            if stats:
                # the segment's own counts ride on the span of its fetch,
                # so a reader can sum them over any interval
                stats = [int(n) for n in stats[0]]
                sp.set(**dict(zip(self._stat_names, stats)))
        with annotate("serving.host_bookkeeping", phase="host_bookkeeping"):
            # the blocking fetch proves this segment (and every program
            # dispatched before it) executed: quarantined page frees up to
            # its dispatch index are safe to recycle
            self._mark_executed(h["disp"])
            useful0 = self._useful
            # slots outside ``mask`` pass through the program unchanged, so
            # wholesale assignment composes across bisected sub-batches
            self._lengths = lengths.copy()
            self._cur_tok = cur_tok.copy()
            # slots freed while this segment was in flight (abort /
            # failover retirement) must stay at the idle length — the
            # device view still carries the dead request's advance, and
            # resurrecting it here would hand the next admission a slot
            # that lies about its occupancy
            for slot, req in enumerate(self._slot_req):
                if req is None:
                    self._lengths[slot] = 1
            for slot in np.flatnonzero(h["mask"]):
                req = self._slot_req[slot]
                if req is None:
                    continue
                toks = req.tokens
                for s in range(self._segment_len):
                    if not was_active[s, slot] or len(toks) >= \
                            req.max_new_tokens:
                        break
                    toks.append(int(emitted[s, slot]))
                    self._useful += 1
                done = (len(toks) >= req.max_new_tokens
                        or (self.eos_token_id is not None
                            and toks and toks[-1] == self.eos_token_id)
                        or not bool(still_active[slot]))
                if done:
                    self._retire(req, "ok", finished, slot=slot)
        self._t_host0 = time.monotonic()
        self._stall_live = sum(r is not None for r in self._slot_req)
        if telemetry.enabled() and self._useful > useful0:
            # one bump per consumed segment, not per token
            _M_TOKENS.inc(self._useful - useful0)
        if stats and telemetry.enabled():
            for counter, n in zip(self._stat_counters, stats):
                counter.inc(n)

    def _drain_pipeline(self, finished, cause="dirty"):
        """Consume the in-flight segment (if any) so the host view of
        slots/lengths is current — required before any admission, and
        before bisection replays. A segment whose async execution failed
        is replayed serially from the last synced host state so the
        bisection isolation still applies. ``cause`` names why the
        pipeline could not run on (``admission``, ``dirty``,
        ``not_spec_worthy``): the next dispatch is a sync one, and its
        ``serving.decode_stall`` span carries it."""
        h, self._inflight = self._inflight, None
        self._dirty = False
        if h is None:
            return
        self._stall_cause = cause
        with annotate("serving.drain", cause=cause):
            try:
                self._consume(h, finished)
            except Exception as e:  # isolation boundary: replay + bisect
                self._replay_window(h["mask"], finished, e)

    def _replay_window(self, mask, finished, error):
        """A dispatched segment failed at its fetch: take its window again
        from the last synced host state. Pages are rewritten identically
        by a replay, so a page model decodes the window serially, bisecting
        to isolate. A STATE may already hold the window's tokens (the
        segment, and a speculative one built on it, update it in place),
        and a recurrence applied twice is silently wrong: its rows go back
        through prefill instead (:meth:`_readmit_window`)."""
        live = np.array([r is not None for r in self._slot_req])
        self._stall_cause = "replay"
        if self._state:
            self._readmit_window(mask & live, finished, error)
        else:
            self._segment_round(mask & live, finished)

    def _readmit_window(self, mask, finished, error):
        """The rows of a failed window, for a state model: each request
        goes back to the FRONT of the queue with its emitted tokens folded
        into its prompt (the preemption shape: the slot's grant zeroes the
        state and prefill rebuilds it, so the continuation is the
        uninterrupted run's). A request that was already taken back once
        retires as ``"failed"``: bisection cannot single out an offender
        here, so nothing is retried for ever."""
        taken = []
        for slot in np.flatnonzero(mask):
            req = self._slot_req[slot]
            if req is None:
                continue
            if req.preempted:
                bump_counter("serving.poison_request")
                req.error = error
                telemetry.flight_dump("poison_request", rid=req.rid,
                                      error=repr(error))
                self._retire(req, "failed", finished, slot=int(slot))
                continue
            bump_counter("serving.state_readmitted")
            if req.tokens:
                req.prompt = np.concatenate(
                    [req.prompt, np.asarray(req.tokens, np.int32)])
            req.preempted = True
            req.t_queued = time.monotonic()
            self._slot_req[slot] = None
            self._lengths[slot] = 1
            taken.append(req)
        self._queue.extendleft(reversed(taken))

    def _segment_round(self, mask, finished):
        """One compiled decode segment over the slots in ``mask`` + host
        token collection — the SERIAL turn (dispatch, wait, consume). A
        dispatch failure bisects the ACTIVE MASK (the compiled shape is
        fixed, so isolation masks slots out rather than re-batching) until
        the offending slot is alone, then retires it as ``"failed"`` — its
        co-batched slots decode in the retried halves. Per-request key
        streams make the replayed halves token-identical to an unbisected
        run."""
        if not mask.any():
            return
        h = None
        try:
            h = self._dispatch_segment(mask)
            self._consume(h, finished)
        except Exception as e:  # isolation boundary: bisect, never crash
            if h is not None and self._state:
                # the segment was dispatched and may have run: a state
                # cannot take its tokens a second time
                self._readmit_window(mask, finished, e)
                return
            idx = np.flatnonzero(mask)
            if len(idx) == 1:
                slot = int(idx[0])
                req = self._slot_req[slot]
                bump_counter("serving.poison_request")
                req.error = e
                telemetry.flight_dump("poison_request", rid=req.rid,
                                      error=repr(e))
                self._retire(req, "failed", finished, slot=slot)
                return
            left = mask.copy()
            left[idx[len(idx) // 2:]] = False
            self._segment_round(left, finished)
            self._segment_round(mask & ~left, finished)

    def _pipelined_round(self, mask, finished):
        """One OVERLAPPED scheduler turn: dispatch the next segment before
        consuming the previous one, so the device computes segment N+1
        while the host does segment N's bookkeeping.

        The speculative dispatch feeds segment N's device outputs straight
        back as the carry — retirements the device itself decided (eos,
        token budget) ride the carried active mask, so no host sync is
        needed. Host-only mask changes (admission, abort, deadline) drain
        the pipeline first via ``step()``. Per-request key streams keep
        the speculative segment token-identical to the serial schedule."""
        prev = self._inflight
        if prev is None:
            try:
                self._inflight = self._dispatch_segment(mask)
            except Exception:
                # sync dispatch failed: fall back to the serial round,
                # which replays with bisection
                self._stall_cause = "replay"
                self._segment_round(mask, finished)
            return
        seg = self._segment_len
        # speculate only when some slot can outlive the in-flight segment
        # (absent eos): otherwise every masked slot retires when ``prev``
        # is consumed and the speculative segment would be pure waste
        with annotate("serving.round_plan"):
            spec_worthy = any(
                self._slot_req[s] is not None
                and len(self._slot_req[s].tokens) + seg
                < self._slot_req[s].max_new_tokens
                for s in np.flatnonzero(mask))
        if not spec_worthy:
            self._drain_pipeline(finished, "not_spec_worthy")
            return
        try:
            h = self._dispatch_segment(
                mask, carry=(prev["tok"], prev["lengths"], prev["active"]),
                key_offset=seg)
        except Exception:
            # the speculative dispatch failed before running: drain the
            # pipeline, then replay this segment serially with bisection
            self._drain_pipeline(finished, "replay")
            live = np.array([r is not None for r in self._slot_req])
            self._segment_round(mask & live, finished)
            return
        # h becomes the in-flight segment BEFORE prev's bookkeeping so
        # pages freed by retirements inside _consume see it and
        # quarantine (h still writes every carried slot's cell)
        self._inflight = h
        try:
            self._consume(prev, finished)
        except Exception as e:  # isolation boundary: bisect, never crash
            # prev's ASYNC execution failed (surfaced at the fetch, not
            # the dispatch): the speculative segment was built on its
            # outputs — discard it and replay prev's window serially from
            # the last synced host state, bisecting to isolate
            self._inflight = None
            self._replay_window(prev["mask"], finished, e)
            return

    def step(self):
        """One scheduler turn: admit queued requests into free slots
        (same-bucket admissions share ONE compiled prefill dispatch at the
        group width, under poison isolation), run one compiled decode
        segment — overlapped with the previous segment's host bookkeeping
        when the pipeline is enabled — then enforce deadlines BETWEEN
        segments (never mid-dispatch). Returns the list of ``Request``
        objects retired this turn (one segment behind the device when
        pipelined).

        The turn is ONE span tree in the telemetry sink and the device
        trace: ``serving.turn`` over its leaves ``serving.drain``
        (``device_wait`` + ``host_bookkeeping`` inside), ``admit_plan``,
        the prefill spans, ``ensure_pages``, ``kv_account``,
        ``segment_dispatch`` and ``deadline_sweep`` — a device idle gap
        is named by the leaf that was open in it."""
        with annotate("serving.turn"):
            return self._turn()

    def _turn(self):
        finished: list[Request] = []
        # admission and mask repair need a current host view: consume the
        # in-flight segment BEFORE touching slots (prefill rewrites a
        # freed slot's pages; the in-flight segment was built on the old
        # mask)
        if self._inflight is not None and (
                self._dirty or (self._queue and self.free_slots() > 0)):
            self._drain_pipeline(
                finished, "dirty" if self._dirty else "admission")
        with annotate("serving.admit_plan"):
            by_bucket, r_by_bucket, long_adm = self._admit_plan(finished)
        for bucket, grp in by_bucket.items():
            self._isolate(
                grp, lambda sub, b=bucket: self._dispatch_prefill(
                    sub, b, finished), finished)
        for bucket, grp in r_by_bucket.items():
            self._isolate(
                grp, lambda sub, b=bucket: self._dispatch_resume(
                    sub, b, finished), finished)
        if long_adm:
            self._isolate(
                long_adm, lambda sub: self._chunked_prefill(sub, finished),
                finished)
        with annotate("serving.ensure_pages"):
            if self._cow_pair:
                # every copy is dispatched (or its request terminally
                # retired) by now: release the plan-time source-page
                # holds — the isolates never raise, so this line is
                # always reached
                self._recycle(self._pool.decref(
                    [s for s, _ in self._cow_pair.values()]))
                self._cow_pair = {}
            # decode growth: every active slot must hold pages for the
            # next dispatch window BEFORE it is dispatched (may preempt
            # under pool pressure — never fails a running decode)
            self._ensure_pages(finished)

        with annotate("serving.kv_account"):
            active_np = np.array([r is not None for r in self._slot_req])
            if telemetry.enabled():
                self._kv_account(active_np)
                perfwatch.memory_watchdog().maybe_poll()
            live = bool(active_np.any())
            if live:
                self._occ_sum += float(active_np.mean())
                self._occ_n += 1
        if live:
            if self._pipeline:
                self._pipelined_round(active_np, finished)
            else:
                self._segment_round(active_np, finished)
        elif self._inflight is not None:
            # nothing live in the host view but a segment still in flight
            # (every slot retired at the last consume): drain it
            self._drain_pipeline(finished, "not_spec_worthy")
        with annotate("serving.deadline_sweep"):
            self._deadline_sweep(finished)
        return finished

    def _admit_plan(self, finished):
        """The admission walk of one turn: grant slots and pages to the
        queue's head requests and group them by the prefill program each
        rides. Returns ``(by_bucket, resume_by_bucket, long_adm)``."""
        # ---- admission: FIFO over the queue, bounded by free slots AND
        # free POOL PAGES. A head the pool cannot serve DEFERS the whole
        # queue (no skip-ahead — a stream of small requests must not
        # starve a big one) with serving.kv_pool_exhausted backpressure.
        self.admission_blocked = False
        self._resume_base = {}
        self._cow_pair = {}
        chunk_w = self.prompt_buckets[-1]
        free = [s for s in range(self.max_slots)
                if self._slot_req[s] is None]
        admitting, long_adm, resume_adm = [], [], []
        fi = 0
        while self._queue and fi < len(free):
            req = self._queue[0]
            if req.status != "pending":
                self._queue.popleft()
                continue
            if req.kv_import is not None:
                # disaggregated DECODE admission: adopt the completed KV
                # import — pure host bookkeeping, no prefill dispatch.
                # A missing/incomplete ticket (source died, chunks never
                # finished) falls through to a normal local re-prefill.
                imp = self._imports.pop(req.kv_import, None)
                req.kv_import = None
                if (imp is not None
                        and len(imp["done"]) >= int(
                            imp["meta"]["n_chunks"])
                        and int(imp["meta"]["prefill_len"])
                        == int(req.prompt.size)):
                    self._queue.popleft()
                    slot = free[fi]
                    fi += 1
                    self._stamp_admit(slot, req)
                    self._adopt_import(slot, req, imp, finished)
                    continue
                if imp is not None:
                    self._recycle(self._pool.decref(imp["pages"]))
                bump_counter("serving.kv_import_miss")
            plan = self._plan_admission(req)
            if plan is None and self._quarantine:
                # the missing pages may be freed-but-unproven: block on
                # the pool buffers (proves every dispatched program
                # executed, draining the quarantine) and retry — without
                # this, a session whose only retirement rode a failed
                # dispatch could defer the head forever with no active
                # slot left to trigger the _ensure_pages flush
                jax.block_until_ready(self._ks[0])
                self._mark_executed(self._disp_n)
                plan = self._plan_admission(req)
            if plan is None:
                bump_counter("serving.kv_pool_exhausted")
                self.admission_blocked = True
                break
            self._queue.popleft()
            slot = free[fi]
            fi += 1
            self._stamp_admit(slot, req)
            shared, m, cow_src, newp = plan
            self._slot_pages[slot] = list(shared) + newp
            self._set_table_row(slot)
            self._slot_adm[slot] = self._adm_seq
            self._adm_seq += 1
            self._prefix_lookup_tokens += int(req.prompt.size)
            if m:
                self._prefix_hit_tokens += m
                self._resume_base[id(req)] = m
                if telemetry.enabled():
                    _M_PREFIX_SAVED.inc(m)
                if cow_src is not None:
                    # the divergent page: copy the cached content, then
                    # append into the private copy (dispatched inside
                    # the request's resume-group isolation scope)
                    self._cow_pair[id(req)] = (
                        cow_src, self._slot_pages[slot][len(shared)])
                if req.prompt.size - m <= chunk_w:
                    resume_adm.append((slot, req))
                else:
                    long_adm.append((slot, req))
            elif req.prompt.size > chunk_w:
                long_adm.append((slot, req))
            else:
                admitting.append((slot, req))
        by_bucket: dict[int, list] = {}
        for slot, req in admitting:
            b = _bucket(req.prompt.size, self.prompt_buckets)
            by_bucket.setdefault(b, []).append((slot, req))
        r_by_bucket: dict[int, list] = {}
        for slot, req in resume_adm:
            b = _bucket(req.prompt.size - self._resume_base[id(req)],
                        self.prompt_buckets)
            r_by_bucket.setdefault(b, []).append((slot, req))
        return by_bucket, r_by_bucket, long_adm

    def _deadline_sweep(self, finished):
        # deadline enforcement BETWEEN segments: an expired slot retires
        # with its partial output and frees capacity for the queue; queued
        # requests whose budget ran out while waiting drain as timed_out;
        # a run-level timeout retires everything still unfinished
        retired_slot = False
        for slot in range(self.max_slots):
            req = self._slot_req[slot]
            if req is not None and (req.deadline.expired()
                                    or self._run_deadline.expired()):
                self._retire(req, "timed_out", finished, slot=slot)
                retired_slot = True
        if retired_slot and self._inflight is not None:
            # the device cannot see a deadline retirement: force a drain
            # + sync turn before the next dispatch
            self._dirty = True
        if self._queue:
            waiting: deque[Request] = deque()
            for req in self._queue:
                if req.status != "pending":
                    continue
                if req.deadline.expired() or self._run_deadline.expired():
                    self._retire(req, "timed_out", finished)
                else:
                    waiting.append(req)
            self._queue = waiting

    # ------------------------------------------------ dynamic page pool

    def _growth_horizon(self) -> int:
        """Positions the next dispatch window may write past a slot's
        current length: one segment, or two when the pipeline may hold
        an unconsumed segment plus a speculative one."""
        return self._segment_len * (2 if self._pipeline else 1)

    def _plan_admission(self, req):
        """Page plan for admitting ``req``: match its prompt against the
        prefix cache, then reserve pool pages for the unshared part.
        Returns ``(shared_pages, resume_tokens, cow_src, new_pages)`` —
        commits pool references on success — or ``None`` when the pool
        (after LRU cache eviction) cannot cover the admission plus its
        first-window decode growth: the caller defers the queue head.
        A previously PREEMPTED request requires coverage of its FULL
        remaining budget, so it cannot thrash straight back out."""
        if not self._paged:
            return [], 0, None, []   # a granted slot owns its state
        P = int(req.prompt.size)
        page = self.page_size
        chunk_w = self.prompt_buckets[-1]
        shared, m, cow_src = [], 0, None
        if self._prefix is not None and P > 1:
            pages, matched, partial = self._prefix.match(req.prompt)
            mtok = matched + (partial.r if partial is not None else 0)
            # never serve the WHOLE prompt from cache: the last token
            # must run through the model to produce sampling logits
            mtok = min(mtok, P - 1)
            if P - mtok > chunk_w:
                # long divergent tail rides the page-aligned chunked
                # path: round the resume base down to a page boundary
                # (drops at most page_size-1 shared tokens)
                mtok = (mtok // page) * page
            full = mtok // page
            shared = pages[:full]
            m = mtok
            if m % page:
                # resume base sits mid-page: the covering cached page is
                # mapped via copy-on-write (writers must not touch the
                # shared original)
                cow_src = pages[full] if full < len(pages) else partial.page
        total = -(-P // page)
        new_needed = total - len(shared)
        remaining = req.max_new_tokens - len(req.tokens)
        final_len = max(P + remaining - 1, P)
        want_tokens = (final_len if req.preempted
                       else min(P + self._growth_horizon(), final_len))
        check_needed = max(-(-want_tokens // page), total) - len(shared)
        if self._pool.available() < check_needed:
            if self._prefix is not None:
                excl = set(shared)
                if cow_src is not None:
                    excl.add(cow_src)
                self._prefix.evict(
                    check_needed - self._pool.available(), exclude=excl)
            if self._pool.available() < check_needed:
                return None
        for p in shared:
            self._pool.incref(p)
        if cow_src is not None:
            # hold the copy source until the CoW dispatch reads it
            self._pool.incref(cow_src)
        newp = self._pool.alloc(new_needed) if new_needed else []
        return shared, m, cow_src, newp

    def _ensure_pages(self, finished):
        """Grant every active slot the pages its next dispatch window
        can write (admission granted prompt coverage only; decode grows
        page by page). Under pool pressure: evict prefix-cache leaves
        first, flush the free-quarantine (draining the pipeline proves
        execution), and as a last resort PREEMPT the youngest slot back
        to the queue — its stream resumes bit-identically via the
        per-request key stream, and the prefix cache usually makes the
        re-prefill one page of work. A running decode never fails."""
        if not self._paged:
            return                   # nothing grows with a sequence
        horizon = self._growth_horizon()
        while True:
            need = []
            for slot, req in enumerate(self._slot_req):
                if req is None:
                    continue
                tgt = min(int(self._lengths[slot]) + horizon,
                          int(self._limits[slot]))
                short = (-(-tgt // self.page_size)
                         - len(self._slot_pages[slot]))
                if short > 0:
                    need.append((slot, short))
            total = sum(n for _, n in need)
            if not total:
                return
            if self._pool.available() < total and self._prefix is not None:
                self._prefix.evict(total - self._pool.available())
            if self._pool.available() >= total:
                for slot, n in need:
                    self._slot_pages[slot].extend(self._pool.alloc(n))
                    self._set_table_row(slot)
                return
            if self._quarantine:
                # freed pages are waiting on execution proof: drain the
                # pipeline (a blocking fetch) — or block on the pool
                # buffers directly when nothing is in flight
                if self._inflight is not None:
                    self._dirty = True
                    self._drain_pipeline(finished)
                else:
                    jax.block_until_ready(self._ks[0])
                    self._mark_executed(self._disp_n)
                continue
            victims = [s for s, r in enumerate(self._slot_req)
                       if r is not None]
            if len(victims) <= 1:
                # arithmetically unreachable (pool >= pages of one full
                # sequence and a lone slot's own grants count against
                # its need), but never spin here
                return
            self._preempt(max(victims, key=lambda s: self._slot_adm[s]),
                          finished)

    def _preempt(self, slot, finished):
        """Pull the request off ``slot`` to free its pages, folding its
        emitted tokens into the prompt (the failover-resume shape: key
        stream indices are ``token_base + len(tokens)``, both unchanged,
        so the eventual continuation is bit-identical). The request goes
        back to the FRONT of the queue."""
        req = self._slot_req[slot]
        bump_counter("serving.kv_preempted")
        if self._inflight is not None:
            # the in-flight segment still decodes this slot; discard its
            # unconsumed emissions (regenerated identically later) and
            # sync the host view first
            self._dirty = True
            self._drain_pipeline(finished)
            if self._slot_req[slot] is not req or req.status != "pending":
                return  # retired while draining — pages already freed
        if req.tokens:
            req.prompt = np.concatenate(
                [req.prompt, np.asarray(req.tokens, np.int32)])
        req.preempted = True
        req.t_queued = time.monotonic()  # a second engine_wait begins
        self._slot_req[slot] = None
        self._lengths[slot] = 1
        self._free_slot_pages(slot)
        self._queue.appendleft(req)
        if telemetry.enabled():
            telemetry.trace_event("serving.kv_preempt", trace=req.trace,
                                  rid=req.rid, emitted=len(req.tokens))

    def read_state(self, slot):
        """A state model: what ``slot``'s state rows hold now, a layer a
        pair of host arrays in the model's own layout (a retired request's
        stay until its slot is granted again; ``Request.slot`` names it).
        For tests and for a benchmark's comparison: it waits for whatever
        is in flight on the device."""
        if not self._state:
            raise ValueError(
                f"{type(self.model).__name__} keeps pages, not a state")
        return [(np.asarray(s[slot]), np.asarray(z[slot]))
                for s, z in self._keep.states(self._ks, self._vs)]

    # -------------------------- KV page transfer (disaggregation handoff)
    #
    # Engine-side primitive surface for prefill/decode disaggregation:
    # the SOURCE mints a ticket over the pages a hold_kv prefill pinned
    # (export_kv), serves CRC-framed fixed-width chunks (transfer_chunk)
    # and releases the pin when the handoff completes (release_export);
    # the DESTINATION lands chunks idempotently by ticket id
    # (import_kv_chunk) and the adopting request seats onto the landed
    # pages at admission. The chunk programs are AOT-warmed — the whole
    # path dispatches zero post-warmup compiles. The transfer DRIVER
    # (retries, failover, journaling) lives in models/transfer.py and
    # the router; the engine only moves pages.

    def _pinned_pages(self) -> int:
        """Pool pages pinned by the transfer machinery (holds + live
        export tickets + partially imported chunks) — granted, but
        invisible to the slot table."""
        return (sum(len(h["pages"])
                    for h in getattr(self, "_kv_holds", {}).values())
                + sum(len(e["pages"])
                      for e in getattr(self, "_exports", {}).values())
                + sum(len(i["pages"])
                      for i in getattr(self, "_imports", {}).values()))

    def export_pages(self, rid):
        """Mint (or re-serve) the transfer ticket over the pages a
        ``hold_kv`` prefill retirement pinned for ``rid``. Idempotent by
        rid — a router re-drive after a crash gets the SAME ticket, so
        the destination's by-ticket dedup makes the whole handoff
        exactly-once. Returns the ticket dict, or None when the rid
        holds no exportable pages (never prefilled here, already
        released, or a respawned engine)."""
        self._refuse_state("export_pages")
        tid = self._export_by_rid.get(rid)
        if tid is not None and tid in self._exports:
            return dict(self._exports[tid]["ticket"])
        hold = self._kv_holds.pop(rid, None)
        if hold is None or hold["first_token"] is None:
            return None
        tid = uuid.uuid4().hex
        n_pages = len(hold["pages"])
        ticket = {
            "ticket": tid,
            "rid": rid,
            "n_pages": n_pages,
            "chunk_pages": _XFER_WIDTH,
            "n_chunks": -(-n_pages // _XFER_WIDTH),
            "prefill_len": hold["prefill_len"],
            "first_token": hold["first_token"],
            "page_size": self.page_size,
        }
        self._exports[tid] = {"pages": hold["pages"], "ticket": ticket}
        self._export_by_rid[rid] = tid
        return dict(ticket)

    def transfer_chunk(self, ticket, idx):
        """SOURCE side: serve chunk ``idx`` of a live export as
        ``[n_valid, payk, payv, crc32]`` — payloads are host
        ``(layers, W, page, kv, hd)`` arrays, CRC framed over both.
        An unknown ticket raises typed ``ServingUnavailable``: the
        caller cannot distinguish a released ticket from a respawned
        source, and both mean the pages are gone — re-prefill."""
        try:
            inject("transfer.source_death")
        except InjectedFault as e:
            bump_counter("transfer.source_death")
            raise ServingUnavailable(
                f"injected source death mid-transfer ({ticket})") from e
        exp = self._exports.get(ticket)
        if exp is None:
            raise ServingUnavailable(
                f"unknown export ticket {ticket!r}: no pinned pages "
                "(released, or a respawned source process)")
        sel = exp["pages"][idx * _XFER_WIDTH:(idx + 1) * _XFER_WIDTH]
        if not sel:
            raise ValueError(
                f"chunk {idx} out of range for ticket {ticket!r}")
        pad = sel + [self._dump_page] * (_XFER_WIDTH - len(sel))
        self._ks, self._vs, payk, payv = self._call(
            ("export", _XFER_WIDTH), self._export_p, self._params,
            self._ks, self._vs, jnp.asarray(np.asarray(pad, np.int32)))
        payk = np.asarray(jax.device_get(payk))
        payv = np.asarray(jax.device_get(payv))
        crc = zlib.crc32(payv.tobytes(), zlib.crc32(payk.tobytes()))
        return [len(sel), payk, payv, crc]

    def release_export(self, ticket) -> bool:
        """SOURCE side: drop a finished (or abandoned) export's pin —
        the pages decref back toward the free list. Idempotent."""
        exp = self._exports.pop(ticket, None)
        if exp is None:
            return False
        self._export_by_rid.pop(exp["ticket"]["rid"], None)
        self._recycle(self._pool.decref(exp["pages"]))
        return True

    def import_kv_chunk(self, meta, idx, payk, payv, crc):
        """DESTINATION side: land one CRC-framed chunk of the export
        described by ``meta`` (the ticket dict). First chunk allocates
        the local page grants; chunks land idempotently by ticket id +
        index, so a resumed transfer replays duplicates harmlessly.
        Returns ``"done"`` when every chunk has landed, ``"ok"`` on a
        partial landing, ``"dup"`` for an already-landed index,
        ``"crc_mismatch"`` for a corrupt frame (caller re-sends), or
        ``"no_capacity"`` when the pool cannot grant the pages."""
        self._refuse_state("import_pages (import_kv_chunk)")
        try:
            inject("transfer.import_fail")
        except InjectedFault:
            bump_counter("transfer.import_fail")
            raise
        tid = meta["ticket"]
        st = self._imports.get(tid)
        if st is None:
            n_pages = int(meta["n_pages"])
            pages = self._pool.alloc(n_pages)
            if pages is None and self._prefix is not None:
                # same pressure valve admission uses: evict unreferenced
                # prefix pages, then retry the grant
                self._prefix.evict(n_pages - self._pool.available())
                pages = self._pool.alloc(n_pages)
            if pages is None:
                bump_counter("serving.kv_pool_exhausted")
                return "no_capacity"
            st = {"pages": pages, "meta": dict(meta), "done": set()}
            self._imports[tid] = st
        idx = int(idx)
        n_chunks = int(st["meta"]["n_chunks"])
        if idx in st["done"]:
            return "done" if len(st["done"]) >= n_chunks else "dup"
        payk = np.asarray(payk)
        payv = np.asarray(payv)
        if zlib.crc32(payv.tobytes(),
                      zlib.crc32(payk.tobytes())) != int(crc):
            bump_counter("transfer.crc_mismatch")
            return "crc_mismatch"
        w = int(st["meta"].get("chunk_pages", _XFER_WIDTH))
        sel = st["pages"][idx * w:(idx + 1) * w]
        if not sel:
            raise ValueError(
                f"chunk {idx} out of range for ticket {tid!r}")
        pad = sel + [self._dump_page] * (_XFER_WIDTH - len(sel))
        self._ks, self._vs = self._call(
            ("import", _XFER_WIDTH), self._import_p, self._params,
            self._ks, self._vs, jnp.asarray(np.asarray(pad, np.int32)),
            jnp.asarray(payk), jnp.asarray(payv))
        st["done"].add(idx)
        return "done" if len(st["done"]) >= n_chunks else "ok"

    def drop_import(self, ticket) -> bool:
        """DESTINATION side: abandon a (possibly partial) import and
        free its local page grants. Idempotent."""
        st = self._imports.pop(ticket, None)
        if st is None:
            return False
        self._recycle(self._pool.decref(st["pages"]))
        return True

    def _kv_usage(self, active_idx):
        """ONE definition of the page-granular KV arithmetic (the gauges
        and ``kv_stats`` must never desynchronize): pool occupancy,
        bytes, and fragmentation — the allocated-but-unused TAIL of the
        pages granted to active slots (the dynamic-allocator waste; the
        static slot map's waste was every slot's whole unreached tail).
        Prefix accounting rides along: hit rate is shared prompt tokens
        over admitted prompt tokens for the session."""
        n = len(active_idx)
        slot_pages = getattr(self, "_slot_pages",
                             [[] for _ in range(self.max_slots)])
        if n:
            used = int(self._lengths[list(active_idx)]
                       .astype(np.int64).sum())
            # logical grants (shared pages count once per MAPPING): the
            # fragmentation denominator — per-slot tail waste is defined
            # against what each slot was granted
            pages = sum(len(slot_pages[int(s)]) for s in active_idx)
            # physical bytes (shared pages count ONCE): what the slots
            # actually occupy of the pool — under prefix sharing the
            # logical sum can exceed the pool, the byte gauge must not
            phys = len({p for s in active_idx
                        for p in slot_pages[int(s)]})
        else:
            used = pages = phys = 0
        cap_tokens = pages * self.page_size
        pool = getattr(self, "_pool", None)
        free = pool.available() if pool is not None else 0
        lookups = getattr(self, "_prefix_lookup_tokens", 0)
        hits = getattr(self, "_prefix_hit_tokens", 0)
        return {
            # pages at what a token costs, and live slots at what a
            # slot's state holds (either is 0 where no layer keeps it)
            "bytes_in_use": (phys * self.page_size
                             * self._kv_bytes_per_token
                             + n * self._state_bytes_per_slot),
            "slot_occupancy": n / self.max_slots if self.max_slots else 0.0,
            "fragmentation_pct": (100.0 * (1.0 - used / cap_tokens)
                                  if cap_tokens else 0.0),
            "bytes_per_token": self._kv_bytes_per_token,
            "state_bytes_per_slot": self._state_bytes_per_slot,
            "slots_live": n,
            "pages_total": self._pool_pages,
            "pages_free": free,
            "pages_granted": phys,
            "pages_pinned_export": self._pinned_pages(),
            "prefix_cached_pages": (len(self._prefix)
                                    if self._prefix is not None else 0),
            "prefix_hit_rate": (hits / lookups) if lookups else 0.0,
            "prefix_tokens_saved": hits,
            "max_len": self.max_len,
            "max_len_rounded_from": self._max_len_rounded_from,
            "page_size": self.page_size,
        }

    def _kv_account(self, active_np):
        """Refresh the logical KV-occupancy gauges from the host view of
        the slots (one segment behind the device when pipelined)."""
        u = self._kv_usage(np.flatnonzero(active_np))
        _M_KV_BYTES.set(u["bytes_in_use"])
        _M_KV_OCC.set(u["slot_occupancy"])
        _M_KV_FRAG.set(u["fragmentation_pct"])
        _M_KV_PAGES_FREE.set(u["pages_free"])
        _M_KV_PAGES_TOTAL.set(u["pages_total"])
        _M_KV_PINNED.set(u["pages_pinned_export"])
        _M_PREFIX_HIT.set(u["prefix_hit_rate"])
        for slot in range(self.max_slots):
            _M_KV_SLOT_PAGES.set(len(self._slot_pages[slot]),
                                 slot=slot)

    def kv_stats(self) -> dict:
        """Point-in-time KV accounting for THIS engine (the gauges are
        process-level and last-writer-wins across engines)."""
        return self._kv_usage(
            [s for s, r in enumerate(getattr(self, "_slot_req", ()))
             if r is not None])

    def note_rejection(self):
        """Count a frontend-level rejection in the session stats, so
        ``stats()['rejected']`` reflects the whole serving stack (the
        engine itself never rejects — admission control lives above)."""
        self._counts["rejected"] = self._counts.get("rejected", 0) + 1

    def stats(self):
        """Running session stats. ``tokens_per_sec`` is 0.0 for an empty
        or zero-duration session (never inf).

        ``tokens_per_sec`` is measured over the session WALL clock, so a
        cold session (no prior ``warmup()``) absorbs every first-shape
        compilation into the number — call ``warmup()`` first (or compare
        only warmed sessions) when reading it as device throughput.
        ``host_gap_ms`` is the mean host-side gap between finishing one
        segment's bookkeeping and issuing the next dispatch
        (``host_gap_total_s`` is the session total) — with the pipeline
        enabled this work overlaps device compute; a growing value flags
        host-overhead regressions either way.

        ``phases`` (perfwatch step-time attribution) summarizes the
        PROCESS-wide ``serving.phase_s`` histogram — p50/p95/p99 + mean
        per scheduler phase (prefill / chunked_prefill /
        segment_dispatch / device_wait / host_bookkeeping / host_gap);
        ``kv`` is this engine's logical KV occupancy (bytes at page
        granularity, slot occupancy, interior fragmentation). Both are
        empty with ``FLAGS_telemetry=0``."""
        dt = time.monotonic() - self._t0
        return {
            "phases": (perfwatch.phase_summaries()
                       if telemetry.enabled() else {}),
            "kv": self.kv_stats() if telemetry.enabled() else {},
            "tokens_per_sec": (self._useful / dt
                               if dt > 0 and self._useful else 0.0),
            "useful_tokens": self._useful,
            "segments": self._seg_runs,
            "mean_occupancy": (self._occ_sum / self._occ_n
                               if self._occ_n else 0.0),
            "wall_s": dt,
            "host_gap_ms": (1e3 * self._gap_sum / self._gap_n
                            if self._gap_n else 0.0),
            "host_gap_total_s": self._gap_sum,
            "pipelined": self._pipeline,
            "timed_out": self._counts.get("timed_out", 0),
            "failed": self._counts.get("failed", 0),
            "cancelled": self._counts.get("cancelled", 0),
            "rejected": self._counts.get("rejected", 0),
        }

    # ------------------------------------------------------------ host loop

    def run(self, prompts, max_new_tokens, segment=16,
            request_deadline_s=None, timeout_s=None):
        """Generate ``max_new_tokens`` for every prompt (list of 1-D int
        arrays, mixed lengths), admitting/retiring between ``segment``-step
        compiled decode windows. Returns (outputs, stats): outputs[i] is
        the generated id array for prompts[i]; stats carries sustained
        tokens/sec over the decode segments, occupancy, per-request
        ``statuses``, and ``timed_out``/``failed``/``cancelled``/
        ``rejected`` counts.

        Resilience budgets (checked BETWEEN segments, so a straggler
        never blocks in-flight slots mid-dispatch):

        * ``request_deadline_s`` — wall-clock budget per request (scalar,
          or a per-request sequence; None entries are unbounded), measured
          from ``run()`` entry so queue wait counts. A request past its
          deadline is retired with whatever tokens it produced and status
          ``"timed_out"`` — it stops pinning a slot, queued requests that
          expired before admission drain the same way, and a long-context
          admission expiring mid-prefill skips its remaining chunks.
        * ``timeout_s`` — budget for the whole call; on expiry every
          unfinished request retires as ``timed_out`` and run() returns.

        Failure isolation: an exception inside a prefill / chunked-prefill
        / decode dispatch bisects the batch (see ``_isolate``) — the
        offending request retires as ``"failed"`` with its partial tokens
        while its co-batched peers complete normally. Token streams are
        identical with the pipeline on or off, under bisection replays,
        and for any admission interleaving (per-request key streams).
        """
        prompts_np = [np.asarray(p).astype(np.int32).ravel()
                      for p in prompts]
        for p in prompts_np:
            # validate UP FRONT: a request that can never fit must raise
            # before any other request's work is dispatched
            self._validate(p, max_new_tokens)
        if request_deadline_s is None or not np.iterable(request_deadline_s):
            request_deadline_s = [request_deadline_s] * len(prompts)
        if len(request_deadline_s) != len(prompts):
            raise ValueError(
                f"request_deadline_s has {len(request_deadline_s)} entries "
                f"for {len(prompts)} prompts")
        self.start(segment=segment, run_deadline=Deadline(timeout_s))
        reqs = [self.submit(p, max_new_tokens, deadline_s=s, rid=i)
                for i, (p, s) in enumerate(
                    zip(prompts_np, request_deadline_s))]
        while self.has_work():
            self.step()
        stats = self.stats()
        stats["statuses"] = [r.status for r in reqs]
        return [r.output() for r in reqs], stats
