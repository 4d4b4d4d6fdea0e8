"""Serving engine: scheduler-driven continuous batching over three registries.

The paper's GEMV-V scenario as a service: weights are converted once to a
quantized residency mode (``convert_params``), stay device-resident, and
every request runs prefill + N decode steps against them.  Per the paper's
§IV-B amortization argument, the bit-plane/packing transform happens at
convert time; the per-request activation quantization is fused in the
kernels.

Residency is governed by **four registry concepts**, one per resident
concern:

* ``mode``          — *weight* residency (:mod:`repro.core.residency`):
                      which layout each parameter tree leaf serves from.
* ``cache_format``  — *decode-cache* residency (:mod:`repro.core.kvcache`):
                      how K/V (and the MLA latent) slots are stored/read.
* *pages*           — *physical cache placement* (:mod:`repro.core.paging`):
                      a ``paged_*`` cache format breaks the slot→storage
                      identity; a refcounted :class:`~repro.core.paging.
                      PagePool` plus a radix prefix index decide which
                      physical pages back each slot's block table (prefix
                      sharing, COW, eviction).
* ``scheduler``     — *host-side orchestration*
                      (:mod:`repro.serve.scheduler`): which requests batch
                      together, when refills run, how prefill work is
                      chunked against decode latency.

so e.g. ``ServeEngine(mode={"ffn": "bsdp"}, cache_format="paged_int4_bp",
scheduler="prefix_cache")`` serves both dominant resident payloads
bit-plane-resident while shared prompt prefixes occupy one physical copy
and long prompts chunk so queued requests' TTFT never stalls behind a
monolithic prefill.

``ServeEngine`` implements continuous batched decode: requests of different
lengths share one ring-cache batch; finished (or cancelled) slots are
refilled by new prompts without stopping the decode loop.  Each ``step()``
is ``scheduler.plan(EngineView) → _execute(StepPlan)``: all refills in the
plan run as ONE microbatched prefill call (left-padded, negative positions
masked), and chunk rows + decode rows share one chunked-decode invocation.
Requests are lifecycle objects (``QUEUED → PREFILLING → DECODING → DONE |
CANCELLED``) with per-token streaming callbacks and three-clock SLO stamps
(wall seconds / engine steps / processed-position work units) surfaced by
:meth:`ServeEngine.stats`.

The whole step loop is instrumented through :mod:`repro.obs` — the fifth
registry concept: each ``step()`` decomposes into ``engine.plan`` /
``reserve`` / ``cow`` / ``prefill`` / ``decode`` / ``complete`` spans
(``prefill`` and ``decode`` each end in an ``engine.readback`` span, the
wait for the logits on the host), request lifecycle stamps double as
``request.*`` instant events (uid → queue wait and TTFT/TPOT derivable
from the trace alone, value-identical to ``stats()``),
and resident weight/cache bytes are gauged per step from the same registry
accounting the dry-run twins predict.  ``ServeEngine(..., trace=True)``
retains it all in a ring (:meth:`ServeEngine.timeline`); with no sink
registered, every site is a single-branch no-op.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kvcache, paging, qlinear, residency
from repro.models import model as model_lib
from repro.obs import trace as obs
from repro.serve import scheduler as sched_lib
from repro.serve.scheduler import (
    CANCELLED,
    DECODING,
    DONE,
    PREFILLING,
    QUEUED,
    EngineStats,
    EngineView,
    Stamp,
    StepPlan,
)

# Parameter-tree paths (leaf dict keys) eligible for quantized residency.
QUANTIZABLE_KEYS = (
    "wq", "wk", "wv", "wo",
    "w_in", "w_out", "w_uq", "w_dq", "w_dkv", "w_uk", "w_uv",
    "in_proj", "out_proj", "x_proj",
    "shared_w_in", "shared_w_out",
    "head",
)


def convert_params(params, cfg, spec, *, min_dim: int = 64):
    """One-time residency conversion (the amortized layout transform).

    ``spec`` is anything :meth:`repro.core.residency.ResidencySpec.parse`
    accepts: a bare format name (uniform residency), a per-layer policy map
    (``{"ffn": "bsdp", "mixer": "w8a16", "default": "w8a8"}``), a CLI string
    (``"ffn=bsdp,default=w8a8"``) or a ResidencySpec.  The tree is walked
    with dot-joined paths; 2-D float leaves under quantizable keys (and 3-D
    stacked/expert variants, handled per-slice) become the
    :class:`QuantLinearState` of whichever format the policy selects for
    their path.  Norms, biases, embeddings, SSM dynamics — and leaves the
    policy maps to ``bf16`` — stay float.
    """
    spec = residency.ResidencySpec.parse(spec)
    if spec.is_trivial:
        return params

    def walk(tree, path):
        if isinstance(tree, dict):
            return {
                k: _convert_leaf(v, spec.mode_for(".".join(path + (k,))), min_dim)
                if k in QUANTIZABLE_KEYS
                else walk(v, path + (k,))
                for k, v in tree.items()
            }
        return tree

    return walk(params, ())


def _convert_leaf(w, mode, min_dim):
    if residency.get_format(mode).keeps_float_params:
        return w
    if not isinstance(w, jnp.ndarray) or w.ndim < 2:
        return w
    if w.ndim == 2:
        if min(w.shape) < min_dim:
            return w
        return residency.from_float(w.astype(jnp.float32), mode)
    # stacked [L, K, N] (scan) or [E, K, N] (experts) or [L, E, K, N]
    lead = w.shape[:-2]
    flat = w.reshape(-1, *w.shape[-2:])
    if min(w.shape[-2:]) < min_dim:
        return w
    states = [residency.from_float(flat[i].astype(jnp.float32), mode) for i in range(flat.shape[0])]
    data = jnp.stack([s.data for s in states]).reshape(*lead, *states[0].data.shape)
    scale = jnp.stack([s.scale for s in states]).reshape(*lead, *states[0].scale.shape)
    return residency.QuantLinearState(
        data=data, scale=scale, mode=mode, k=states[0].k, n=states[0].n
    )


def resident_bytes(params) -> int:
    """Total device-resident weight bytes (roofline memory-term input).

    Quantized leaves are byte-counted by their registered format's
    ``resident_bytes`` (payload + scales) and float leaves by their array
    size — the same registry accounting the dry-run's ``abstract_quant``
    walk uses, so the two cannot drift.
    """
    total = 0
    for leaf in jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, residency.QuantLinearState)
    ):
        if isinstance(leaf, residency.QuantLinearState):
            total += residency.get_format(leaf.mode).resident_bytes(leaf)
        else:
            total += leaf.size * leaf.dtype.itemsize
    return total


@dataclasses.dataclass(eq=False)  # identity equality: queue membership
class Request:
    """One serving request as a lifecycle object.

    ``Request(uid, prompt, max_new)`` keeps working positionally (legacy
    construction); ``uid=None`` is auto-assigned at ``submit`` time.
    ``state`` walks ``QUEUED → PREFILLING → DECODING → DONE``; ``cancel()``
    moves any non-terminal state to ``CANCELLED`` and the engine frees the
    slot at its next step.  ``on_token(req, tok)`` streams every emitted
    token; ``arrival``/``scheduled``/``first_token``/``finished`` are
    three-clock :class:`~repro.serve.scheduler.Stamp` records (queue wait
    and TTFT/TPOT inputs); ``scheduled`` is when it left the queue holding
    a slot.
    """

    uid: Optional[int] = None
    prompt: np.ndarray = None  # [P] int32
    max_new: int = 0
    out: list = dataclasses.field(default_factory=list)
    #: optional teacher-forced continuation — when set, decode feeds these
    #: tokens instead of argmax sampling.  Used for residency-mode logit
    #: regression (identical token stream across modes) and speculative
    #: verification.
    force: Optional[np.ndarray] = None
    state: str = QUEUED
    #: prompt tokens already consumed (== len(prompt) once DECODING)
    prefilled: int = 0
    on_token: Optional[Callable[["Request", int], None]] = None
    arrival: Optional[Stamp] = None
    scheduled: Optional[Stamp] = None
    first_token: Optional[Stamp] = None
    finished: Optional[Stamp] = None

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def done(self) -> bool:
        """Legacy flag: terminal state (DONE or CANCELLED)."""
        return self.state in (DONE, CANCELLED)

    @done.setter
    def done(self, value: bool) -> None:  # legacy writers
        if value:
            self.state = DONE

    def cancel(self) -> None:
        """Cancel the request; the engine frees its slot at the next step
        (a queued request is dropped before ever taking a slot)."""
        if self.state not in (DONE, CANCELLED):
            self.state = CANCELLED


class ServeEngine:
    """Greedy batched decoder over a fixed slot count (continuous batching).

    ``mode`` selects the weight-residency policy — a registered format name
    for uniform residency, or any per-layer :class:`repro.core.residency.
    ResidencySpec` form (policy dict / ``"pat=fmt,..."`` string).
    Parameters are converted ONCE at engine construction — the paper's
    amortized layout transform — and every prefill and multi-slot decode
    step thereafter runs through each layer's format.

    ``cache_format`` independently selects the decode-cache residency — a
    name registered in :data:`repro.core.kvcache.FORMATS` (``"bf16"``,
    ``"int8"``, ``"int4_bp"``, ``"int4_bp_fused"`` — the last reads the
    ring through the fused Pallas decode-attention kernel).  Cache splice
    and refill operate on the quantized storage; weight and cache
    residency compose freely — e.g. ``mode="bsdp_fused"`` (one
    single-contraction MXU call per dense tile) × ``cache_format=
    "int4_bp_fused"`` serves both dominant payloads through the fused
    bit-plane kernels.  The ``paged_*`` adapters additionally break the
    slot→storage identity: slots hold block tables into a shared
    :class:`~repro.core.paging.PagePool` (``page_pool_pages`` caps the
    physical pool; default reserves ``slots × pages_per_slot``), and a
    scheduler declaring ``wants_prefix_cache`` (``"prefix_cache"``) maps
    shared tokenized prompt prefixes onto the same physical pages
    (refcounted, COW on the first divergent append).

    ``scheduler`` selects the orchestration policy — anything
    :func:`repro.serve.scheduler.make_scheduler` accepts (a registered name
    like ``"fcfs"``/``"sjf"``/``"token_budget"``, a CLI string with kwargs
    ``"token_budget:budget=16"``, a Scheduler class or instance).  The
    default ``"fcfs"`` reproduces the legacy FIFO loop bit-exactly.
    """

    def __init__(
        self,
        params,
        cfg,
        *,
        tp: int = 1,
        slots: int = 4,
        max_len: int = 256,
        rules=None,
        impl: Optional[str] = "jnp",
        mode: residency.SpecLike = "bf16",
        cache_format: Optional[str] = None,
        scheduler: sched_lib.SchedulerLike = "fcfs",
        min_dim: int = 64,
        trace_logits: bool = False,
        trace: bool = False,
        page_pool_pages: Optional[int] = None,
        clock: Callable[[], float] = time.perf_counter,
    ):
        spec = residency.ResidencySpec.parse(mode)
        if not spec.is_trivial:
            params = convert_params(params, cfg, spec, min_dim=min_dim)
        if cache_format is not None:
            cfg = dataclasses.replace(cfg, cache_format=cache_format)
        self.params, self.cfg, self.tp = params, cfg, tp
        self.slots, self.max_len, self.rules, self.impl = slots, max_len, rules, impl
        self.spec = spec
        self.mode = spec.describe()
        self._fmt = kvcache.format_for(cfg)
        self.cache_format = self._fmt.name
        self.scheduler = sched_lib.make_scheduler(scheduler)
        self.trace_logits = trace_logits
        #: when ``trace_logits``: [(kind, slots, np.ndarray logits)] in
        #: execution order — ("prefill", (slot,), [vocab]) and
        #: ("decode", live_slots, [n_live, vocab]) entries (a chunked
        #: request's first-token logits also record as "prefill").
        self.logit_trace: list = []
        self.queue: list[Request] = []
        self.active: list[Optional[Request]] = [None] * slots
        self.requests: list[Request] = []  # every admitted request, in order
        self.caches = None
        # np.int32 to match the jnp.int32 positions at the decode boundary
        self.pos = np.zeros(slots, np.int32)
        # left-padded microbatched refill / chunked prefill need
        # position-aware layers only; SSM state would absorb pad tokens,
        # so hybrids refill one by one and never chunk
        self._pad_ok = all(
            cfg.mixer_kind(i) in ("attn", "attn_cross", "cross")
            for i in range(cfg.n_layers)
        )
        # -- paged residency: pool + block tables + radix prefix index ----
        self._paged = isinstance(self._fmt, paging.PagedCacheFormat)
        self.page_pool: Optional[paging.PagePool] = None
        self.prefix_index: Optional[paging.RadixPrefixIndex] = None
        if self._paged:
            self._page = self._fmt.page_size
            self._npp = self._fmt.pages_per_slot(max_len)
            self._ring_len = self._fmt.slot_capacity(max_len)
            pool_pages = (slots * self._npp if page_pool_pages is None
                          else int(page_pool_pages))
            self.page_pool = paging.PagePool(pool_pages, self._page)
            self.prefix_index = paging.RadixPrefixIndex(
                self._fmt.prefix_unit)
            # host mirrors of the device block tables, one row per slot
            self._tables = np.zeros((slots, self._npp), np.int64)
            self._table_valid = np.zeros(slots, bool)
            # True ⇒ the page is also held by the prefix index / another
            # slot: any write into it must copy first (COW)
            self._shared_mask = np.zeros((slots, self._npp), bool)
        # prefix sharing remaps pool rows only; it needs every per-position
        # leaf paged, which holds for pure GQA self-attention (MLA carries
        # an unpaged float k_rope; cross/SSM carry per-slot state)
        self._prefix_sharing = (
            self._paged and self._pad_ok
            and bool(getattr(self.scheduler, "wants_prefix_cache", False))
            and all(cfg.mixer_kind(i) == "attn" for i in range(cfg.n_layers))
            and not getattr(cfg, "kv_lora_rank", 0)
        )
        self._clock = clock
        self._next_uid = 0
        self._uids: set = set()
        self.step_index = 0
        self.work = 0          # processed batch positions (analytic clock)
        self.wall_s = 0.0      # seconds spent inside step()
        self._total_tokens = 0
        # -- observability (fifth registry concept) -----------------------
        # trace=True registers a per-engine RingSink: spans/counters emitted
        # anywhere in the stack during this engine's steps land in
        # ``timeline()``.  With trace=False the engine still instruments —
        # an externally registered sink (e.g. launch/serve.py --trace) sees
        # the same stream; with NO sink registered every site is the
        # zero-overhead disabled path.
        self._ring: Optional[obs.RingSink] = None
        if trace:
            self._ring = obs.register_sink(
                obs.RingSink() if trace is True else trace)
        self._weight_bytes: Optional[int] = None  # gauged lazily per step

        self._decode = jax.jit(
            lambda p, tok, caches, pos: model_lib.decode_step(
                p, tok, caches, pos, cfg, tp=tp, rules=rules, impl=impl
            )
        )

    # -- admission ------------------------------------------------------
    def submit(
        self,
        prompt,
        max_new: int = 0,
        *,
        uid: Optional[int] = None,
        force: Optional[np.ndarray] = None,
        on_token: Optional[Callable] = None,
    ) -> Request:
        """Admit one request (legacy ``submit(prompt, max_new)`` pattern, or
        pass a pre-built :class:`Request` as ``prompt``).  Auto-assigns
        ``uid`` when omitted; duplicate uids are rejected at admit time
        (a duplicate would silently corrupt slot accounting)."""
        if isinstance(prompt, Request):
            req = prompt
        else:
            req = Request(
                uid=uid, prompt=np.asarray(prompt), max_new=max_new,
                force=None if force is None else np.asarray(force),
                on_token=on_token,
            )
        if req.uid is None:
            while self._next_uid in self._uids:
                self._next_uid += 1
            req.uid = self._next_uid
        if req.uid in self._uids:
            raise ValueError(f"duplicate request uid {req.uid!r}")
        self.scheduler.admit(req, self._view())  # may raise → rejected
        self._uids.add(req.uid)
        self._next_uid = max(self._next_uid, req.uid) + 1
        req.state = QUEUED
        req.arrival = self._stamp()
        self.queue.append(req)
        self.requests.append(req)
        if obs.active():
            obs.counter("sched.admit", scheduler=self.scheduler.name)
            self._note_lifecycle("request.arrival", req, req.arrival)
        return req

    # -- bookkeeping helpers --------------------------------------------
    def _stamp(self) -> Stamp:
        return Stamp(self._clock(), self.step_index, self.work)

    def _note_lifecycle(self, name: str, req: Request, stamp: Stamp,
                        **extra) -> None:
        """Emit one request-lifecycle instant carrying the EXACT stamp the
        engine recorded — :func:`repro.obs.metrics.request_stats_from_events`
        rebuilds TTFT/TPOT from these, value-identical to the Stamp path."""
        obs.event(name, uid=req.uid, state=req.state, t=stamp.time,
                  step=stamp.step, work=stamp.work,
                  prompt_len=req.prompt_len, new_tokens=len(req.out),
                  **extra)

    def _note_scheduled(self, req: Request) -> None:
        """Stamp the moment ``req`` left the queue holding a slot.  The
        event also carries ``wait_s``, the two Stamps' difference, so the
        queue wait reads from this one event even where the arrival mark
        is no longer retained."""
        req.scheduled = self._stamp()
        if obs.active():
            self._note_lifecycle(
                "request.scheduled", req, req.scheduled,
                wait_s=req.scheduled.time - req.arrival.time)

    def timeline(self) -> list:
        """All obs records retained by this engine's ring sink (requires
        ``trace=True`` at construction): span/point records in emission
        order — feed to :func:`repro.obs.export.chrome_trace`,
        :func:`repro.obs.metrics.summarize_spans` or
        :func:`repro.obs.metrics.traced_kernels`."""
        if self._ring is None:
            raise RuntimeError(
                "timeline() requires ServeEngine(..., trace=True)")
        return self._ring.records()

    def _view(self) -> EngineView:
        return EngineView(
            slots=self.slots, active=tuple(self.active),
            queue=tuple(self.queue), chunking_ok=self._pad_ok,
            max_len=self.max_len, step_index=self.step_index,
            pages=None if self.page_pool is None else self.page_pool.stats(),
        )

    @staticmethod
    def _next_token(req: Request, logits_row: np.ndarray) -> int:
        i = len(req.out)
        if req.force is not None and i < len(req.force):
            return int(req.force[i])
        return int(np.argmax(logits_row))

    def _emit(self, req: Request, logits_row: np.ndarray) -> None:
        tok = self._next_token(req, logits_row)
        req.out.append(tok)
        self._total_tokens += 1
        if obs.active():
            obs.counter("engine.tokens")
        if req.first_token is None:
            req.first_token = self._stamp()
            if obs.active():
                self._note_lifecycle("request.first_token", req,
                                     req.first_token)
        if req.on_token is not None:
            req.on_token(req, tok)

    def _finish(self, req: Request, slot: Optional[int], state: str) -> None:
        req.state = state
        req.finished = self._stamp()
        if obs.active():
            self._note_lifecycle("request.finished", req, req.finished)
        if slot is not None:
            self.active[slot] = None
            if self._paged and self._table_valid[slot]:
                # drop this slot's references; pages pinned by the prefix
                # index (or another slot's table) stay resident
                self.page_pool.release(self._tables[slot])
                self._table_valid[slot] = False
                self._shared_mask[slot] = False
        self.scheduler.on_complete(req, self._view())

    def _sweep_terminal(self) -> None:
        """Free slots/queue entries whose requests were moved to a terminal
        state from outside the engine (``cancel()``, or a legacy writer
        setting ``done = True`` mid-flight)."""
        for req in list(self.queue):
            if req.state in (CANCELLED, DONE):
                self.queue.remove(req)
                self._finish(req, None, req.state)
        for slot in range(self.slots):
            req = self.active[slot]
            if req is not None and req.state in (CANCELLED, DONE):
                # mid-decode cancel/stop: the slot frees NOW; its ring-cache
                # row is overwritten wholesale by the next refill splice
                self._finish(req, slot, req.state)

    # -- paged residency ------------------------------------------------
    def _alloc_pages(self, n: int, keep=()) -> np.ndarray:
        """Allocate ``n`` physical pages, evicting least-recently-matched
        prefix-index leaves until the pool can satisfy the request; pages
        a live block table maps, and ``keep``, are never evicted."""
        mapped = set(self._tables[self._table_valid].ravel().tolist())
        mapped.update(int(p) for p in keep)
        while True:
            try:
                return self.page_pool.alloc(n)
            except paging.PoolExhausted:
                page = self.prefix_index.evict_lru(
                    lambda p: p not in mapped)
                if page is None:
                    raise
                self.page_pool.release([page])
                self.page_pool.note_eviction()

    def _try_attach_prefix(self, slot: int, req: Request) -> bool:
        """Map the request's leading block-table entries onto the physical
        pages of the longest registered prompt prefix (refcounted).  The
        request enters PREFILLING with ``prefilled = matched_tokens`` so a
        chunk-planning scheduler advances only the unshared suffix; at
        least one suffix token is always left so the chunk path produces
        the first-token logits.  Where the format's prefix unit is below
        its page, the prefix may end inside a page: the suffix writes into
        that page at once, so it is copied into the slot's first private
        page here instead of being mapped shared."""
        if not self._prefix_sharing or self.caches is None:
            return False
        matched = self.prefix_index.match(req.prompt)  # a page per unit
        unit = self.prefix_index.unit
        per_page = self._page // unit
        # the units of one page may name different pages (an attach that
        # copied the page it ended inside registers the copy for the units
        # after): the prefix stops at the first unit off its page's first
        first = matched[np.arange(len(matched)) // per_page * per_page]
        off = np.flatnonzero(matched != first)
        n = min(off[0] if off.size else len(matched),
                (req.prompt_len - 1) // unit, self._ring_len // unit - 1)
        if n <= 0:
            return False
        n_tok = n * unit
        k = n_tok // self._page  # whole pages mapped shared
        held = matched[:n:per_page]  # and the page the prefix ends inside
        self.page_pool.retain(held)
        try:
            private = self._alloc_pages(self._npp - k, keep=held)
        except paging.PoolExhausted:
            self.page_pool.release(held)
            raise
        shared = held[:k]
        if len(held) > k:
            self.caches = _tree_batched_named(
                self.caches, _pool_copy(held[k:], private[:1]))
            self.page_pool.release(held[k:])
            self.page_pool.note_cow()
        self._tables[slot, :k] = shared
        self._tables[slot, k:] = private
        self._table_valid[slot] = True
        self._shared_mask[slot] = False
        self._shared_mask[slot, :k] = True
        table_row = jnp.asarray(self._tables[slot], jnp.int32)
        pos_row = np.full(self._ring_len, -1, np.int32)
        pos_row[:n_tok] = np.arange(n_tok, dtype=np.int32)
        pos_row = jnp.asarray(pos_row)

        def attach(name, leaf, axis):
            if name in paging.TABLE_KEYS:
                return (leaf.at[slot].set(table_row) if axis == 0
                        else leaf.at[:, slot].set(table_row))
            if name == "pos_ids":
                return (leaf.at[slot].set(pos_row) if axis == 0
                        else leaf.at[:, slot].set(pos_row))
            return leaf

        self.caches = _tree_batched_named(self.caches, attach)
        self.active[slot] = req
        self.pos[slot] = n_tok
        req.prefilled = n_tok
        req.state = PREFILLING
        self.page_pool.note_prefix_hit(n_tok)
        return True

    def _register_prefix(self, slot: int, req: Request) -> None:
        """Register a fully-prefilled prompt's whole prefix units in the
        radix index (called at the PREFILLING → DECODING transition)."""
        if not self._prefix_sharing:
            return
        unit = self.prefix_index.unit
        n = min(req.prompt_len, self._ring_len) // unit
        if n <= 0:
            return
        pages = np.repeat(self._tables[slot], self._page // unit)[:n]
        new = self.prefix_index.insert(req.prompt[: n * unit], pages)
        if new:
            self.page_pool.retain(new)
        # any of this slot's whole prompt pages now multiply held (by the
        # index or an attach donor) must COW before the ring wraps into
        # them.  A part-filled one it goes on writing past the prompt,
        # where no other holder reads (an attach copies it): only the wrap
        # copies it here
        for j in range(min(req.prompt_len, self._ring_len) // self._page):
            if self.page_pool.refs[self._tables[slot, j]] > 1:
                self._shared_mask[slot, j] = True

    def _cow_writes(self, writes) -> None:
        """Copy-on-write: before this step's appends, give every shared
        page about to be written a private copy.  ``writes`` rows are
        ``(slot, positions)``.  A page is shared where the slot mapped it
        so (``_shared_mask``), or where a write wraps the ring into a page
        another holder maps; under ring recycling the first divergent
        append IS that wrap write."""
        if not self._paged:
            return
        ops = []
        for slot, positions in writes:
            for p in positions:
                j = (int(p) % self._ring_len) // self._page
                old = int(self._tables[slot, j])
                if not (self._shared_mask[slot, j]
                        or (p >= self._ring_len
                            and self.page_pool.refs[old] > 1)):
                    continue
                new = int(self._alloc_pages(1)[0])
                ops.append((slot, j, old, new))
                self._tables[slot, j] = new
                self._shared_mask[slot, j] = False
                self.page_pool.release([old])
                self.page_pool.note_cow()
        if not ops:
            return
        slots_a = jnp.asarray([o[0] for o in ops], jnp.int32)
        js_a = jnp.asarray([o[1] for o in ops], jnp.int32)
        new_a = jnp.asarray([o[3] for o in ops], jnp.int32)

        def retable(name, leaf, axis):
            if name in paging.TABLE_KEYS:
                return (leaf.at[slots_a, js_a].set(new_a) if axis == 0
                        else leaf.at[:, slots_a, js_a].set(new_a))
            return leaf

        self.caches = _tree_batched_named(self.caches, _pool_copy(
            [o[2] for o in ops], [o[3] for o in ops]))
        self.caches = _tree_batched_named(self.caches, retable)

    # -- execution ------------------------------------------------------
    def _prefill_slots(self, assignments: list[tuple[int, Request, int]]):
        """Microbatched refill: ONE prefill call for every queued refill.

        ``assignments`` rows are ``(slot, request, n_tokens)`` —
        ``n_tokens == len(prompt)`` for whole-prompt refills, less for a
        chunking scheduler's first chunk (the request stays PREFILLING and
        advances through the chunked-decode path on later steps).

        Prompts of different lengths are left-padded; pad tokens carry
        negative positions, which rope/masking ignore and the ring caches
        drop — so each row's cache is identical to a batch=1 prefill.  The
        per-row caches are then spliced into the slot batch (the caches are
        quantized storage throughout: splice and refill never materialize a
        float cache).
        """
        lens = [n for _, _, n in assignments]
        s_max = max(lens)
        toks = np.zeros((len(assignments), s_max), np.int32)
        pos = np.zeros((len(assignments), s_max), np.int32)
        for i, (_, req, n) in enumerate(assignments):
            pad = s_max - n
            toks[i, pad:] = req.prompt[:n]
            pos[i] = np.arange(s_max, dtype=np.int32) - pad
        batch = {"tokens": jnp.asarray(toks)}
        if s_max != min(lens):
            batch["positions"] = jnp.asarray(pos)
        logits, cache_b = model_lib.prefill(
            self.params, batch, self.cfg, tp=self.tp,
            max_len=self.max_len, rules=self.rules, impl=self.impl,
        )
        self.work += toks.size
        if self.caches is None:
            # first refill: allocate zeros at the full slot-batch shape
            # directly (no slots× temporary from a concatenate broadcast).
            # Paged pool leaves size by the PHYSICAL pool, not slots×npp —
            # the two differ when page_pool_pages caps residency below the
            # naive per-slot reservation (the prefix-sharing capacity win).
            pool_n = self.page_pool.num_pages if self._paged else 0

            def zeros(name, a, axis):
                n = pool_n if name in paging.POOL_KEYS and self._paged \
                    else self.slots
                return jnp.zeros(
                    a.shape[:axis] + (n,) + a.shape[axis + 1:], a.dtype)

            self.caches = _tree_batched_named(cache_b, zeros)
        # one scatter per leaf splices ALL refilled rows at once (row i of
        # the prefill batch → slot assignments[i][0]) — no per-slot copy
        slot_ids = jnp.array([slot for slot, _, _ in assignments], jnp.int32)
        if self._paged:
            # each refilled slot's physical pages were reserved by
            # ``_execute``; the prefill batch wrote its rows through
            # IDENTITY tables, so batch row i's pages are pool rows
            # [i·npp, (i+1)·npp) in order and the flat page-id scatter
            # below lands them on the reserved pages
            new_tables = np.stack(
                [self._tables[slot] for slot, _, _ in assignments])
            page_ids = jnp.asarray(new_tables.reshape(-1), jnp.int32)
            table_rows = jnp.asarray(new_tables, jnp.int32)

            def splice(name, full, rows, axis):
                if name in paging.POOL_KEYS:
                    return (full.at[page_ids].set(rows) if axis == 0
                            else full.at[:, page_ids].set(rows))
                if name in paging.TABLE_KEYS:
                    rows = table_rows
                return (full.at[slot_ids].set(rows) if axis == 0
                        else full.at[:, slot_ids].set(rows))

            self.caches = _tree_batched_pair_named(
                self.caches, cache_b, splice)
        else:
            self.caches = _tree_batched_pair(
                self.caches, cache_b,
                lambda full, rows, axis: (
                    full.at[slot_ids].set(rows) if axis == 0
                    else full.at[:, slot_ids].set(rows)
                ),
            )
        with obs.span("engine.readback"):
            last_logits = np.asarray(logits[:, -1])
        for i, (slot, req, n) in enumerate(assignments):
            self.active[slot] = req
            self.pos[slot] = n
            req.prefilled = n
            if n == len(req.prompt):
                req.state = DECODING
                self._register_prefix(slot, req)
                if self.trace_logits:
                    self.logit_trace.append(("prefill", (slot,), last_logits[i]))
                self._emit(req, last_logits[i])
            else:
                req.state = PREFILLING  # chunk logits are partial: discard

    def _chunk_decode(self, chunks, decode_slots):
        """One model invocation for this step's chunk rows + decode rows.

        Rows are right-aligned in a ``[slots, S]`` token block (``S`` = the
        longest chunk, 1 when no chunks): a chunk row carries its next
        prompt tokens at positions ``prefilled..prefilled+n``, a decode row
        its last output token at ``pos[slot]``, and everything else pads
        with negative positions (rope/mask-ignored, dropped from the ring
        scatter).  Rows are batch-independent through every layer, so mixed
        chunk+decode batches are numerically identical to running them
        separately.

        Returns the ``(request, slot)`` pairs that hit ``max_new`` this
        step; the caller finishes them under the ``engine.complete`` span.
        """
        s_len = max([n for _, n in chunks], default=1)
        toks = np.zeros((self.slots, s_len), np.int32)
        pos = np.full((self.slots, s_len), -1, np.int32)
        for slot, n in chunks:
            req = self.active[slot]
            a = req.prefilled
            toks[slot, s_len - n:] = req.prompt[a:a + n]
            pos[slot, s_len - n:] = np.arange(a, a + n, dtype=np.int32)
        for slot in decode_slots:
            toks[slot, -1] = self.active[slot].out[-1]
            pos[slot, -1] = self.pos[slot]
        if self._paged and obs.active():
            self._note_pages_read(pos)
        logits, self.caches = self._decode(
            self.params, jnp.asarray(toks), self.caches, jnp.asarray(pos)
        )
        self.work += toks.size
        with obs.span("engine.readback"):
            step_logits = np.asarray(logits[:, -1])
        for slot, n in chunks:
            req = self.active[slot]
            req.prefilled += n
            self.pos[slot] = req.prefilled
            if req.prefilled >= len(req.prompt):
                req.state = DECODING  # last chunk: its logits ARE the TTFT
                self._register_prefix(slot, req)
                if self.trace_logits:
                    self.logit_trace.append(("prefill", (slot,), step_logits[slot]))
                self._emit(req, step_logits[slot])
        if decode_slots and self.trace_logits:
            self.logit_trace.append(
                ("decode", tuple(decode_slots), step_logits[list(decode_slots)])
            )
        finished = []
        for slot in decode_slots:
            req = self.active[slot]
            self._emit(req, step_logits[slot])
            self.pos[slot] += 1
            if len(req.out) >= req.max_new:
                finished.append((req, slot))
        return finished

    def _note_pages_read(self, pos: np.ndarray) -> None:
        """Count what one attention layer of this decode call reads: the
        pages the cache format touches for rows at positions ``pos``
        (``attn.pages_read``) and the pages the live block tables map
        (``attn.pages_mapped``) — from host values, no device readback."""
        read = self._fmt.pages_read(pos.max(axis=1), self._npp)
        obs.counter("attn.pages_read", int(read.sum()))
        obs.counter("attn.pages_mapped",
                    int(self._table_valid.sum()) * self._npp)

    def _execute(self, plan: StepPlan) -> bool:
        """Run one validated :class:`StepPlan`; returns progress."""
        refills = []
        attached = 0
        starved = False
        with obs.span("engine.reserve", refills=len(plan.refills)):
            for slot, req, n in plan.refills:
                if self.active[slot] is not None:
                    raise ValueError(f"plan refills occupied slot {slot}")
                if req not in self.queue:
                    raise ValueError(
                        f"plan refills unqueued request {req.uid}")
                self.queue.remove(req)
                try:
                    if self._try_attach_prefix(slot, req):
                        attached += 1  # prefix mapped; chunks do the suffix
                        self._note_scheduled(req)
                        continue
                    if self._paged:
                        # reserve physical pages up front; under pool
                        # pressure the request waits (live slots free pages
                        # as they finish, and a registered prefix may let
                        # it attach)
                        self._tables[slot] = self._alloc_pages(self._npp)
                        self._table_valid[slot] = True
                        self._shared_mask[slot] = False
                except paging.PoolExhausted:
                    self.queue.insert(0, req)
                    if obs.active():
                        obs.counter("sched.requeue",
                                    scheduler=self.scheduler.name)
                    starved = True
                    break
                self._note_scheduled(req)
                refills.append((slot, req, min(n, len(req.prompt))))
        if refills:
            with obs.span("engine.prefill", slots=len(refills),
                          tokens=sum(n for _, _, n in refills)):
                if self._pad_ok:
                    self._prefill_slots(refills)
                else:  # SSM state cannot skip pad tokens: refill per slot
                    for one in refills:
                        self._prefill_slots([one])
        chunks = [
            (slot, min(n, self.active[slot].prompt_len
                       - self.active[slot].prefilled))
            for slot, n in plan.chunks
            if self.active[slot] is not None
            and self.active[slot].state == PREFILLING and n > 0
        ]
        decode_slots = tuple(
            s for s in plan.decode
            if self.active[s] is not None and self.active[s].state == DECODING
        )
        if chunks or decode_slots:
            if self._paged:
                with obs.span("engine.cow"):
                    self._cow_writes(
                        [(slot, range(self.active[slot].prefilled,
                                      self.active[slot].prefilled + n))
                         for slot, n in chunks]
                        + [(s, (self.pos[s],)) for s in decode_slots])
            with obs.span("engine.decode", chunks=len(chunks),
                          decode=len(decode_slots)):
                finished = self._chunk_decode(chunks, decode_slots)
            if finished:
                # finishes deferred out of the decode loop so slot frees,
                # page releases and scheduler.on_complete callbacks group
                # under one span (same slot order as the emit loop)
                with obs.span("engine.complete", n=len(finished)):
                    for req, slot in finished:
                        self._finish(req, slot, DONE)
        progress = bool(refills or attached or chunks or decode_slots)
        if starved and not progress:
            # nothing live to ever free a page: the pool cannot hold even
            # one request — a sizing error, not a transient
            raise paging.PoolExhausted(
                f"page pool ({self.page_pool.num_pages} pages) cannot hold "
                f"one request ({self._npp} pages/slot) and no live slot "
                "will free any")
        return progress

    def step(self) -> bool:
        """One scheduler-planned step; False when no progress was possible
        (empty queue and no live slots — or a scheduler that planned no
        work while work exists, which ``run()`` treats as termination)."""
        t0 = self._clock()
        with obs.span("engine.step", step=self.step_index):
            self._sweep_terminal()
            with obs.span("engine.plan"):
                plan = self.scheduler.plan(self._view())
            progressed = self._execute(plan)
            if obs.active():
                self._note_resident_gauges()
        self.step_index += 1
        self.wall_s += self._clock() - t0
        return progressed

    def run(self):
        while self.step():
            pass

    def _note_resident_gauges(self) -> None:
        """Gauge the live resident-byte twins.  Both values are the same
        registry-derived accounting :meth:`resident_bytes` reports, so the
        tier-1 byte-exactness test can assert the traced gauges against
        ``dryrun.analytic_cache_bytes`` / ``abstract_quant`` byte-for-byte."""
        if self._weight_bytes is None:
            self._weight_bytes = resident_bytes(self.params)
        obs.gauge("bytes.weights", self._weight_bytes)
        if self.caches is not None:
            obs.gauge("bytes.cache",
                      kvcache.cache_resident_bytes(self.caches))

    # -- SLO surface ----------------------------------------------------
    def stats(self) -> EngineStats:
        """Per-request TTFT/TPOT + aggregate tok/s (see
        :class:`repro.serve.scheduler.EngineStats`)."""
        return EngineStats(
            scheduler=self.scheduler.describe(),
            requests=tuple(
                sched_lib.request_stats(r) for r in self.requests
            ),
            total_tokens=self._total_tokens,
            wall_s=self.wall_s,
            work=self.work,
            steps=self.step_index,
            pages=None if self.page_pool is None else self.page_pool.stats(),
        )

    def resident_bytes(self) -> dict:
        """Registry-derived resident-byte breakdown: weight bytes from each
        leaf's :class:`~repro.core.residency.ResidencyFormat` and cache
        bytes from the live ring caches — the serving-side numbers the
        dry-run's ``abstract_quant`` / ``eval_shape(init_cache)`` twins
        must (and are tested to) reproduce exactly."""
        weights = resident_bytes(self.params)
        cache = 0 if self.caches is None else kvcache.cache_resident_bytes(
            self.caches)
        return {"weights": weights, "cache": cache,
                "total": weights + cache}


def _pool_copy(old, new):
    """:func:`_tree_batched_named` function copying page-pool rows ``old``
    to rows ``new`` in every layer."""
    old, new = (jnp.asarray(np.asarray(a), jnp.int32) for a in (old, new))

    def fn(name, leaf, axis):
        if name not in paging.POOL_KEYS:
            return leaf
        return (leaf.at[new].set(leaf[old]) if axis == 0
                else leaf.at[:, new].set(leaf[:, old]))
    return fn


def _tree_batched(caches, fn):
    """Map ``fn(leaf, batch_axis)`` over a decode-cache tree: prefix-layer
    leaves carry batch at axis 0, scanned-stack leaves at axis 1."""
    return {
        "prefix": jax.tree_util.tree_map(lambda a: fn(a, 0), caches["prefix"]),
        "stack": jax.tree_util.tree_map(lambda a: fn(a, 1), caches["stack"]),
    }


def _tree_batched_pair(full, part, fn):
    """Two-tree variant of :func:`_tree_batched`."""
    return {
        "prefix": jax.tree_util.tree_map(
            lambda f, o: fn(f, o, 0), full["prefix"], part["prefix"]),
        "stack": jax.tree_util.tree_map(
            lambda f, o: fn(f, o, 1), full["stack"], part["stack"]),
    }


def _leaf_name(path) -> Optional[str]:
    """Last string dict key on a tree path — the cache leaf's flat name
    (``"k"``, ``"k_pages"``, ``"pos_ids"``, …), which is what decides
    whether a leaf lives in the page pool, a block table, or a slot row."""
    name = None
    for p in path:
        key = getattr(p, "key", None)
        if isinstance(key, str):
            name = key
    return name


def _tree_batched_named(caches, fn):
    """Name-aware :func:`_tree_batched`: ``fn(leaf_name, leaf, axis)``."""
    return {
        "prefix": jax.tree_util.tree_map_with_path(
            lambda path, a: fn(_leaf_name(path), a, 0), caches["prefix"]),
        "stack": jax.tree_util.tree_map_with_path(
            lambda path, a: fn(_leaf_name(path), a, 1), caches["stack"]),
    }


def _tree_batched_pair_named(full, part, fn):
    """Name-aware :func:`_tree_batched_pair`."""
    return {
        "prefix": jax.tree_util.tree_map_with_path(
            lambda path, f, o: fn(_leaf_name(path), f, o, 0),
            full["prefix"], part["prefix"]),
        "stack": jax.tree_util.tree_map_with_path(
            lambda path, f, o: fn(_leaf_name(path), f, o, 1),
            full["stack"], part["stack"]),
    }
