"""Batched serving engine: paged-KV continuous batching (default) with the
legacy fixed-slot engine kept as the comparison baseline.

**Paged mode** (``mode="paged"``, the default for attention-KV families):
KV state lives in a shared page pool (:mod:`repro.serve.pages`) addressed
through per-request block tables; a scheduler
(:mod:`repro.serve.scheduler`) admits requests by page capacity, prefills
prompts in batched chunks through ``prefill_chunk`` (one forward per chunk
across all pending lanes), and preempts the longest-running request when
pages run out.  Each jitted dispatch also samples every lane's next id
(:mod:`repro.serve.sampler`), so the host fetches ``(B,)`` ids, not logits.  Decode throughput then scales with pool capacity — the
serving analogue of the paper's GEMV-per-memory-capacity argument.

**Fixed-slot mode** (``mode="slots"``; also the fallback for ssm/hybrid
families, whose O(1) recurrent state has nothing to page): the original
engine — a fixed ``(n_slots, max_len)`` cache rectangle, per-token prompt
prefill, one fused ``decode_step`` per token across active slots.

Both modes run every linear through the same resolved
:class:`~repro.engine.EnginePlan`; with ``EngineConfig.kv_bits = 8`` the
paged pools are int8 bit-planed exactly as ``weight_bits`` bit-planes the
stationary weights — cache traffic drops to 1 byte/element through the
same dispatch layer.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import logging
from typing import Deque, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs
from repro.config.base import EngineConfig, ModelConfig, ServeConfig
from repro.dist.hints import use_mesh
from repro.dist.sharding import (
    batch_shardings,
    cache_shardings,
    param_shardings,
    pool_pages_for_mesh,
)
from repro.engine import resolve_attn_backend, resolve_plan
from repro.ft.failures import RestartPolicy
from repro.models import (
    decode_step,
    decode_step_paged,
    init_cache,
    quantize_params,
)
from repro.models import prefill_chunk as _prefill_chunk_fn
from repro.serve.pages import (
    NULL_PAGE,
    PAGED_FAMILIES,
    AuditError,
    KVPages,
    PageAllocator,
    fork_tail_page,
    init_kv_pages,
    page_arrays,
    pages_for,
)
from repro.serve.prefix_cache import PrefixCache
from repro.serve.sampler import sample, sample_rows
from repro.serve.scheduler import (
    PRIORITY_WEIGHTS,
    BudgetScheduler,
    PagedScheduler,
)

logger = logging.getLogger(__name__)


class AdmissionRejected(RuntimeError):
    """Load shedding: ``submit`` refused the request outright.

    ``reason``: ``"queue_full"`` (bounded admission queue at capacity) or
    ``"pool_too_small"`` (the prompt can never fit the page pool — waiting
    would deadlock behind eviction+preemption).  Rejecting at the door
    keeps the admitted requests' latency bounded under overload; the
    caller (or :class:`repro.serve.frontend.ServeFrontend`) decides
    whether to retry, degrade, or surface the error.
    """

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


# eq=False: a Request is an identity (queue membership, slot residency and
# cancellation all compare by ``is``); field-wise dataclass equality would
# even crash comparing the ndarray ``last_logits``
@dataclasses.dataclass(eq=False)
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # host copy of the lane's newest logits: set only where the host
    # samples (a ``_sample_next`` override, slots mode); the paged engine
    # samples inside its jitted step and never copies them
    last_logits: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False)
    # paged: the newest output id, whose K/V the next decode step writes
    # (None until a dispatch has sampled one — "ready to decode" — and
    # again after a preemption or fault sends the request back to prefill)
    pending: Optional[int] = None
    # --- paged-scheduler state --------------------------------------
    prefill_tokens: List[int] = dataclasses.field(
        default_factory=list, repr=False)
    prefill_pos: int = 0
    admit_seq: int = -1
    preemptions: int = 0
    # prefill tokens served from the prefix cache at (re-)admission
    cached_tokens: int = 0
    # clock reading at ``submit()`` — the anchor for per-request latency
    submit_t: float = 0.0
    # time-to-first-token measured from ``submit_t`` (per request; the
    # old run()-relative measurement overstated TTFT for every request
    # submitted after the engine started stepping)
    ttft: Optional[float] = None
    # --- SLA / front-end state --------------------------------------
    priority: str = "default"         # interactive | default | batch
    tenant: str = "default"           # fair-share accounting key part
    cancelled: bool = False           # terminal, but not successfully done
    # "length" | "cancelled" | "timed_out" | "error" (None while running)
    finish_reason: Optional[str] = None
    # recompute-style retries after step faults / non-finite logits
    retries: int = 0

    # deprecated alias (pre-paged code set this attribute dynamically)
    @property
    def _last_logits(self):
        return self.last_logits

    @_last_logits.setter
    def _last_logits(self, value):
        self.last_logits = value


class Sampled(NamedTuple):
    """What a jitted paged dispatch returns beside the page pool: each
    lane's next id and whether its logits were all finite (the hot
    path's only host fetch), the logits themselves (copied only for a
    ``_sample_next`` override) and the advanced sampling key."""
    ids: jax.Array       # (B,) int32
    finite: jax.Array    # (B,) bool
    logits: jax.Array    # (B, 1, V) or (B, 1, K, V)
    key: jax.Array


class ServeEngine:
    """Continuous-batching serving over a paged or fixed-slot KV cache.

    ``mode``: ``"paged"`` | ``"slots"`` | ``"auto"`` (paged for attention
    families, slots for ssm/hybrid).  ``page_size`` / ``n_pages`` /
    ``prefill_chunk`` configure the paged pool (``n_pages=0`` sizes the
    pool to the full ``n_slots × max_len`` rectangle — no preemption;
    smaller pools trade preemptions for memory, admission is always
    capacity-checked).

    ``attn_backend``: paged-attention read path for decode *and* chunked
    prefill — ``gather`` (the materialize-then-attend reference) or the
    fused in-place Pallas kernel (``pallas_interpret`` / ``pallas_tpu``,
    which also runs the in-kernel prefill grid).  None defers to the
    resolved plan (``EngineConfig.attn_backend``), whose ``"auto"`` picks
    the kernel on TPU — including mesh-carrying engines, where it
    shard_maps over the pool's heads-over-model placement — and
    ``gather`` elsewhere.

    ``prefix_cache``: share KV pages across requests
    (:mod:`repro.serve.prefix_cache`) — prompts are matched against a
    radix tree of resident pages at admission and only the unmatched
    suffix is prefilled; completed prefills are inserted back into the
    tree.  A bool (``None`` defers to ``ServeConfig.prefix_cache``); the
    engine owns its :class:`PrefixCache` — the tree indexes this engine's
    pool, so foreign instances are rejected.  Paged mode only.  Cache
    state (tree, refcounts) is host-side, exactly like block tables — it
    does not change what any jitted step sees.

    ``mesh``: run on a production ``(data, model)`` mesh — params are
    placed by ``dist.sharding.param_shardings`` (TP), the KV page pool by
    ``cache_shardings`` (pages over ``data``, heads over ``model``; the
    pool is padded so the page axis divides), and the plan is resolved
    with the mesh so ``EngineConfig.sharded`` backends shard_map their
    GEMVs.  The allocator, block tables, scheduler and prefix cache stay
    host-side exactly as on one device.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        scfg: Optional[ServeConfig] = None,
        *,
        n_slots: int = 4,
        max_len: int = 256,
        seed: int = 0,
        mode: Optional[str] = None,
        page_size: Optional[int] = None,
        n_pages: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        prefix_cache=None,
        mesh=None,
        attn_backend: Optional[str] = None,
        clock=None,
        telemetry=None,
        chaos=None,
    ):
        self.cfg = cfg
        self.scfg = scfg or ServeConfig()
        self.mesh = mesh
        # ``chaos``: optional ft.ChaosInjector — deterministic fault
        # injection at the engine's hook sites (page grants, step faults,
        # NaN logits, preemption storms; see docs/robustness.md).  None
        # (production) costs one attribute check per site.
        self.chaos = chaos
        # ``clock``: injectable timebase for every engine timestamp
        # (``submit_t``, TTFT, telemetry spans) — defaults to the serve
        # clock (repro.obs.clock).  ``telemetry``: an explicit Telemetry /
        # NullTelemetry; None defers to the process-wide repro.obs switch.
        self._clock = clock if clock is not None else obs.clock.now
        self.obs = (telemetry if telemetry is not None
                    else obs.telemetry(clock))
        if chaos is not None:
            # every fired fault self-reports through the engine's
            # telemetry (ChaosInjector.fire) — including sites the engine
            # never sees directly, like the allocator's page_grant
            chaos.obs = self.obs
        # the EngineConfig is resolved into an EnginePlan exactly once, at
        # construction; the plan is the only engine object the decode loop
        # ever sees.  The mesh rides in the plan, so the sharded backend
        # needs no further plumbing.
        self.plan = resolve_plan(self.scfg.engine, mesh=mesh)
        self.eng = self.plan  # back-compat alias
        if self.plan is not None and self.plan.bits:
            # one compiled program: eager packing would hold an f32 copy
            # of each whole stacked weight at once (GBs at real widths);
            # already-packed params pass through unchanged
            params = jax.jit(quantize_params, static_argnums=(1, 2))(
                params, cfg, self.plan.bits)
        if mesh is not None:
            params = jax.device_put(params, param_shardings(mesh, params))
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.key = jax.random.PRNGKey(seed)
        self.kv_bits = self.plan.kv_bits if self.plan is not None else 0
        # the paged-attention read path (gather reference vs the fused
        # in-place kernel, decode and chunked prefill alike): explicit
        # kwarg beats the plan beats the raw EngineConfig (which still
        # carries attn_backend when the engine itself is disabled and the
        # plan resolves to None).  "auto" resolves by host (TPU → fused)
        # with or without a mesh — on a mesh the kernel shard_maps over
        # the pool's heads-over-model placement.
        self.attn_backend = resolve_attn_backend(
            attn_backend
            or (self.plan.attn_backend if self.plan is not None
                else getattr(self.scfg.engine, "attn_backend", None)),
            mesh=mesh)

        mode = mode or self.scfg.mode
        auto_fallback = False
        if mode == "auto":
            if cfg.family in PAGED_FAMILIES:
                mode = "paged"
            else:
                auto_fallback = True
                # the silent fallback hid a capability gap (ROADMAP open
                # item: zamba2's shared-attention sites do have a real KV
                # cache) — name the family so operators see which models
                # run the legacy fixed-slot engine
                logger.warning(
                    "ServeEngine: family %r has no pageable KV cache; "
                    "falling back to mode='slots' (fixed-slot engine, "
                    "no paging, no prefix cache)", cfg.family)
                mode = "slots"
        if mode == "paged" and cfg.family not in PAGED_FAMILIES:
            raise ValueError(
                f"family {cfg.family!r} has no pageable KV cache; "
                "use mode='slots'")
        if mode not in ("paged", "slots"):
            raise ValueError(f"unknown serve mode {mode!r}")
        self.mode = mode

        if prefix_cache is None:
            prefix_cache = self.scfg.prefix_cache
        if not isinstance(prefix_cache, bool):
            # an instance would index a *different* pool's pages — and an
            # empty one would even be falsy; refuse rather than surprise
            raise TypeError(
                f"prefix_cache must be a bool (got "
                f"{type(prefix_cache).__name__}); the engine builds and "
                "owns the PrefixCache over its own page pool")
        if prefix_cache and mode != "paged":
            if auto_fallback:
                # the fallback warning above already names the family;
                # a generic prefix-cache config must not explode on it
                prefix_cache = False
            else:
                raise ValueError(
                    "prefix_cache shares KV *pages* across requests; "
                    "mode='slots' has no page pool to share")
        if self.scfg.sched == "budget" and mode != "paged":
            if not auto_fallback:
                raise ValueError(
                    "sched='budget' interleaves chunked prefill with "
                    "decode under a token budget; mode='slots' prefills "
                    "synchronously and has no scheduler to budget")
            logger.warning(
                "ServeEngine: sched='budget' ignored in mode='slots' "
                "(fixed-slot fallback runs FCFS)")
        if self.scfg.audit and mode != "paged":
            # the invariants audited (refcounts, free list, radix tree)
            # are paged-pool state; slots mode has none of it
            if not auto_fallback:
                raise ValueError(
                    "audit proves page-pool invariants; mode='slots' has "
                    "no page pool to audit")
            logger.warning(
                "ServeEngine: audit ignored in mode='slots' "
                "(no page pool)")

        self.queue: Deque[Request] = collections.deque()
        self._next_rid = 0
        self.shed_count = 0  # AdmissionRejected raises since construction
        self.quarantined = 0  # requests finished with finish_reason="error"
        self.retried = 0  # recompute-style retries granted across requests
        self._engine_step = 0
        # per-request restart budgets (rid -> RestartPolicy), created on
        # first fault, dropped at terminal states
        self._retry: Dict[int, RestartPolicy] = {}
        self._errored_step: List[Request] = []
        self.obs.attach_engine(n_slots, mode)

        cfg_ = self.cfg
        plan_ = self.plan

        if mode == "paged":
            self.page_size = page_size or self.scfg.page_size
            self.prefill_chunk = prefill_chunk or self.scfg.prefill_chunk
            max_blocks = pages_for(max_len, self.page_size)
            self._max_blocks = max_blocks
            if n_pages is None:
                n_pages = self.scfg.n_pages
            if not n_pages:  # full rectangle + null page: never preempts
                n_pages = n_slots * max_blocks + 1
            # pages-over-data needs a divisible page axis; padding only
            # grows spare capacity (the allocator sees more free pages)
            n_pages = pool_pages_for_mesh(n_pages, mesh)
            self.pages = init_kv_pages(cfg, n_pages, self.page_size,
                                       kv_bits=self.kv_bits)
            if mesh is not None:
                self.pages = jax.device_put(
                    self.pages, cache_shardings(mesh, self.pages))
            self.alloc = PageAllocator(n_pages, self.page_size, n_slots,
                                       max_len, obs=self.obs)
            self.alloc.chaos = chaos  # page_grant fault site
            # the prefix cache attaches to the allocator (resident-page
            # ownership + LRU eviction when the free list runs dry)
            self.prefix_cache = None
            if prefix_cache:
                self.prefix_cache = PrefixCache(self.alloc, obs=self.obs)
                self.alloc.attach_cache(self.prefix_cache)
            if self.scfg.sched == "budget":
                # default budget: every lane decodes plus one full prefill
                # chunk per step — decode-first with steady prefill progress
                step_tokens = (self.scfg.step_tokens
                               or n_slots + self.prefill_chunk)
                self.sched = BudgetScheduler(
                    self.alloc, self.prefill_chunk,
                    prefix_cache=self.prefix_cache,
                    step_tokens=step_tokens, obs=self.obs)
            else:
                self.sched = PagedScheduler(
                    self.alloc, self.prefill_chunk,
                    prefix_cache=self.prefix_cache, obs=self.obs)
            # lane-state shardings are computed once: block tables and
            # positions always enter the device under their mesh placement
            self._table_shardings = None
            if mesh is not None:
                bt0, pos0 = self.alloc.device_tables()
                sh = batch_shardings(mesh, {"bt": bt0, "pos": pos0})
                self._table_shardings = (sh["bt"], sh["pos"])

            # the page pool is donated: each step scatters into it and the
            # old value is dropped, so XLA may update the buffers in place
            # instead of copying the whole pool per token/chunk.  The mesh
            # rides in explicitly (the plan may be None on a meshed
            # engine) so the fused kernel can shard_map over it.
            # Each dispatch also samples every lane's next id from the
            # logits it just computed (``temperature`` / ``top_k`` are
            # static: the trace adapts to them), so the host fetches
            # ``(B,)`` ids and finite flags instead of ``(B, V)`` logits.
            abk_ = self.attn_backend
            mesh_ = mesh
            temp_, top_k_ = self.scfg.temperature, self.scfg.top_k

            def _sampled(logits, key):
                key, sub = jax.random.split(key)
                ids, finite = sample_rows(logits, sub, temp_, top_k_)
                return Sampled(ids, finite, logits, key)

            @functools.partial(jax.jit, donate_argnums=(1,))
            def _dec(params, pages, bt, pos, active, tokens, key):
                logits, pages = decode_step_paged(
                    params, pages, bt, pos, active, tokens, cfg_, plan_,
                    attn_backend=abk_, mesh=mesh_)
                return _sampled(logits, key), pages

            @functools.partial(jax.jit, donate_argnums=(1,))
            def _pf(params, pages, bt, tokens, pos0, seq_lens, key):
                logits, pages = _prefill_chunk_fn(
                    params, pages, bt, tokens, pos0, seq_lens, cfg_, plan_,
                    attn_backend=abk_, mesh=mesh_)
                return _sampled(logits, key), pages

            self._decode_paged = _dec
            self._prefill_paged = _pf
            # analytic cost tables (repro.obs.costs): the jitted decode /
            # prefill shapes are fixed at construction, so one memoized
            # table per dispatch kind prices every step.  Built lazily on
            # the first charged step — with obs disabled they never exist.
            self._cost_dims = None
            self._cost_specs = None
            self._decode_cost_table = None
            self._prefill_cost_table = None
            self._fork_cost_table = None
        else:
            self.prefix_cache = None
            if self.kv_bits:
                raise ValueError(
                    "kv_bits is wired through the paged engine "
                    "(int8 KV pages); mode='slots' serves the "
                    "full-precision cache only")
            self.cache = init_cache(cfg, n_slots, max_len)
            if mesh is not None:
                self.cache = jax.device_put(
                    self.cache, cache_shardings(mesh, self.cache))
            self.slot_req: List[Optional[Request]] = [None] * n_slots

            @jax.jit
            def _step(params, cache, tokens):
                return decode_step(params, cache, tokens, cfg_, plan_)

            self._step = _step

    # ------------------------------------------------------------------ API
    def submit(self, prompt: List[int], max_new_tokens: Optional[int] = None,
               *, priority: str = "default", tenant: str = "default"
               ) -> Request:
        """Enqueue a prompt; returns its :class:`Request` handle.

        Raises ``ValueError`` for malformed prompts (caller bugs) and
        :class:`AdmissionRejected` for load shedding (the bounded queue
        is full, or the prompt can never fit the page pool) — transient,
        retriable conditions a front-end turns into ``shed`` streams.
        """
        prompt = list(prompt)
        if not prompt:
            # an empty prompt leaves nothing to condition on (the old
            # engine crashed with an unbound ``logits`` here): reject at
            # the door — callers that want generation-from-nothing should
            # submit an explicit BOS token.
            raise ValueError(
                "empty prompt: submit at least one token (e.g. BOS)")
        if min(prompt) < 0 or max(prompt) >= self.cfg.vocab_size:
            # out-of-vocab ids embed to an all-zero one-hot, whose norm
            # divides by ~0 and decodes to non-finite logits — which the
            # fault isolation would then quarantine after burning its
            # retry budget.  Invalid input is a caller bug: reject it at
            # the door instead of diagnosing it as a device fault.
            bad = next(t for t in prompt
                       if t < 0 or t >= self.cfg.vocab_size)
            raise ValueError(
                f"prompt token {bad} outside the model vocabulary "
                f"[0, {self.cfg.vocab_size})")
        if len(prompt) > self.max_len - 2:
            raise ValueError(
                f"prompt of {len(prompt)} tokens cannot fit max_len="
                f"{self.max_len} with room to generate (limit is "
                f"max_len - 2 = {self.max_len - 2})")
        if priority not in PRIORITY_WEIGHTS:
            raise ValueError(
                f"unknown priority {priority!r}; choose from "
                f"{sorted(PRIORITY_WEIGHTS)}")
        queue = self.sched.queue if self.mode == "paged" else self.queue
        if self.scfg.max_queue and len(queue) >= self.scfg.max_queue:
            self.shed_count += 1
            self.obs.on_shed("queue_full")
            raise AdmissionRejected("queue_full")
        if (self.mode == "paged"
                and pages_for(len(prompt) + 1, self.page_size)
                > self.alloc.n_pages - 1):
            # unreachable via the max_len check for sane pool sizes, but
            # a request that can never be granted must not sit in the
            # queue deadlocking everything behind eviction+preemption
            self.shed_count += 1
            self.obs.on_shed("pool_too_small")
            raise AdmissionRejected("pool_too_small")
        req = Request(self._next_rid, prompt,
                      self.scfg.max_new_tokens if max_new_tokens is None
                      else max_new_tokens,
                      priority=priority, tenant=tenant)
        req.prefill_tokens = list(prompt)
        req.submit_t = self._clock()
        self._next_rid += 1
        queue.append(req)
        self.obs.on_submit(req.rid, len(prompt), req.submit_t)
        return req

    def has_work(self) -> bool:
        """Anything queued or resident?"""
        if self.mode == "paged":
            return self.sched.has_work()
        return bool(self.queue) or any(
            r is not None for r in self.slot_req)

    def step(self) -> List[Request]:
        """One scheduler iteration (admit → prefill chunk → decode token →
        retire); returns the requests that finished this step.  The unit
        the streaming front-end drives — ``run()`` is just this in a
        loop."""
        with self._mesh_ctx():
            return self._step_framed()

    def _step_framed(self) -> List[Request]:
        """One step with its telemetry framing (B/E span on the engine
        track, step counter + duration histogram).  Caller holds the
        mesh context."""
        t0 = self.obs.now()
        self.obs.step_begin()
        try:
            if self.mode == "paged":
                return self._step_paged()
            return self._step_slots()
        finally:
            self.obs.step_end(t0)

    def run(self) -> List[Request]:
        """Drive until queue + slots drain; returns completed requests."""
        # the mesh context makes the model-internal sharding hints live
        # (they are no-ops off-mesh); device placement itself was pinned at
        # construction via param/cache shardings.
        finished: List[Request] = []
        with self._mesh_ctx():
            while self.has_work():
                finished.extend(self._step_framed())
        return finished

    def cancel(self, req: Request, reason: str = "cancelled") -> bool:
        """Terminate a request *now*, wherever it is in its lifecycle.

        Queued: dropped from the queue.  Resident (mid-prefill or
        decoding): its pages are released immediately — including
        prefix-cache pins taken at admission and any partially-filled
        private pages of a chunked prefill — and pending copy-on-write
        forks are discarded before their dst page can be reused.  Tokens
        generated so far stay on ``req.output``.  Returns False if the
        request already reached a terminal state."""
        if req.done or req.cancelled:
            return False
        req.cancelled = True
        req.finish_reason = reason
        self._retry.pop(req.rid, None)
        self.obs.on_cancel(req.rid, reason)
        if self.mode == "paged":
            for slot, r in enumerate(self.sched.slot_req):
                if r is req:
                    self.sched.drop_forks(slot)
                    self.alloc.free_slot(slot)
                    self.sched.slot_req[slot] = None
                    return True
            try:
                self.sched.queue.remove(req)
            except ValueError:
                pass  # between retire bookkeeping and caller: already out
            return True
        for slot, r in enumerate(self.slot_req):
            if r is req:
                self.slot_req[slot] = None
                return True
        try:
            self.queue.remove(req)
        except ValueError:
            pass
        return True

    def request_phase(self, req: Request) -> str:
        """Lifecycle phase: ``queued`` | ``prefilling`` | ``decoding`` |
        ``done`` | ``cancelled`` (the front-end refines ``cancelled``
        into cancelled/timed-out via ``finish_reason``)."""
        if req.done:
            return "done"
        if req.cancelled:
            return "cancelled"
        slots = self.sched.slot_req if self.mode == "paged" else self.slot_req
        for r in slots:
            if r is req:
                ready = (req.pending if self.mode == "paged"
                         else req.last_logits)
                if (ready is None
                        or req.prefill_pos < len(req.prefill_tokens)):
                    return "prefilling"
                return "decoding"
        return "queued"

    def _mesh_ctx(self):
        if self.mesh is None:
            return contextlib.nullcontext()
        return use_mesh(self.mesh)

    @property
    def preemptions(self) -> int:
        return self.sched.preemptions if self.mode == "paged" else 0

    @property
    def prefill_computed(self) -> int:
        """Prompt tokens actually run through ``prefill_chunk`` (cache
        hits keep this below the total submitted prompt tokens)."""
        return self.sched.prefill_computed if self.mode == "paged" else 0

    def prefix_stats(self) -> Optional[Dict[str, int]]:
        """Prefix-cache counters (thin shim over :meth:`metrics`)."""
        return (self.prefix_cache.stats()
                if self.prefix_cache is not None else None)

    def metrics(self) -> Dict:
        """Unified engine snapshot: lifecycle counters, prefix-cache
        stats when a cache is attached, and — with ``repro.obs`` enabled
        — the full telemetry snapshot (registry + request states) under
        ``"obs"``.  Subsumes ``prefix_stats()`` / ``prefill_computed``
        (both kept as thin shims)."""
        out: Dict = {
            "mode": self.mode,
            "submitted": self._next_rid,
            "shed": self.shed_count,
            "preemptions": self.preemptions,
            "quarantined": self.quarantined,
            "prefill_computed": self.prefill_computed,
        }
        if self.prefix_cache is not None:
            out["prefix"] = self.prefix_cache.stats()
        out["ft"] = {
            "quarantined": self.quarantined,
            "retried": self.retried,
            "chaos": (self.chaos.summary()
                      if self.chaos is not None else {}),
        }
        if self.obs.enabled:
            out["obs"] = self.obs.snapshot()
            out["costs"] = (self.obs.costs.snapshot()
                            if self.obs.costs is not None else {})
        return out

    # ================================================= cost attribution
    def _cost_base(self):
        """Model dims + live linear specs for the ledger tables (the
        specs walk the *actual* param tree, so packed weights price at
        ``bits/8`` bytes per element)."""
        if self._cost_specs is None:
            self._cost_dims = obs.model_dims(self.cfg)
            self._cost_specs = obs.linear_specs(self.params)
        return self._cost_dims, self._cost_specs

    def _charge_decode(self, rids) -> None:
        """Charge one paged decode step to the cost ledger.  The jitted
        step always runs the full ``(n_slots, max_blocks·page_size)``
        shapes regardless of how many lanes are active, so one memoized
        table is exact for every step; attribution splits the step total
        across the lanes that actually decoded."""
        if not self.obs.enabled:
            return
        if self._decode_cost_table is None:
            dims, specs = self._cost_base()
            self._decode_cost_table = obs.decode_step_costs(
                dims, batch=self.n_slots,
                context=self._max_blocks * self.page_size,
                page_size=self.page_size,
                attn_backend=self.attn_backend,
                kv_bits=self.kv_bits, specs=specs)
        self.obs.on_costs(self._decode_cost_table, rids)

    def _charge_prefill(self, rids) -> None:
        """Charge one chunked-prefill dispatch (``(n_slots, chunk)``,
        padded — see :meth:`_charge_decode` for why one table is exact)."""
        if not self.obs.enabled:
            return
        if self._prefill_cost_table is None:
            dims, specs = self._cost_base()
            self._prefill_cost_table = obs.prefill_chunk_costs(
                dims, batch=self.n_slots, chunk=self.prefill_chunk,
                context=self._max_blocks * self.page_size,
                page_size=self.page_size,
                attn_backend=self.attn_backend,
                kv_bits=self.kv_bits, specs=specs)
        self.obs.on_costs(self._prefill_cost_table, rids)

    def _charge_fork(self, rid: int) -> None:
        """Charge one prefix-cache COW tail-page fork (pure page copies)."""
        if not self.obs.enabled:
            return
        if self._fork_cost_table is None:
            dims, _ = self._cost_base()
            self._fork_cost_table = obs.costs.fork_cost(
                dims, page_size=self.page_size, kv_bits=self.kv_bits)
        self.obs.on_costs(self._fork_cost_table, (rid,))

    # ==================================================== invariant audit
    def audit(self) -> None:
        """Prove the engine's host-side bookkeeping invariants; raises
        :class:`~repro.serve.pages.AuditError` naming the first
        violation.  Covers the allocator (refcount conservation, free
        list, block tables), the prefix-cache radix tree, and the
        scheduler (residency/queue consistency, pending forks).  Runs
        automatically per step/phase under ``ServeConfig(audit=...)``;
        callable directly from drills and tests.  Paged mode only."""
        if self.mode != "paged":
            raise ValueError("audit() proves page-pool invariants; "
                             "mode='slots' has no page pool")
        self.alloc.audit()
        if self.prefix_cache is not None:
            self.prefix_cache.audit()
        self._audit_sched()

    def _audit_sched(self) -> None:
        """Scheduler-level invariants: a live request sits in exactly one
        place (one lane, or the queue, never both/twice), no terminal
        request holds a lane, and every pending COW fork's target is a
        page its owner lane actually maps."""
        def fail(msg: str) -> None:
            raise AuditError(f"ServeEngine.audit: {msg}")

        resident: Dict[int, int] = {}
        for slot, req in enumerate(self.sched.slot_req):
            if req is None:
                if self.alloc._mapped[slot]:
                    fail(f"empty lane {slot} still maps "
                         f"{len(self.alloc._mapped[slot])} pages")
                continue
            if id(req) in resident:
                fail(f"rid {req.rid} resident in two lanes")
            resident[id(req)] = slot
            if req.done or req.cancelled:
                fail(f"terminal rid {req.rid} still resident in "
                     f"lane {slot}")
        seen_q = set()
        for req in self.sched.queue:
            if id(req) in resident:
                fail(f"rid {req.rid} both queued and resident")
            if id(req) in seen_q:
                fail(f"rid {req.rid} queued twice")
            seen_q.add(id(req))
            if req.done or req.cancelled:
                fail(f"terminal rid {req.rid} still queued")
        for slot, _src, dst in self.sched.pending_forks:
            req = self.sched.slot_req[slot]
            if req is None:
                fail(f"pending fork owned by empty lane {slot}")
            if dst not in self.alloc._mapped[slot]:
                fail(f"pending fork dst page {dst} not mapped by its "
                     f"owner lane {slot}")

    # ================================================= snapshot / restore
    def snapshot(self) -> Dict:
        """Crash-consistent snapshot of all serving state (paged mode).

        Returns ``{"arrays": {name: np.ndarray}, "host": <JSON-able>}``
        covering the device page pool, the sampling key, allocator
        tables, the prefix-cache radix tree, scheduler queues (including
        fair-share virtual time and pending COW forks), and every
        in-flight request — everything :meth:`restore` needs to resume
        token-identically.  Arrays are materialized to host numpy at
        snapshot time, so later (donating) engine steps cannot mutate a
        taken snapshot.  Terminal requests are the caller's state, not
        the engine's, and are not captured; telemetry state restarts
        fresh.  Persist with :meth:`save_snapshot`.
        """
        if self.mode != "paged":
            raise ValueError("snapshot() covers the paged engine only")
        arrays: Dict[str, np.ndarray] = {
            "pages/k": np.asarray(self.pages.k),
            "pages/v": np.asarray(self.pages.v),
            "key": np.asarray(self.key),
        }
        if self.pages.quantized:
            arrays["pages/k_scale"] = np.asarray(self.pages.k_scale)
            arrays["pages/v_scale"] = np.asarray(self.pages.v_scale)

        live: List[Request] = [r for r in self.sched.slot_req
                               if r is not None]
        live += [r for r in self.sched.queue if r not in live]
        reqs = []
        for r in live:
            if r.last_logits is not None:
                arrays[f"logits/{r.rid}"] = np.asarray(r.last_logits)
            reqs.append({
                "rid": r.rid,
                "prompt": [int(t) for t in r.prompt],
                "max_new_tokens": r.max_new_tokens,
                "output": [int(t) for t in r.output],
                "pending": r.pending,
                "prefill_tokens": [int(t) for t in r.prefill_tokens],
                "prefill_pos": r.prefill_pos,
                "admit_seq": r.admit_seq,
                "preemptions": r.preemptions,
                "cached_tokens": r.cached_tokens,
                "submit_t": r.submit_t,
                "ttft": r.ttft,
                "priority": r.priority,
                "tenant": r.tenant,
                "retries": r.retries,
            })

        sched: Dict = {
            "queue": [r.rid for r in self.sched.queue],
            "slots": [r.rid if r is not None else None
                      for r in self.sched.slot_req],
            "admit_seq": self.sched._admit_seq,
            "preemptions": self.sched.preemptions,
            "prefill_computed": self.sched.prefill_computed,
            "pending_forks": [list(f) for f in self.sched.pending_forks],
        }
        if isinstance(self.sched, BudgetScheduler):
            sched["vtime"] = [[t, p, vt] for (t, p), vt
                              in self.sched._vtime.items()]

        host: Dict = {
            "geometry": {
                "family": self.cfg.family,
                "n_slots": self.n_slots,
                "max_len": self.max_len,
                "page_size": self.page_size,
                "n_pages": self.alloc.n_pages,
                "prefill_chunk": self.prefill_chunk,
                "kv_bits": self.kv_bits,
                "sched": type(self.sched).__name__,
                "prefix_cache": self.prefix_cache is not None,
            },
            "engine": {
                "next_rid": self._next_rid,
                "shed_count": self.shed_count,
                "quarantined": self.quarantined,
                "retried": self.retried,
                "engine_step": self._engine_step,
            },
            "alloc": {
                "free": [int(p) for p in self.alloc.free],
                "pos": [int(x) for x in self.alloc.pos],
                "mapped": [[int(p) for p in m]
                           for m in self.alloc._mapped],
            },
            "requests": reqs,
            "sched": sched,
            "retry": {str(rid): [pol.restarts, pol.last_failure_step]
                      for rid, pol in self._retry.items()},
        }
        if self.prefix_cache is not None:
            host["cache"] = self.prefix_cache.snapshot_state()
        return {"arrays": arrays, "host": host}

    def restore(self, snap: Dict) -> None:
        """Load a :meth:`snapshot` into this (same-configuration) engine.

        The engine must have been constructed with the same geometry —
        family, slots, lengths, page pool, kv_bits, scheduler class and
        prefix-cache setting (validated; mesh placement may differ: the
        pool is re-placed under this engine's shardings).  After restore,
        stepping resumes exactly where the snapshot was taken: the
        recovery drill pins greedy outputs token-identical to the
        uninterrupted run.
        """
        if self.mode != "paged":
            raise ValueError("restore() covers the paged engine only")
        host = snap["host"]
        geom = host["geometry"]
        mine = {
            "family": self.cfg.family,
            "n_slots": self.n_slots,
            "max_len": self.max_len,
            "page_size": self.page_size,
            "n_pages": self.alloc.n_pages,
            "prefill_chunk": self.prefill_chunk,
            "kv_bits": self.kv_bits,
            "sched": type(self.sched).__name__,
            "prefix_cache": self.prefix_cache is not None,
        }
        diff = {k: (geom.get(k), mine[k]) for k in mine
                if geom.get(k) != mine[k]}
        if diff:
            raise ValueError(
                f"snapshot geometry does not match this engine: {diff}")

        arrays = snap["arrays"]
        pages = KVPages(
            np.asarray(arrays["pages/k"]), np.asarray(arrays["pages/v"]),
            (np.asarray(arrays["pages/k_scale"])
             if "pages/k_scale" in arrays else None),
            (np.asarray(arrays["pages/v_scale"])
             if "pages/v_scale" in arrays else None),
            self.page_size, self.kv_bits)
        if self.mesh is not None:
            self.pages = jax.device_put(
                pages, cache_shardings(self.mesh, pages))
        else:
            self.pages = jax.tree_util.tree_map(jnp.asarray, pages)
        self.key = jnp.asarray(np.asarray(arrays["key"]))

        # allocator first: the cache's blocked recount reads refcounts
        alloc = host["alloc"]
        self.alloc.free = [int(p) for p in alloc["free"]]
        self.alloc._mapped = [[int(p) for p in m]
                              for m in alloc["mapped"]]
        self.alloc.pos[:] = alloc["pos"]
        self.alloc.block_tables[:, :] = NULL_PAGE
        self.alloc.refcount[:] = 0
        for slot, mapped in enumerate(self.alloc._mapped):
            for blk, page in enumerate(mapped):
                self.alloc.block_tables[slot, blk] = page
                self.alloc.refcount[page] += 1
        if self.prefix_cache is not None:
            self.prefix_cache.restore_state(host["cache"])

        by_rid: Dict[int, Request] = {}
        for r in host["requests"]:
            req = Request(r["rid"], list(r["prompt"]),
                          r["max_new_tokens"],
                          priority=r["priority"], tenant=r["tenant"])
            req.output = list(r["output"])
            req.prefill_tokens = list(r["prefill_tokens"])
            req.prefill_pos = r["prefill_pos"]
            req.admit_seq = r["admit_seq"]
            req.preemptions = r["preemptions"]
            req.cached_tokens = r["cached_tokens"]
            req.submit_t = r["submit_t"]
            req.ttft = r["ttft"]
            req.retries = r["retries"]
            lg = arrays.get(f"logits/{req.rid}")
            if lg is not None:
                req.last_logits = np.asarray(lg)
            req.pending = r.get("pending")
            by_rid[req.rid] = req

        sched = host["sched"]
        self.sched.queue = collections.deque(
            by_rid[rid] for rid in sched["queue"])
        self.sched.slot_req = [
            by_rid[rid] if rid is not None else None
            for rid in sched["slots"]]
        self.sched._admit_seq = sched["admit_seq"]
        self.sched.preemptions = sched["preemptions"]
        self.sched.prefill_computed = sched["prefill_computed"]
        self.sched.pending_forks = [
            (int(s), int(src), int(dst))
            for s, src, dst in sched["pending_forks"]]
        if isinstance(self.sched, BudgetScheduler):
            self.sched._vtime = {(t, p): vt
                                 for t, p, vt in sched.get("vtime", [])}

        eng = host["engine"]
        self._next_rid = eng["next_rid"]
        self.shed_count = eng["shed_count"]
        self.quarantined = eng["quarantined"]
        self.retried = eng.get("retried", 0)  # absent in older snapshots
        self._engine_step = eng["engine_step"]
        self._retry = {}
        for rid, (restarts, last_step) in host["retry"].items():
            self._retry[int(rid)] = RestartPolicy(
                max_restarts=self.scfg.max_request_retries,
                backoff_s=0.0,
                reset_after_steps=self.scfg.retry_reset_steps,
                restarts=restarts, last_failure_step=last_step)
        # in-flight requests resume under *this* engine's telemetry:
        # fresh timelines open for every restored rid (any stale
        # non-terminal timeline from a prior run of this engine is
        # discarded), so their spans terminate cleanly on retire
        self.obs.on_restore(sorted(by_rid))
        # a snapshot from before the engine sampled inside its step holds
        # a ready lane's logits and no pending id: draw the token its next
        # decode step would have drawn, so the lane resumes decoding
        tnow = self._clock()
        for r in host["requests"]:
            req = by_rid[r["rid"]]
            if ("pending" not in r and req.last_logits is not None
                    and req.prefill_pos >= len(req.prefill_tokens)
                    and not self._should_retire(req, limit_only=False)):
                self._emit_token(req, self._sample_next(req), tnow)
                req.pending = req.output[-1]

    def save_snapshot(self, directory: str, step: int) -> str:
        """Persist :meth:`snapshot` through ``repro.ckpt`` (manifest +
        checksummed shards, atomic commit).  Returns the written path."""
        from repro.ckpt import save_checkpoint

        snap = self.snapshot()
        specs = {name: [list(a.shape), str(a.dtype)]
                 for name, a in snap["arrays"].items()}
        return save_checkpoint(
            directory, step, snap["arrays"],
            extra={"kind": "serve-engine-snapshot",
                   "host": snap["host"], "array_specs": specs})

    def load_snapshot(self, directory: str,
                      step: Optional[int] = None) -> int:
        """Restore from a :meth:`save_snapshot` directory (``step=None``
        loads the latest committed snapshot).  Returns the step loaded.

        The array template ``repro.ckpt`` needs is rebuilt from the
        manifest's ``array_specs`` — snapshots are self-describing, so
        restore needs no record of which requests were in flight.
        """
        import json
        import os

        from repro.ckpt import load_checkpoint
        from repro.ckpt.checkpoint import latest_step

        if step is None:
            step = latest_step(directory)
            if step is None:
                raise FileNotFoundError(
                    f"no committed snapshot in {directory}")
        final = os.path.join(directory, f"step_{step:08d}")
        with open(os.path.join(final, "manifest_0.json")) as f:
            extra = json.load(f)["extra"]
        if extra.get("kind") != "serve-engine-snapshot":
            raise ValueError(
                f"{final} is not a serve-engine snapshot")
        template = {name: np.zeros(shape, dtype=np.dtype(dt))
                    for name, (shape, dt)
                    in extra["array_specs"].items()}
        arrays, _ = load_checkpoint(directory, template, step)
        self.restore({"arrays": arrays, "host": extra["host"]})
        return step

    # ================================================== paged internals
    def _step_paged(self) -> List[Request]:
        self._engine_step += 1
        finished: List[Request] = []
        self._errored_step = []  # quarantines land here (terminal too)
        # host spans tile the step (``serve.*``, through obs.annotate):
        # none encloses the dispatch spans, so a device-idle gap is put
        # down to the innermost thing the host was doing
        with self.obs.annotate("serve.schedule"):
            if self.chaos is not None and self.chaos.fire("preempt_storm"):
                # mass eviction drill: recompute-style, token-preserving
                # (fire() itself reports the fault through chaos.obs)
                self.sched.preempt_storm()
            self.sched.admit()
            self._apply_forks()
        self._maybe_audit(2)
        self._prefill_once()
        self._maybe_audit(2)
        # pre-decode retire: max_new_tokens=0 must emit no tokens
        with self.obs.annotate("serve.retire"):
            finished.extend(self._retire_paged(limit_only=True))
        self._decode_once_paged()
        self._maybe_audit(2)
        with self.obs.annotate("serve.retire"):
            finished.extend(self._retire_paged())
        self._maybe_audit(1)
        finished.extend(self._errored_step)
        return finished

    def _maybe_audit(self, level: int) -> None:
        """Run :meth:`audit` when ``ServeConfig.audit`` reaches
        ``level`` (1 = post-step, 2 = also after each phase)."""
        if self.scfg.audit < level:
            return
        try:
            self.audit()
        except AuditError:
            self.obs.on_audit(self.scfg.audit, False)
            raise
        self.obs.on_audit(self.scfg.audit, True)

    def _apply_forks(self) -> None:
        """Run the device copies of pending copy-on-write forks (mid-page
        cache hits recorded at admission) before anything reads or writes
        the forked pages."""
        for slot, src, dst in self.sched.pending_forks:
            self.pages = fork_tail_page(
                self.pages, jnp.int32(src), jnp.int32(dst))
            owner = self.sched.slot_req[slot]
            if owner is not None:
                self._charge_fork(owner.rid)
        self.sched.pending_forks.clear()

    def _prefill_once(self) -> None:
        """Advance every pending prompt by one batched chunk."""
        codebooks = (self.cfg.n_codebooks
                     if self.cfg.family == "audio" else 0)
        with self.obs.annotate("serve.schedule"):
            batch = self.sched.prefill_batch(audio_codebooks=codebooks)
        if batch is None:
            return
        tokens, pos0, seq_lens, lanes = batch
        t0 = self.obs.now()
        with self.obs.annotate("serve.tables"):
            bt, _ = self.alloc.device_tables(self._table_shardings)
        with self.obs.annotate("serve.prefill_chunk"):
            out, self.pages = self._prefill_paged(
                self.params, self.pages, bt, jnp.asarray(tokens),
                jnp.asarray(pos0), jnp.asarray(seq_lens), self.key)
            with self.obs.annotate("serve.logits_to_host"):
                ids, finite, lg = self._land(out)
        with self.obs.annotate("serve.lane_update"):
            prefilled = self._prefill_lanes(lanes, finite, t0)
        with self.obs.annotate("serve.sample"):
            self._emit(prefilled, ids, lg)

    def _prefill_lanes(self, lanes, finite: np.ndarray,
                       t0: float) -> List[Tuple[int, Request]]:
        """Per-lane bookkeeping after a prefill chunk landed; returns the
        lanes whose prompt is now wholly prefilled on finite logits."""
        self.obs.on_prefill(
            [(slot, self.sched.slot_req[slot].rid, n)
             for slot, n in lanes], t0)
        self._charge_prefill(
            [self.sched.slot_req[slot].rid for slot, _ in lanes])
        fault_slot, finite = self._inject_lane_chaos(
            [s for s, _ in lanes], finite)
        prefilled = []
        for slot, n_real in lanes:
            req = self.sched.slot_req[slot]
            if slot == fault_slot:
                # simulated device error on this lane's chunk: none of
                # its bookkeeping advances — retry or quarantine
                self._fault(slot, req, "step_fault")
                continue
            req.prefill_pos += n_real
            self.alloc.pos[slot] += n_real
            if req.prefill_pos >= len(req.prefill_tokens):
                if not finite[slot]:
                    # non-finite logits must be caught *before* the
                    # prefix-cache insert: poisoned KV pages must never
                    # be published for other requests to share
                    self._fault(slot, req, "nan_logits")
                    continue
                if self.prefix_cache is not None:
                    # the prompt's full pages are write-frozen from here
                    # (decode appends at pos >= len(prefill_tokens)):
                    # publish them for other requests to share
                    self.prefix_cache.insert(req.prefill_tokens,
                                             self.alloc.block_row(slot))
                prefilled.append((slot, req))
        return prefilled

    def _decode_once_paged(self) -> None:
        with self.obs.annotate("serve.schedule"):
            ready = self._grant_decode()
        if not ready:
            return
        t0 = self.obs.now()
        with self.obs.annotate("serve.tables"):
            bt, pos = self.alloc.device_tables(self._table_shardings)
            tokens = self._lane_tokens({s: r.pending for s, r in ready})
            active = jnp.asarray(
                self.sched.lane_mask([s for s, _ in ready]))
        with self.obs.annotate("serve.decode_step"):
            out, self.pages = self._decode_paged(
                self.params, self.pages, bt, pos, active, tokens, self.key)
            with self.obs.annotate("serve.logits_to_host"):
                ids, finite, lg = self._land(out)
        with self.obs.annotate("serve.lane_update"):
            decoded = self._decode_lanes(ready, finite, t0)
        with self.obs.annotate("serve.sample"):
            self._emit(decoded, ids, lg)

    def _land(self, out: Sampled):
        """The host's one sync per dispatch: ``(ids, finite, None)``, or
        ``(None, finite, logits)`` when ``_sample_next`` is overridden and
        the host picks the tokens from its own copy of the logits."""
        self.key = out.key
        if self._host_sampling():
            finite, lg = jax.device_get((out.finite, out.logits))
            return None, finite, lg
        ids, finite = jax.device_get((out.ids, out.finite))
        return ids, finite, None

    def _host_sampling(self) -> bool:
        """True when ``_sample_next`` is overridden, on the instance or
        its class (teacher forcing, a recording hook): it is then called
        once per lane per token on a host copy of that lane's logits."""
        return (getattr(self._sample_next, "__func__", None)
                is not _HOST_SAMPLE_NEXT)

    def _emit(self, lanes: List[Tuple[int, Request]],
              ids: Optional[np.ndarray], lg: Optional[np.ndarray]) -> None:
        """Append each landed lane's next id to its output; the next
        decode step feeds it.  A lane due to retire (length limit or
        ``max_len``) takes none."""
        tnow = self._clock()
        n = 0
        for slot, req in lanes:
            if lg is not None:
                req.last_logits = lg[slot, -1]
            if self._should_retire(req, limit_only=False):
                continue
            tok = int(ids[slot]) if lg is None else self._sample_next(req)
            self._emit_token(req, tok, tnow)
            req.pending = tok
            n += 1
        if n:
            self.obs.on_sampled("device" if lg is None else "host", n)

    def _emit_token(self, req: Request, tok: int, tnow: float) -> None:
        """Append one generated token, stamping TTFT on the first."""
        if not req.output and req.ttft is None:
            req.ttft = tnow - req.submit_t
            self.obs.on_first_token(req.rid, req.ttft, tnow)
        else:
            self.obs.on_token(req.rid, tnow)
        req.output.append(tok)

    def _grant_decode(self) -> List[Tuple[int, Request]]:
        """The decoding lanes granted a page for this step, charged to
        the scheduler's budget."""
        # page grant first (may preempt): a preempted lane drops out of
        # this step and resumes via re-prefill with identical greedy state
        ready = []
        for slot, req in self.sched.decode_lanes():
            if self._should_retire(req, limit_only=False):
                continue
            if self.sched.slot_req[slot] is not req:
                continue  # preempted by an earlier lane's grant this loop
            if self.sched.grant_decode_page(slot):
                ready.append((slot, req))
        # a later grant may have preempted an earlier-granted lane: keep
        # only lanes still resident
        ready = [(s, r) for s, r in ready if self.sched.slot_req[s] is r]
        if ready:
            self.sched.charge_decode(ready)
        return ready

    def _decode_lanes(self, ready: List[Tuple[int, Request]],
                      finite: np.ndarray,
                      t0: float) -> List[Tuple[int, Request]]:
        """Per-lane bookkeeping after a decode step landed; returns the
        lanes whose step wrote its K/V on finite logits."""
        self.obs.on_decode([(s, r.rid) for s, r in ready], t0)
        self._charge_decode([r.rid for _, r in ready])
        fault_slot, finite = self._inject_lane_chaos(
            [s for s, _ in ready], finite)
        decoded = []
        for slot, req in ready:
            if slot == fault_slot:
                self._fault(slot, req, "step_fault")
                continue
            if not finite[slot]:
                # the step's id for this lane came from non-finite logits
                # and is dropped, never appended; the id it fed is already
                # in ``req.output``, so the recompute retry prefills it
                # back and draws the next id again (greedy output is
                # unchanged)
                self._fault(slot, req, "nan_logits")
                continue
            self.alloc.pos[slot] += 1
            decoded.append((slot, req))
        return decoded

    def _retire_paged(self, limit_only: bool = False) -> List[Request]:
        done = []
        for slot, req in enumerate(self.sched.slot_req):
            if req is None or req.prefill_pos < len(req.prefill_tokens):
                # mid-prefill: even a max_new_tokens=0 request finishes
                # its prefill first (and publishes its prompt's pages)
                continue
            if self._should_retire(req, limit_only):
                req.done = True
                req.finish_reason = "length"
                done.append(req)
                self.sched.drop_forks(slot)
                self.alloc.free_slot(slot)
                self.sched.slot_req[slot] = None
                self._retry.pop(req.rid, None)
                self.obs.on_retire(req.rid, "length", len(req.output))
        return done

    # ============================================== faults / quarantine
    def _inject_lane_chaos(self, slots: List[int], finite: np.ndarray):
        """Consult the chaos injector after a dispatch landed: returns
        ``(fault_slot, finite)`` where ``fault_slot`` (or None) takes a
        simulated device error, and ``finite`` may have one lane's flag
        cleared (a copy — the injected poison then takes the same path
        as logits the step found non-finite)."""
        if self.chaos is None or not slots:
            return None, finite
        fault_slot = None
        if self.chaos.fire("step_fault"):
            fault_slot = slots[self.chaos.pick("step_fault", len(slots))]
        if self.chaos.fire("nan_logits"):
            victim = slots[self.chaos.pick("nan_logits", len(slots))]
            finite = np.array(finite)  # a fetched array may be read-only
            finite[victim] = False
        return fault_slot, finite

    def _scrub_slot_pages(self, slot: int) -> None:
        """Zero a faulted lane's privately-owned pages before they return
        to the free list.

        A non-finite fault has written NaN into the lane's KV pages, and
        the attention paths mask additively (``score + -inf``) — adding
        ``-inf`` to a NaN score is still NaN, so a stale poisoned value
        in the masked tail of a reused page contaminates the *next*
        tenant's softmax.  Pages the prefix cache holds (or other lanes
        share) are skipped: they were write-frozen by a clean prefill
        before this request's fault and other requests still read them.
        """
        cached = (set(self.prefix_cache.pages())
                  if self.prefix_cache is not None else set())
        private = [p for p in self.alloc._mapped[slot]
                   if self.alloc.refcount[p] == 1 and p not in cached]
        if not private:
            return
        idx = jnp.asarray(private, jnp.int32)
        self.pages = self.pages.replace(**{
            name: a.at[:, :, idx].set(0)
            for name, a in page_arrays(self.pages).items()})

    def _fault(self, slot: int, req: Request, kind: str) -> None:
        """One lane's step failed (simulated device error or non-finite
        logits).  Isolation is per-request: within the restart budget the
        request is requeued recompute-style (identical to preemption —
        greedy output is token-preserved); past it, quarantined with
        ``finish_reason="error"``.  Every other lane is untouched.
        """
        self.obs.on_fault(req.rid, kind)
        pol = self._retry.get(req.rid)
        if pol is None:
            pol = self._retry[req.rid] = RestartPolicy(
                max_restarts=self.scfg.max_request_retries,
                backoff_s=0.0,
                reset_after_steps=self.scfg.retry_reset_steps)
        try:
            pol.on_failure(RuntimeError(kind), self._engine_step)
        except RuntimeError:
            self._quarantine(slot, req, kind)
            return
        # recompute-style retry: exactly the preemption path — pages
        # scrubbed and released, generated tokens become prefill, front
        # of the queue
        self._scrub_slot_pages(slot)
        self.sched.drop_forks(slot)
        self.alloc.free_slot(slot)
        self.sched.slot_req[slot] = None
        req.prefill_tokens = list(req.prompt) + list(req.output)
        req.prefill_pos = 0
        req.cached_tokens = 0
        req.last_logits = None
        req.pending = None
        req.retries += 1
        self.retried += 1
        self.sched.queue.appendleft(req)
        self.obs.on_retry(req.rid, kind, pol.restarts)

    def _quarantine(self, slot: int, req: Request, kind: str) -> None:
        """Remove a request whose restart budget is spent: pages and
        prefix-cache pins release, pending forks drop, and the request
        terminates with ``finish_reason="error"`` — tokens generated so
        far stay on ``req.output`` for the caller."""
        req.cancelled = True
        req.finish_reason = "error"
        self._scrub_slot_pages(slot)
        self.sched.drop_forks(slot)
        self.alloc.free_slot(slot)
        self.sched.slot_req[slot] = None
        self._retry.pop(req.rid, None)
        self.quarantined += 1
        self._errored_step.append(req)  # step() returns terminals
        self.obs.on_quarantine(req.rid, kind, len(req.output))

    # ================================================== slots internals
    def _step_slots(self) -> List[Request]:
        finished: List[Request] = []
        self._admit()
        # pre-decode retire: max_new_tokens=0 must emit no tokens
        finished.extend(self._retire(limit_only=True))
        self._decode_one()
        finished.extend(self._retire())
        return finished

    def _admit(self):
        for slot in range(self.n_slots):
            if self.slot_req[slot] is None and self.queue:
                req = self.queue.popleft()
                self.slot_req[slot] = req
                self.obs.on_admit(req.rid, slot, 0)
                self._reset_slot(slot)
                self._prefill_slot(slot, req)

    def _reset_slot(self, slot: int):
        """Reset one slot's cache state before reuse.

        Without this a request admitted into a retired request's slot
        inherits its predecessor's cache position — the old engine silently
        decoded with the previous request's KV prefix (and, for ssm/hybrid,
        recurrent state) as context.  Only ``pos`` and the read-modify-write
        recurrent leaves (``conv``/``h``) need clearing: stale K/V at
        positions <= cur_pos is always freshly overwritten before it is
        read, and positions beyond cur_pos are masked.
        """

        def reset(path, leaf):
            top = path[0].key if hasattr(path[0], "key") else None
            if top == "pos":
                return leaf.at[slot].set(0)
            if top in ("conv", "h"):
                unstacked = any(
                    isinstance(p, jax.tree_util.SequenceKey) for p in path)
                idx = (slot,) if unstacked else (slice(None), slot)
                return leaf.at[idx].set(0)
            return leaf

        self.cache = jax.tree_util.tree_map_with_path(reset, self.cache)

    def _prefill_slot(self, slot: int, req: Request):
        """Prompt tokens enter the slot's cache via sequential decode (one
        slot at a time — the legacy baseline; the paged engine replaces
        this loop with batched chunked prefill)."""
        logits = None
        t0 = self.obs.now()
        with self.obs.annotate("serve.prefill_slot"):
            for t in req.prompt:
                tok = self._slot_tokens({slot: t})
                logits, self.cache = self._masked_step(tok, only_slot=slot)
            req.last_logits = np.asarray(logits[slot, -1])
        self.obs.on_prefill([(slot, req.rid, len(req.prompt))], t0)

    def _slot_tokens(self, updates: Dict[int, int]) -> jnp.ndarray:
        if self.cfg.family == "audio":
            toks = np.zeros((self.n_slots, 1, self.cfg.n_codebooks), np.int32)
            for s, t in updates.items():
                toks[s, 0, :] = t
        else:
            toks = np.zeros((self.n_slots, 1), np.int32)
            for s, t in updates.items():
                toks[s, 0] = t
        return jnp.asarray(toks)

    _lane_tokens = _slot_tokens  # paged mode: same (B, 1[, K]) layout

    def _masked_step(self, tokens, only_slot: Optional[int] = None):
        """Advance decode; slots other than ``only_slot`` (when given) have
        their cache position frozen by restoring pos afterwards."""
        logits, new_cache = self._step(self.params, self.cache, tokens)
        if only_slot is not None:
            keep = jnp.arange(self.n_slots) == only_slot
            new_cache = self._merge_cache(self.cache, new_cache, keep)
        self.cache = new_cache
        return logits, self.cache

    def _merge_cache(self, old, new, keep: jnp.ndarray):
        def merge(path, o, n):
            if o.ndim == 0 or o.shape == ():
                return n
            # the batch (slot) axis position differs by leaf: ``pos`` and
            # unstacked tuple entries are (B, ...), stacked k/v/conv/h are
            # (L, B, ...).  Decide from the leaf's path, not its shape —
            # shape[0] == n_slots is ambiguous whenever n_layers happens
            # to equal n_slots (the old heuristic then merged along the
            # layer axis and corrupted every slot).
            top = path[0].key if hasattr(path[0], "key") else None
            unstacked = any(
                isinstance(p, jax.tree_util.SequenceKey) for p in path)
            batch_ax = 0 if (top == "pos" or unstacked or o.ndim < 2) else 1
            shape = [1] * o.ndim
            shape[batch_ax] = -1
            return jnp.where(keep.reshape(shape), n, o)

        return jax.tree_util.tree_map_with_path(merge, old, new)

    def _decode_one(self):
        active = {s: r for s, r in enumerate(self.slot_req) if r is not None}
        if not active:
            return
        updates = {}
        tnow = self._clock()
        for slot, req in active.items():
            if req.last_logits is None:
                continue
            if len(req.output) >= req.max_new_tokens:
                continue
            tok = self._sample_next(req)
            self._emit_token(req, tok, tnow)
            updates[slot] = tok
        if not updates:
            return
        self.obs.on_sampled("host", len(updates))
        tokens = self._slot_tokens(updates)
        keep = jnp.asarray([s in updates for s in range(self.n_slots)])
        t0 = self.obs.now()
        with self.obs.annotate("serve.decode_step"):
            logits, new_cache = self._step(self.params, self.cache, tokens)
            self.cache = self._merge_cache(self.cache, new_cache, keep)
            lg = np.asarray(logits)  # host sync: the step has landed
        self.obs.on_decode([(s, self.slot_req[s].rid) for s in updates], t0)
        for slot in updates:
            self.slot_req[slot].last_logits = lg[slot, -1]

    def _retire(self, limit_only: bool = False) -> List[Request]:
        done = []
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            if self._should_retire(req, limit_only):
                req.done = True
                req.finish_reason = "length"
                done.append(req)
                self.slot_req[slot] = None
                self.obs.on_retire(req.rid, "length", len(req.output))
        return done

    # ------------------------------------------------------------ shared
    def _sample_next(self, req: Request) -> int:
        """Sample the next token from a request's host ``last_logits``.

        The host sampler: slots mode calls it, and the paged engine calls
        it only where it is overridden (its own dispatches sample inside
        the jit).  ``last_logits`` is ``(V,)``, or ``(K, V)`` for audio —
        the engine's token stream carries one id broadcast across
        codebooks, so the audio path samples codebook 0.
        """
        last = jnp.asarray(req.last_logits)
        if last.ndim == 1:
            last = last[None]
        self.key, sub = jax.random.split(self.key)
        return int(sample(last, sub, self.scfg.temperature,
                          self.scfg.top_k)[0])

    def _should_retire(self, req: Request, limit_only: bool) -> bool:
        limit = len(req.output) >= req.max_new_tokens
        if limit_only:
            return limit
        overflow = len(req.prompt) + len(req.output) >= self.max_len - 1
        return limit or overflow


# the unmodified host sampler: ``_host_sampling`` tells an override by it
_HOST_SAMPLE_NEXT = ServeEngine._sample_next
