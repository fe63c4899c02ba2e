"""Persistent join service: one long-lived session, many queries, cross-query reuse.

``mpc_join`` answers one query and throws everything away: the planner LPs,
the compiled :class:`~repro.mpc.program.RoundProgram`, the executor's learned
overflow capacities, and every AOT-compiled XLA executable die with the call.
A serving deployment answers the *same shapes* over and over — repeated
pattern queries over a graph, dashboards re-running a join as data refreshes —
and the paper's structure makes that reuse sound: the Theorem 6.2 plan is a
pure function of the query's hypergraph and the histogram, never of the
concrete tuples (``compile_plan`` reads only structure + ``HeavyStats``).

:class:`JoinSession` is the layer that exploits it (docs/design/09-service.md):

  * **Plan cache.**  Compiled programs are kept in an LRU keyed by
    :func:`~repro.mpc.program.plan_cache_key` — query structure (schemes +
    shared-table alias classes) plus the full histogram signature.  A hit
    skips the planner LPs and the taxonomy sweep entirely; the cached program
    is :meth:`~repro.mpc.program.RoundProgram.rebind`-ed onto the submitted
    data.  A shifted histogram changes the key, so stale plans are never
    reused — they age out of the LRU.
  * **Executor persistence.**  One :class:`DataplaneExecutor` lives as long
    as the session: its learned overflow capacities and the process-wide
    :class:`~repro.mpc.executors.ExecutableCache` survive across submits, so
    a warm repeat of any query runs with zero recompiles and zero retries —
    steady-state latency is the pure dispatch cost of the stage-batched
    scheduler.
  * **Batch submission.**  :meth:`JoinSession.submit_batch` shares per-table
    work across queries binding the same physical ``Relation.table``: one
    scatter placement on the simulator, one unique-count pass for the
    histogram on the dataplane (the cross-query extension of the
    shared-input Scatter path).
  * **Cross-query coalescing.**  :meth:`JoinSession.submit_async` enqueues
    requests into a bounded submission queue; a drainer thread groups queued
    queries whose compiled programs share a
    :func:`~repro.mpc.program.coalesce_signature` and runs each group through
    ONE pass of the stage-batched scheduler
    (:meth:`DataplaneExecutor.run_many`) — stages from different queries
    landing in the same geometry bucket ride one fused ``shard_map``
    dispatch, so the strictly serial collective stream (concurrent
    collective executions deadlock) serves many queries per dispatch.
    Identical submissions (same plan key, same bound tables) collapse
    further: one member executes and the rest share its result.  Results
    demultiplex per query with correct counts/stats and are byte-identical
    to serial :meth:`submit` (tests/test_service_async.py).
    :meth:`JoinSession.submit_coalesced` is the same machinery as a
    synchronous call.  Admission control is a bounded queue: a full queue
    rejects with :class:`AdmissionError` (backpressure) instead of queueing
    unboundedly.
  * **Observability.**  Every submit returns a :class:`SessionResult` with
    per-phase latency and cache provenance; :attr:`JoinSession.stats`
    accumulates the session-wide :class:`ServiceStats` (hit/miss counts per
    cache — plan LRU, learned caps, and executables metered separately —
    cold/warm/e2e latency windows with percentiles, and SLO counters).

``mpc_join`` remains the one-shot path and is implemented as a throwaway
session (see :mod:`repro.mpc.engine`); session and one-shot results are
row-multiset identical on both backends (``tests/test_service.py``).
"""

from __future__ import annotations

import math
import queue as queue_mod
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future
from dataclasses import dataclass, field, replace
from typing import Deque, Dict, List, Optional, Sequence, Tuple, Union

from ..core.hypergraph import rho
from ..core.planner import heavy_parameter
from ..core.query import Attr, JoinQuery
from ..core.taxonomy import HeavyStats, compute_stats
from ..obs import new_request_id, span
from ..train.fault import Heartbeat, StragglerMonitor
from .executors import (
    DataplaneExecutor,
    DataplaneJoinResult,
    MPCJoinResult,
    SimulatorExecutor,
)
from .faults import (
    DeadlineExceededError,
    DegradedSessionError,
    JoinServiceError,
    ProgramVerificationError,
    QueryFailedError,
    describe_query,
)
from .program import (
    RoundProgram,
    RunConfig,
    _verify_default,
    coalesce_signature,
    compile_plan,
    plan_cache_key,
)
from .verify import verify_bindings, verify_program
from .simulator import MPCSimulator
from .statistics import distributed_stats


#: sliding-window size of the ServiceStats latency samples.
LATENCY_WINDOW = 512


class AdmissionError(RuntimeError):
    """The submission queue is full — the request was rejected, not queued.

    Backpressure signal of the bounded async queue: callers should retry
    later or shed load; ``ServiceStats.rejected`` counts these."""


@dataclass
class ServiceStats:
    """Session-wide service counters (live object on :attr:`JoinSession.stats`).

    Each cache layer meters separately so provenance is unambiguous:
    ``plan_hits``/``plan_misses``/``plan_evictions`` are the plan LRU;
    ``caps_hits``/``caps_misses``/``caps_evictions`` are the executor's
    learned-overflow-caps store (a *capacity* cache — its eviction cannot
    change results, only cause one rediscovery retry); ``jit_hits``/
    ``jit_misses`` are the process-wide executable cache.  ``retries``
    aggregates the dataplane scheduler's overflow retries.

    ``cold_us``/``warm_us`` collect per-submit service latencies split by
    plan-cache outcome (cold = the submit compiled a new plan) and
    ``e2e_us`` collects queue-inclusive latencies of async submits, each
    over a sliding window of the last :data:`LATENCY_WINDOW` samples — a
    bounded store, like every other cache in this layer.  ``percentile``
    reads any window; ``slo_ok``/``slo_violations`` count submits against
    the session's ``slo_target_us`` (e2e when queued, service time
    otherwise).

    The coalescing layer adds: ``async_submits`` (requests entering the
    queue), ``rejected`` (admission-control bounces), ``coalesced_batches``/
    ``coalesced_queries``/``max_coalesced_batch`` (multi-query drains), and
    ``deduped`` (requests served by sharing an identical member's
    execution).

    The robustness layer (docs/design/10-robustness.md) adds: ``failed``
    (requests resolved with a typed :class:`~repro.mpc.faults.JoinServiceError`),
    ``deadline_exceeded`` (the subset that hit their monotonic budget),
    ``degraded_fallbacks`` (coalesced groups whose fused dispatch failed and
    fell back to per-member serial execution), ``drainer_crashes`` (drainer
    supervision trips → degraded sessions), ``slow_batches`` (drain batches
    the :class:`~repro.train.fault.StragglerMonitor` flagged), and
    ``quarantined_caps``/``quarantined_plans`` (cache entries invalidated
    because a failed attempt touched them — ``quarantined_caps`` mirrors the
    executor's lifetime counter).

    The verification layer (docs/design/11-verification.md) adds:
    ``verified`` (submits whose compiled program passed the *full* static
    verifier — plan-cache misses only; hits re-verify bindings, which is
    deliberately not counted here) and ``verify_us`` (total wall time spent
    in any verification, full or bindings-only, so the warm-path cost is
    observable and provably near zero)."""

    submits: int = 0
    plan_hits: int = 0
    plan_misses: int = 0
    plan_evictions: int = 0
    cached_plans: int = 0
    jit_hits: int = 0
    jit_misses: int = 0
    retries: int = 0
    caps_hits: int = 0
    caps_misses: int = 0
    caps_evictions: int = 0
    async_submits: int = 0
    rejected: int = 0
    coalesced_batches: int = 0
    coalesced_queries: int = 0
    max_coalesced_batch: int = 0
    deduped: int = 0
    failed: int = 0
    deadline_exceeded: int = 0
    degraded_fallbacks: int = 0
    drainer_crashes: int = 0
    slow_batches: int = 0
    quarantined_caps: int = 0
    quarantined_plans: int = 0
    slo_ok: int = 0
    slo_violations: int = 0
    verified: int = 0
    verify_us: float = 0.0
    cold_us: Deque[float] = field(default_factory=lambda: deque(maxlen=LATENCY_WINDOW))
    warm_us: Deque[float] = field(default_factory=lambda: deque(maxlen=LATENCY_WINDOW))
    e2e_us: Deque[float] = field(default_factory=lambda: deque(maxlen=LATENCY_WINDOW))

    @property
    def mean_cold_us(self) -> float:
        return sum(self.cold_us) / len(self.cold_us) if self.cold_us else 0.0

    @property
    def mean_warm_us(self) -> float:
        return sum(self.warm_us) / len(self.warm_us) if self.warm_us else 0.0

    def percentile(self, q: float, window: str = "warm") -> float:
        """Latency percentile over one sliding window (``warm``/``cold``/
        ``e2e``), linearly interpolated; 0.0 on an empty window."""
        if window not in ("warm", "cold", "e2e"):
            raise ValueError(f"unknown latency window {window!r}")
        samples = sorted(getattr(self, f"{window}_us"))
        if not samples:
            return 0.0
        rank = (q / 100.0) * (len(samples) - 1)
        lo = int(math.floor(rank))
        hi = min(lo + 1, len(samples) - 1)
        frac = rank - lo
        return samples[lo] * (1.0 - frac) + samples[hi] * frac


@dataclass
class SessionResult:
    """One submit's answer plus its service provenance.

    ``result`` is the backend result (:class:`MPCJoinResult` on the
    simulator, :class:`DataplaneJoinResult` on the dataplane); the convenience
    properties forward the common fields.  ``plan_cache_hit`` says whether the
    plan LRU served the compiled program; the ``*_us`` fields break the
    submit's wall-clock into statistics / compile / execute phases;
    ``stats_us``, ``compile_us`` and ``verify_us`` are the durations of the
    ``planner.stats``, ``planner.compile`` and ``planner.verify`` spans
    (:mod:`repro.obs`).
    ``request_id`` is the id the request got at admission, which its
    ``service.submit`` or ``service.batch`` span carries in a trace.

    Coalescing provenance: ``coalesced`` is True when the request ran inside
    a multi-query scheduler pass (its ``execute_us`` is then the *shared*
    batch execute wall — the whole point is that k queries split it);
    ``batch_size`` is that drain batch's size; ``deduplicated`` is True when
    an identical concurrent submission executed and this request shares its
    result object.  ``queue_us``/``e2e_us`` are nonzero only for
    :meth:`JoinSession.submit_async` requests (time spent queued, and
    enqueue-to-resolution wall).  ``caps_hits``/``caps_misses``/
    ``caps_evictions`` forward the learned-caps counters of the run so cache
    provenance (plan LRU vs learned caps vs executables) is unambiguous
    per-result, not just session-wide."""

    result: Union[MPCJoinResult, DataplaneJoinResult]
    plan_key: Tuple
    plan_cache_hit: bool
    stats_us: float
    compile_us: float
    execute_us: float
    total_us: float
    coalesced: bool = False
    batch_size: int = 1
    deduplicated: bool = False
    queue_us: float = 0.0
    e2e_us: float = 0.0
    #: True when the *full* static verifier ran over this submit's compiled
    #: program (plan-cache miss); cache hits re-verify bindings only and
    #: report False — the observable proof that verification stays off the
    #: warm hot path.  ``verify_us`` is the time spent either way (part of
    #: ``total_us``).
    verified: bool = False
    verify_us: float = 0.0
    request_id: int = 0

    @property
    def count(self) -> int:
        return self.result.count

    @property
    def rows(self):
        return self.result.rows

    @property
    def per_h_counts(self):
        return self.result.per_h_counts

    @property
    def retries(self) -> int:
        return getattr(self.result, "retries", 0)

    @property
    def retry_log(self) -> list:
        return getattr(self.result, "retry_log", [])

    @property
    def jit_cache_misses(self) -> int:
        return getattr(self.result, "jit_cache_misses", 0)

    @property
    def caps_hits(self) -> int:
        return getattr(self.result, "caps_hits", 0)

    @property
    def caps_misses(self) -> int:
        return getattr(self.result, "caps_misses", 0)

    @property
    def caps_evictions(self) -> int:
        return getattr(self.result, "caps_evictions", 0)


@dataclass
class _Request:
    """One queued (or inline) submission flowing through ``_execute_batch``."""

    query: JoinQuery
    lam: Optional[int] = None
    stats: Optional[HeavyStats] = None
    materialize: bool = True
    h_subsets: Optional[Sequence[Sequence[Attr]]] = None
    fuse_semijoin: Optional[bool] = None
    batch: Optional[Dict] = None          # submit_batch's shared-table memos
    future: Optional[Future] = None       # async submits resolve through this
    t_enqueue: Optional[float] = None     # perf_counter at queue admission
    deadline: Optional[float] = None      # absolute monotonic budget (or None)
    rid: int = field(default_factory=new_request_id)   # id given at admission
    # filled by _prepare:
    executor: object = None
    program: Optional[RoundProgram] = None
    plan_key: Optional[Tuple] = None
    plan_cache_hit: bool = False
    stats_us: float = 0.0
    compile_us: float = 0.0
    verified: bool = False
    verify_us: float = 0.0
    error: Optional[BaseException] = None


#: drainer shutdown sentinel (enqueued by :meth:`JoinSession.close`).
_SHUTDOWN = object()


class JoinSession:
    """A persistent join service over one executor: repeated ``submit`` calls
    with cross-query plan/compile reuse.

    Args:
        p: machine count every submitted plan is compiled for (the dataplane
            maps it onto however many devices its mesh has).
        backend: ``"dataplane"`` (default — the long-lived
            :class:`DataplaneExecutor`) or ``"simulator"`` (a fresh metered
            :class:`~repro.mpc.simulator.MPCSimulator` per submit, so each
            query gets its own load ledger; plans are still cached across
            submits).
        executor: optionally inject a configured :class:`DataplaneExecutor`
            (e.g. ``batch_stages=False``); ignored on the simulator backend.
        plan_cache_size: LRU bound on cached compiled programs.
        seed: shared-randomness seed (scatter + routing hashes).
        fuse_semijoin: default fusion flag for submits that don't pass one.
        max_queue: admission bound of the async submission queue — a full
            queue rejects :meth:`submit_async` with :class:`AdmissionError`.
        max_coalesce: most requests one drain batch may coalesce.
        slo_target_us: per-query latency SLO; when set, every submit counts
            into ``stats.slo_ok``/``stats.slo_violations`` (async submits
            judged on queue-inclusive e2e latency).
        async_autostart: start the drainer thread lazily on the first
            :meth:`submit_async` (disable to unit-test admission control or
            to drive the queue deterministically via :meth:`close`).
        fault_plan: a :class:`~repro.mpc.faults.FaultPlan` consulted at every
            injection site — executor dispatch/compile/overflow plus the
            drainer — for chaos testing (None = no injection).
        heartbeat_path: when set, the drainer writes a
            :class:`~repro.train.fault.Heartbeat` file before every drain
            batch, so an external supervisor can detect a wedged session.
        straggler_factor: drain batches slower than ``factor ×`` the running
            EMA are counted into ``stats.slow_batches`` (the
            :class:`~repro.train.fault.StragglerMonitor` contract).

    Failure semantics (docs/design/10-robustness.md): every failed request
    resolves exactly once with a typed
    :class:`~repro.mpc.faults.JoinServiceError` naming its query; a fused
    coalesced dispatch that fails falls back to per-member serial execution
    so batchmates of a poisoned query still get byte-identical results; a
    crashed drainer resolves everything pending with
    :class:`~repro.mpc.faults.DegradedSessionError` and flips the session
    degraded until :meth:`restart`; caches touched by a failed attempt are
    quarantined so transient faults never poison the warm steady state.

    A repeat submit of a cached query shape is the *warm path*: the plan LRU
    skips ``compile_plan``, and on the dataplane the executor's learned caps
    and executable cache make the run retry-free and recompile-free —
    ``tests/test_service.py`` locks ``jit_cache_misses == 0`` and an empty
    ``retry_log`` on the second submit, including after an LRU
    eviction/readmission cycle (learned caps are executor-lifetime state,
    keyed independently of the plan LRU).

    Thread-safety: all executor access is serialized under one re-entrant
    lock — concurrent collective executions deadlock, so multiplexing happens
    at the bucket layer (coalesced dispatches), never with parallel runs."""

    def __init__(
        self,
        p: int,
        backend: str = "dataplane",
        executor: Optional[DataplaneExecutor] = None,
        plan_cache_size: int = 64,
        seed: int = 0,
        fuse_semijoin: bool = False,
        max_queue: int = 256,
        max_coalesce: int = 32,
        slo_target_us: Optional[float] = None,
        async_autostart: bool = True,
        fault_plan=None,
        heartbeat_path=None,
        straggler_factor: float = 2.5,
        verify: Optional[bool] = None,
    ):
        if backend not in ("dataplane", "simulator"):
            raise ValueError(f"unknown backend {backend!r}")
        if max_coalesce < 1:
            raise ValueError("max_coalesce must be >= 1")
        self.p = p
        self.backend = backend
        self.seed = seed
        self.fuse_semijoin = fuse_semijoin
        # static verification: full pass on every plan-cache miss, bindings
        # re-check on every hit (None defers to the REPRO_VERIFY env var, so
        # the test suite runs verified by default without touching prod).
        self.verify = _verify_default() if verify is None else bool(verify)
        self.plan_cache_size = plan_cache_size
        self.max_coalesce = max_coalesce
        self.slo_target_us = slo_target_us
        self.async_autostart = async_autostart
        self.executor: Optional[DataplaneExecutor] = None
        if backend == "dataplane":
            self.executor = executor if executor is not None else DataplaneExecutor()
        self._plans: "OrderedDict[Tuple, RoundProgram]" = OrderedDict()
        self.stats = ServiceStats()
        self._lock = threading.RLock()
        self._queue: "queue_mod.Queue" = queue_mod.Queue(maxsize=max_queue)
        self._drainer: Optional[threading.Thread] = None
        self._closed = False
        self.fault_plan = fault_plan
        self._degraded_cause: Optional[BaseException] = None
        self._monitor = StragglerMonitor(factor=straggler_factor, warmup=1)
        self._heartbeat = (
            Heartbeat(heartbeat_path) if heartbeat_path is not None else None
        )
        self._batch_seq = 0

    # -- single-query entry ---------------------------------------------------

    def submit(
        self,
        query: JoinQuery,
        lam: Optional[int] = None,
        stats: Optional[HeavyStats] = None,
        materialize: bool = True,
        h_subsets: Optional[Sequence[Sequence[Attr]]] = None,
        fuse_semijoin: Optional[bool] = None,
        deadline_s: Optional[float] = None,
        _batch: Optional[Dict] = None,
    ) -> SessionResult:
        """Answer one join query, reusing every cached artifact that applies.

        Args:
            query: the join query (concrete relations attached).
            lam: heavy parameter λ; default Θ(p^{1/(2ρ)}) per the paper.
            stats: inject a precomputed histogram; by default the simulator
                backend runs the 3 metered rounds of the distributed protocol
                and the dataplane backend computes the centralized oracle.
            materialize: return result rows (False: counts only).
            h_subsets: restrict the H-taxonomy (testing).
            fuse_semijoin: override the session's default fusion flag.
            deadline_s: monotonic-clock budget in seconds; past it the query
                fails with :class:`~repro.mpc.faults.DeadlineExceededError`
                (checked between dispatches, never mid-collective).

        Returns:
            A :class:`SessionResult` wrapping the backend result with cache
            provenance and per-phase latency.

        Raises:
            A typed :class:`~repro.mpc.faults.JoinServiceError` naming the
            query on any failure, with the root cause (executor frames
            included) chained on ``__cause__``.
        """
        req = _Request(
            query=query, lam=lam, stats=stats, materialize=materialize,
            h_subsets=h_subsets, fuse_semijoin=fuse_semijoin, batch=_batch,
            deadline=self._abs_deadline(deadline_s),
        )
        with span("service.submit", requests=(req.rid,)):
            out = self._execute_batch([req])[0]
        if isinstance(out, BaseException):
            # re-raise with the stored traceback intact (the original frames
            # would otherwise be replaced by this raise site)
            raise out.with_traceback(out.__traceback__)
        return out

    # -- async / coalescing entry ---------------------------------------------

    def submit_async(
        self,
        query: JoinQuery,
        lam: Optional[int] = None,
        stats: Optional[HeavyStats] = None,
        materialize: bool = True,
        h_subsets: Optional[Sequence[Sequence[Attr]]] = None,
        fuse_semijoin: Optional[bool] = None,
        block: bool = True,
        timeout: Optional[float] = None,
        deadline_s: Optional[float] = None,
    ) -> "Future[SessionResult]":
        """Enqueue one query; a drainer coalesces concurrent requests.

        Returns a :class:`concurrent.futures.Future` resolving to the same
        :class:`SessionResult` a serial :meth:`submit` would produce (byte-
        identical rows — coalescing changes scheduling, never results), with
        ``queue_us``/``e2e_us`` filled in.

        Admission control: the queue is bounded at ``max_queue``.  With
        ``block=False`` (or when ``timeout`` elapses) a full queue raises
        :class:`AdmissionError` immediately — the backpressure signal — and
        increments ``stats.rejected``.

        The drainer thread starts lazily on the first call (disable with
        ``async_autostart=False``; :meth:`close` then drains inline).

        ``deadline_s`` starts the request's monotonic budget at admission —
        time spent queued counts against it, so a request stuck behind a slow
        batch times out instead of blocking its caller forever.

        A degraded session (drainer crashed — see :meth:`restart`) raises
        :class:`~repro.mpc.faults.DegradedSessionError` immediately."""
        if self._closed:
            raise RuntimeError("session is closed")
        if self._degraded_cause is not None:
            raise DegradedSessionError(
                "session is degraded (drainer crashed); call restart()",
                cause=self._degraded_cause,
            )
        req = _Request(
            query=query, lam=lam, stats=stats, materialize=materialize,
            h_subsets=h_subsets, fuse_semijoin=fuse_semijoin,
            future=Future(), t_enqueue=time.perf_counter(),
            deadline=self._abs_deadline(deadline_s),
        )
        try:
            self._queue.put(req, block=block, timeout=timeout)
        except queue_mod.Full:
            self.stats.rejected += 1
            raise AdmissionError(
                f"submission queue full ({self._queue.maxsize} pending)"
            ) from None
        self.stats.async_submits += 1
        if self.async_autostart:
            self.start()
        return req.future

    def submit_coalesced(
        self,
        queries: Sequence[JoinQuery],
        lam: Optional[int] = None,
        materialize: bool = True,
        fuse_semijoin: Optional[bool] = None,
        deadline_s: Optional[float] = None,
    ) -> List[SessionResult]:
        """Answer several queries through ONE coalesced scheduler pass.

        The synchronous twin of draining ``len(queries)`` concurrent
        :meth:`submit_async` requests in one batch (and the deterministic
        seam the tests use): same grouping by
        :func:`~repro.mpc.program.coalesce_signature`, same identical-
        submission dedup, same demux.  Results are in submission order and
        byte-identical to one :meth:`submit` per query.  The first member's
        failure raises (traceback preserved); per-member outcomes are
        available through :meth:`submit_async` instead."""
        share: Dict = {"scatter": {}, "unique": {}}
        reqs = [
            _Request(
                query=q, lam=lam, materialize=materialize,
                fuse_semijoin=fuse_semijoin, batch=share,
                deadline=self._abs_deadline(deadline_s),
            )
            for q in queries
        ]
        with span("service.submit", requests=[r.rid for r in reqs]):
            outs = self._execute_batch(reqs)
        for out in outs:
            if isinstance(out, BaseException):
                raise out.with_traceback(out.__traceback__)
        return outs

    @staticmethod
    def _abs_deadline(deadline_s: Optional[float]) -> Optional[float]:
        """Relative budget (seconds) → absolute ``time.monotonic`` instant."""
        return None if deadline_s is None else time.monotonic() + deadline_s

    def start(self) -> None:
        """Start the drainer thread (idempotent; ``submit_async`` autostarts
        unless the session was built with ``async_autostart=False``).  A
        degraded session refuses — :meth:`restart` is the supervised path
        back."""
        if self._degraded_cause is not None:
            raise DegradedSessionError(
                "session is degraded (drainer crashed); call restart()",
                cause=self._degraded_cause,
            )
        if self._drainer is None or not self._drainer.is_alive():
            self._drainer = threading.Thread(
                target=self._drain_loop, name="join-session-drainer", daemon=True
            )
            self._drainer.start()

    @property
    def degraded(self) -> bool:
        """True after a drainer crash, until :meth:`restart`."""
        return self._degraded_cause is not None

    def restart(self) -> None:
        """Supervised recovery from a drainer crash: clear the degraded
        state, reset the straggler monitor's latency model (post-fault
        batches shouldn't be judged against a pre-fault EMA), and start a
        fresh drainer.  Executor caches are untouched — anything a failed
        attempt poisoned was already quarantined when it failed."""
        if self._closed:
            raise JoinServiceError("cannot restart a closed session")
        self._degraded_cause = None
        self._monitor.reset()
        self.start()

    def close(self, wait: bool = True) -> None:
        """Stop accepting async submits and drain what's already queued.

        With a live drainer the shutdown sentinel is enqueued and (when
        ``wait``) joined; afterwards — and for drainer-less
        (``async_autostart=False``) or degraded sessions — any request still
        queued is swept so **every admitted request resolves exactly once**:
        executed inline on a healthy session, failed with
        :class:`~repro.mpc.faults.DegradedSessionError` on a degraded one."""
        if self._closed:
            return
        self._closed = True
        if self._drainer is not None and self._drainer.is_alive():
            self._queue.put(_SHUTDOWN)
            if not wait:
                return
            self._drainer.join()
        # sweep whatever is still queued (race leftovers, degraded-session
        # backlog, drainer-less sessions) in queue order
        pending: List[_Request] = []
        while True:
            try:
                item = self._queue.get_nowait()
            except queue_mod.Empty:
                break
            if item is not _SHUTDOWN:
                pending.append(item)
        if self._degraded_cause is not None:
            err = DegradedSessionError(
                "session closed while degraded (drainer crashed)",
                cause=self._degraded_cause,
            )
            for req in pending:
                if self._resolve(req, err):
                    self.stats.failed += 1
            return
        while pending:
            batch, pending = pending[: self.max_coalesce], pending[self.max_coalesce:]
            self._process(batch)

    def __enter__(self) -> "JoinSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _drain_loop(self) -> None:
        """Drainer: block on the queue, then coalesce everything already
        waiting (up to ``max_coalesce``) into one batch.  Natural batching —
        under light load batches are singletons and latency is a serial
        submit's; under burst load the batch grows and the per-dispatch cost
        amortizes across it.

        Supervision: the loop body is guarded — any exception escaping it
        (``_process`` itself never raises; this is the heartbeat/injection
        window between dequeue and demux) degrades the session via
        :meth:`_enter_degraded` instead of leaking a dead thread with hung
        futures.  Each batch beats the optional heartbeat file and feeds the
        straggler monitor."""
        while True:
            item = self._queue.get()
            if item is _SHUTDOWN:
                return
            batch = [item]
            stop = False
            while len(batch) < self.max_coalesce:
                try:
                    nxt = self._queue.get_nowait()
                except queue_mod.Empty:
                    break
                if nxt is _SHUTDOWN:
                    stop = True
                    break
                batch.append(nxt)
            try:
                seq = self._batch_seq
                self._batch_seq = seq + 1
                if self._heartbeat is not None:
                    self._heartbeat.beat(seq)
                if self.fault_plan is not None:
                    self.fault_plan.at_drainer()
                t0 = time.perf_counter()
                self._process(batch)
                if self._monitor.record(seq, time.perf_counter() - t0):
                    self.stats.slow_batches += 1
            except BaseException as e:
                self._enter_degraded(e, batch)
                return
            if stop:
                return

    def _enter_degraded(self, cause: BaseException, inflight: List[_Request]) -> None:
        """Drainer-crash path: resolve the in-flight batch AND everything
        still queued with :class:`~repro.mpc.faults.DegradedSessionError`
        (zero hung futures), then flip the session degraded so new
        :meth:`submit_async` calls fail fast until :meth:`restart`."""
        self._degraded_cause = cause
        self.stats.drainer_crashes += 1
        err = DegradedSessionError(
            f"session drainer crashed: {cause!r}", cause=cause
        )
        pending = list(inflight)
        while True:
            try:
                item = self._queue.get_nowait()
            except queue_mod.Empty:
                break
            if item is not _SHUTDOWN:
                pending.append(item)
        for req in pending:
            if self._resolve(req, err):
                self.stats.failed += 1

    @staticmethod
    def _resolve(req: _Request, out) -> bool:
        """Resolve a request's future exactly once; True if this call did it.

        The done() guard (plus the InvalidStateError backstop for the racing
        case) is what makes crash paths safe to run concurrently with the
        normal demux — a future can only ever carry one outcome."""
        fut = req.future
        if fut is None or fut.done():
            return False
        try:
            if isinstance(out, BaseException):
                fut.set_exception(out)
            else:
                fut.set_result(out)
        except Exception:       # InvalidStateError: someone else won the race
            return False
        return True

    def _process(self, batch: List[_Request]) -> None:
        """Execute one drain batch and resolve its futures (never raises —
        a drainer must survive any single request's failure)."""
        now = time.perf_counter()
        queued = " ".join(
            str(round((now - r.t_enqueue) * 1e6)) if r.t_enqueue is not None else "0"
            for r in batch
        )
        with span("service.batch", requests=[r.rid for r in batch], queued_us=queued):
            try:
                outs = self._execute_batch(batch)
            except BaseException as e:  # defensive: _execute_batch reports per-request
                outs = [e] * len(batch)
            with span("service.resolve"):
                for req, out in zip(batch, outs):
                    self._resolve(req, out)

    # -- the shared execution path --------------------------------------------

    def _prepare(self, req: _Request, share: Dict) -> None:
        """Phase 1 of a submit: histogram, plan-cache lookup, compile on miss.

        Fills the request in place; any failure lands in ``req.error`` so one
        bad query never poisons the rest of a coalesced batch."""
        try:
            fuse = (
                self.fuse_semijoin
                if req.fuse_semijoin is None
                else req.fuse_semijoin
            )
            lam, stats = req.lam, req.stats
            if lam is None:
                # only the λ default needs ρ — keep the LP solve off the
                # explicit-λ hot path (steady-state submits must be
                # dispatch-only)
                if stats is not None:
                    lam = stats.lam
                else:
                    lam = heavy_parameter(self.p, float(rho(req.query)))

            with span("planner.stats", request=req.rid) as sp:
                if self.backend == "simulator":
                    sim = MPCSimulator(self.p, seed=self.seed)
                    executor: object = SimulatorExecutor(sim, seed=self.seed)
                    executor.place_inputs(req.query, scatter_cache=share.get("scatter"))
                    if stats is None:
                        stats = distributed_stats(sim, req.query, lam)
                else:
                    executor = self.executor
                    if stats is None:
                        stats = compute_stats(
                            req.query, lam, unique_memo=share.get("unique")
                        )
            req.stats_us = sp.us

            key = plan_cache_key(req.query, stats, self.p, req.h_subsets, fuse)
            cached = self._plans.get(key)
            if cached is not None:
                self._plans.move_to_end(key)
                req.program = cached.rebind(req.query)
                self.stats.plan_hits += 1
                if self.verify:
                    # warm path: the cached plan was fully verified when it
                    # was compiled; only the fresh bindings need re-checking.
                    with span("planner.verify", request=req.rid) as sp:
                        verify_bindings(req.program)
                    req.verify_us = sp.us
            else:
                with span("planner.compile", request=req.rid) as sp:
                    req.program = compile_plan(
                        req.query, stats, self.p,
                        h_subsets=req.h_subsets, fuse_semijoin=fuse,
                        verify=False,  # timed separately below
                    )
                req.compile_us = sp.us
                if self.verify:
                    with span("planner.verify", request=req.rid) as sp:
                        verify_program(
                            req.program,
                            caps=getattr(executor, "_learned_caps", None),
                        )
                    req.verify_us = sp.us
                    req.verified = True
                # cache plan metadata only: the concrete relations are rebound
                # on every hit, so pinning the first submitter's tuple data in
                # the LRU would retain up to plan_cache_size tables for no
                # reader
                self._plans[key] = replace(req.program, query=None)
                self.stats.plan_misses += 1
                while len(self._plans) > self.plan_cache_size:
                    self._plans.popitem(last=False)
                    self.stats.plan_evictions += 1
            req.executor = executor
            req.plan_key = key
            req.plan_cache_hit = cached is not None
        except BaseException as e:
            req.error = e

    def _execute_batch(
        self, reqs: List[_Request]
    ) -> List[Union[SessionResult, BaseException]]:
        """Prepare, group, run, and demux one batch of requests.

        Grouping (dataplane only; the simulator backend runs serially — each
        query owns a metered simulator):

          1. requests are grouped by ``(coalesce_signature(program),
             materialize)`` — the bucket-compatibility rule: equal signatures
             mean identical op sequences and matching stage-geometry
             histograms, so the group shares one ``run_many`` scheduler pass;
          2. within a group, requests with identical *executions* — equal
             plan key AND the same bound table objects — deduplicate: one
             representative runs, the duplicates share its result (the
             ``deduped`` counter; results are read-only).

        Scheduler counters (dispatches, jit, caps, retries) aggregate into
        :attr:`stats` once per ``run_many`` call — they are batch-level, so
        summing them per member would multi-count."""
        with self._lock:
            t_batch = time.perf_counter()
            share = (
                reqs[0].batch
                if len(reqs) == 1 and reqs[0].batch is not None
                else (reqs[0].batch or {"scatter": {}, "unique": {}})
            )
            for req in reqs:
                self._prepare(req, req.batch if req.batch is not None else share)

            # deadline admission: a request already past its budget (e.g. it
            # queued behind a slow batch) fails cheaply before any dispatch
            now = time.monotonic()
            for req in reqs:
                if (
                    req.error is None
                    and req.deadline is not None
                    and now > req.deadline
                ):
                    req.error = DeadlineExceededError(
                        f"query {describe_query(req.query)} exceeded its "
                        "deadline before execution",
                        query=req.query, deadline_s=req.deadline,
                    )

            live = [r for r in reqs if r.error is None]
            outs: Dict[int, Union[SessionResult, BaseException]] = {}

            if self.backend == "simulator" or self.executor is None:
                for req in live:
                    t0 = time.perf_counter()
                    try:
                        res = req.executor.run(
                            req.program, materialize=req.materialize
                        )
                    except BaseException as e:
                        req.error = e
                        continue
                    execute_us = (time.perf_counter() - t0) * 1e6
                    self.stats.jit_hits += getattr(res, "jit_cache_hits", 0)
                    self.stats.jit_misses += getattr(res, "jit_cache_misses", 0)
                    self.stats.retries += getattr(res, "retries", 0)
                    outs[id(req)] = self._wrap(
                        req, res, execute_us, len(reqs), coalesced=False,
                        deduplicated=False,
                    )
            else:
                # group by bucket compatibility, preserving submission order
                groups: "OrderedDict[Tuple, List[_Request]]" = OrderedDict()
                for req in live:
                    gkey = (coalesce_signature(req.program), req.materialize)
                    groups.setdefault(gkey, []).append(req)
                for members in groups.values():
                    # identical-submission dedup: same plan key + same bound
                    # table objects ⇒ same bytes out, so run once and share
                    reps: List[_Request] = []
                    assign: List[int] = []
                    seen: Dict[Tuple, int] = {}
                    for req in members:
                        dk = (
                            req.plan_key,
                            tuple(id(r.data) for r in req.query.relations),
                        )
                        if dk in seen:
                            assign.append(seen[dk])
                            self.stats.deduped += 1
                        else:
                            seen[dk] = len(reps)
                            assign.append(len(reps))
                            reps.append(req)
                    deadlines = [r.deadline for r in reps if r.deadline is not None]
                    t0 = time.perf_counter()
                    try:
                        results, bstats = self.executor.run_many(
                            [r.program for r in reps],
                            config=RunConfig(
                                materialize=members[0].materialize,
                                deadline=min(deadlines) if deadlines else None,
                                fault_plan=self.fault_plan,
                            ),
                        )
                    except BaseException as e:
                        if len(reps) == 1:
                            for req in members:
                                req.error = e
                        else:
                            # coalesced-group failure isolation: the fused
                            # dispatch is all-or-nothing, so fall back to
                            # per-member serial runs — the poisoned member
                            # fails alone and its batchmates still produce
                            # the exact bytes a serial submit would have
                            # (salts never depend on coalescing)
                            self.stats.degraded_fallbacks += 1
                            self._run_serial_fallback(members, reps, assign, outs, len(reqs))
                        continue
                    execute_us = (time.perf_counter() - t0) * 1e6
                    self._absorb(bstats)
                    coalesced = len(members) > 1
                    with span("service.resolve"):
                        for req, ri in zip(members, assign):
                            outs[id(req)] = self._wrap(
                                req, results[ri], execute_us, len(reqs),
                                coalesced=coalesced,
                                deduplicated=(req is not reps[ri]),
                            )

            if len(reqs) > 1:
                self.stats.coalesced_batches += 1
                self.stats.coalesced_queries += len(reqs)
                self.stats.max_coalesced_batch = max(
                    self.stats.max_coalesced_batch, len(reqs)
                )
            self.stats.cached_plans = len(self._plans)
            if self.executor is not None:
                # mirror of the executor's lifetime quarantine counter (the
                # per-run count is unavailable when the run itself raised)
                self.stats.quarantined_caps = self.executor.caps_quarantined

            t_done = time.perf_counter()
            final: List[Union[SessionResult, BaseException]] = []
            for req in reqs:
                if req.error is not None:
                    err = self._typed_error(req)
                    req.error = err
                    self.stats.failed += 1
                    if isinstance(err, DeadlineExceededError):
                        self.stats.deadline_exceeded += 1
                    # plan quarantine: the compiled program a failed attempt
                    # used is dropped from the LRU — if the failure was the
                    # plan's fault (stale histogram, planner bug), the next
                    # submit recompiles instead of re-failing forever
                    if (
                        req.plan_key is not None
                        and self._plans.pop(req.plan_key, None) is not None
                    ):
                        self.stats.quarantined_plans += 1
                        self.stats.cached_plans = len(self._plans)
                    final.append(err)
                    continue
                out = outs[id(req)]
                if req.t_enqueue is not None:
                    out.queue_us = max(0.0, (t_batch - req.t_enqueue) * 1e6)
                    out.e2e_us = (t_done - req.t_enqueue) * 1e6
                    self.stats.e2e_us.append(out.e2e_us)
                if self.slo_target_us is not None:
                    lat = out.e2e_us if req.t_enqueue is not None else out.total_us
                    if lat <= self.slo_target_us:
                        self.stats.slo_ok += 1
                    else:
                        self.stats.slo_violations += 1
                final.append(out)
            return final

    def _absorb(self, bstats) -> None:
        """Aggregate one ``run_many`` call's batch-level counters into
        :attr:`stats` (exactly once per scheduler pass)."""
        self.stats.jit_hits += bstats.jit_cache_hits
        self.stats.jit_misses += bstats.jit_cache_misses
        self.stats.retries += bstats.retries
        self.stats.caps_hits += bstats.caps_hits
        self.stats.caps_misses += bstats.caps_misses
        self.stats.caps_evictions += bstats.caps_evictions

    def _run_serial_fallback(
        self,
        members: List[_Request],
        reps: List[_Request],
        assign: List[int],
        outs: Dict,
        batch_size: int,
    ) -> None:
        """The group-isolation fallback ladder, rung 2: after a fused
        coalesced dispatch failed, run each deduplicated representative as
        its own serial scheduler pass (own deadline, fault plan still
        active).  Only the members whose representative fails get an error;
        everyone else's rows are byte-identical to a fault-free serial
        submit because routing salts derive from the query-unqualified stage
        key, never from the batch shape."""
        rep_out: List = []
        for rep in reps:
            t1 = time.perf_counter()
            try:
                res_list, bstats = self.executor.run_many(
                    [rep.program],
                    config=RunConfig(
                        materialize=rep.materialize,
                        deadline=rep.deadline,
                        fault_plan=self.fault_plan,
                    ),
                )
            except BaseException as e:
                rep_out.append(e)
                continue
            self._absorb(bstats)
            rep_out.append((res_list[0], (time.perf_counter() - t1) * 1e6))
        for req, ri in zip(members, assign):
            o = rep_out[ri]
            if isinstance(o, BaseException):
                req.error = o
            else:
                res, ex_us = o
                outs[id(req)] = self._wrap(
                    req, res, ex_us, batch_size,
                    coalesced=False, deduplicated=(req is not reps[ri]),
                )

    def _typed_error(self, req: _Request) -> JoinServiceError:
        """Map a request's raw failure onto the taxonomy, always naming the
        query and always chaining the root cause's traceback."""
        e = req.error
        if isinstance(e, DeadlineExceededError):
            if e.query is None:
                out = DeadlineExceededError(
                    f"query {describe_query(req.query)}: {e}",
                    query=req.query, op_round=e.op_round,
                    deadline_s=e.deadline_s,
                )
                out.__cause__ = e
                return out
            return e
        if isinstance(
            e,
            (
                QueryFailedError,
                DegradedSessionError,
                AdmissionError,
                ProgramVerificationError,
            ),
        ):
            return e
        return QueryFailedError(
            req.query, e, attempt_log=getattr(e, "attempt_log", ())
        )

    def _wrap(
        self,
        req: _Request,
        res: Union[MPCJoinResult, DataplaneJoinResult],
        execute_us: float,
        batch_size: int,
        coalesced: bool,
        deduplicated: bool,
    ) -> SessionResult:
        total_us = req.stats_us + req.compile_us + req.verify_us + execute_us
        self.stats.submits += 1
        if req.verified:
            self.stats.verified += 1
        self.stats.verify_us += req.verify_us
        (self.stats.warm_us if req.plan_cache_hit else self.stats.cold_us).append(
            total_us
        )
        return SessionResult(
            result=res,
            plan_key=req.plan_key,
            plan_cache_hit=req.plan_cache_hit,
            stats_us=req.stats_us,
            compile_us=req.compile_us,
            execute_us=execute_us,
            total_us=total_us,
            coalesced=coalesced,
            batch_size=batch_size,
            deduplicated=deduplicated,
            verified=req.verified,
            verify_us=req.verify_us,
            request_id=req.rid,
        )

    # -- batch entry ----------------------------------------------------------

    def submit_batch(
        self,
        queries: Sequence[JoinQuery],
        lam: Optional[int] = None,
        materialize: bool = True,
        fuse_semijoin: Optional[bool] = None,
    ) -> List[SessionResult]:
        """Answer a batch of queries serially, sharing per-table work.

        Queries binding the same physical ``Relation.table`` share one device
        placement: on the simulator backend the first query's seeded scatter
        shuffle is installed verbatim into every later query's simulator
        (bit-identical to re-scattering — ``scatter_input`` is deterministic);
        on the dataplane backend the histogram's per-(table, column)
        unique-count pass — the sort-dominated part of ``compute_stats`` — is
        computed once per table.  Results are identical to one
        :meth:`submit` per query, in order.  (For a *coalesced* batch — one
        scheduler pass for the whole set — see :meth:`submit_coalesced`.)

        Returns: one :class:`SessionResult` per query, in submission order.
        """
        batch: Dict = {"scatter": {}, "unique": {}}
        return [
            self.submit(
                q,
                lam=lam,
                materialize=materialize,
                fuse_semijoin=fuse_semijoin,
                _batch=batch,
            )
            for q in queries
        ]

    # -- pattern entry (subgraph enumeration) ---------------------------------

    def submit_pattern(
        self,
        pattern,
        graph,
        lam: Optional[int] = None,
        orientation: str = "degree",
        fuse_semijoin: Optional[bool] = None,
    ):
        """Enumerate ``pattern`` in ``graph`` through this session.

        The session-backed twin of
        :func:`repro.graph.enumerate.enumerate_subgraphs`: the pattern is
        compiled to a shared-table :class:`JoinQuery`, submitted (hitting the
        plan cache when the graph's histogram signature is unchanged — e.g.
        the same pattern re-run, or re-run after an edge batch that didn't
        shift any heavy value), and post-processed into exactly-once
        occurrences.

        Returns: an :class:`repro.graph.enumerate.EnumerationResult`.
        """
        from ..graph.enumerate import enumerate_subgraphs

        return enumerate_subgraphs(
            graph,
            pattern,
            p=self.p,
            lam=lam,
            orientation=orientation,
            fuse_semijoin=(
                self.fuse_semijoin if fuse_semijoin is None else fuse_semijoin
            ),
            session=self,
        )

    # -- cache control --------------------------------------------------------

    def clear_plans(self) -> None:
        """Drop every cached compiled program (executor state is kept)."""
        self._plans.clear()
        self.stats.cached_plans = 0

    @property
    def cached_plan_keys(self) -> List[Tuple]:
        """Plan-LRU keys, oldest first (testing/observability)."""
        return list(self._plans.keys())
