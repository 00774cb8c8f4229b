"""Manager — the demand-driven scheduler of the runtime (paper §II: RTF
execution model), with the fault-tolerance features a 1000-node deployment
needs:

* demand-driven dispatch — Workers receive the next bucket when free
  (natural load balancing, same as the paper's 92%-efficiency runs);
* heartbeats + retry — a bucket whose Worker misses its heartbeat deadline
  is re-enqueued (at-least-once; results are idempotent because tasks are
  pure functions of (input, params)); the deadline adapts to observed
  bucket times of the item's own kind (``WorkItem.kind``, the stage) so a
  long-running bucket (e.g. a first-time jit compile) is not mistaken for
  a dead Worker, and a lease whose Worker is *provably* dead (a killed
  worker process) is re-enqueued immediately;
* straggler mitigation — when the queue is empty and a bucket has been
  running longer than ``straggler_factor`` × the median time of its kind,
  a backup copy is launched on an idle Worker; first completion wins (the
  classic demand-driven tail-cloning trick);
* elastic scaling — Workers can join/leave between buckets; the Manager
  only tracks outstanding leases.

Since DESIGN.md §13 the Manager is a **pure scheduler/bookkeeper**: it owns
the queue, lease table, retry/backup policy and result memoisation, and
executes nothing itself. Execution happens behind the
:class:`~repro.runtime.transport.WorkerBackend` protocol — ``Manager()``
defaults to a :class:`~repro.runtime.transport.ThreadBackend` (the
historical in-process Worker pool), and ``Manager(backend=
ProcessRpcBackend(...))`` drives real worker processes through the same
scheduling semantics, results crossing the boundary only as SharedStore
keys. A single pump thread drives the loop: poll completions → settle/fail
→ expire dead/stale leases → offer leases to free workers.

Sessions are **long-lived** (DESIGN.md §10): ``start`` spawns the Worker
pool once, ``submit`` is legal while Workers are running (including from a
completion callback), ``drain`` blocks until every submitted item has a
result, and ``close`` retires the pool — idempotent, callable from any
thread, and safe to race with ``drain`` (an explicit guarded state
transition, not thread-join ordering). The one-shot ``run`` wrapper keeps
the original batch semantics on top of the same machinery. Per-item
completion callbacks fire exactly once per key — on the *first* completion,
under the same lock that records the result — so a raced straggler backup
can never double-report; the callback body runs outside the lock so it may
re-enter ``submit`` (how the streaming executor chains per-input stage
edges).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from repro.runtime.fairshare import FairQueue, TaskCancelled
from repro.runtime.transport import (
    Completion,
    Lease,
    RemoteTaskError,
    WorkerStatus,
    make_backend,
)
from repro.tracing import span

__all__ = ["WorkItem", "Manager", "TaskCancelled", "run_study_distributed"]

# How long the pump blocks per completion poll; bounds the latency of
# straggler/heartbeat detection while the system is idle.
_IDLE_TICK = 0.02
# Parked-pump wake cadence: an idle pool still owes the backend a slow
# heartbeat-frame drain (worker stats ride heartbeats, and a straggler
# lease orphaned by cancel/resubmit completes late and must be consumed)
# — so the park is a timed wait, ~25x sparser than the busy-poll tick.
_PARK_TICK = 0.5

# A worker heartbeat younger than this proves its leases live (only for
# backends whose heartbeats keep flowing mid-task); staler workers fall
# back to age-based expiry, so a wedged-but-running process still recovers.
_LIVENESS_FRESH = 5.0

# Session states — the explicit close()/drain() transition guard.
_NEW, _RUNNING, _CLOSING, _CLOSED = "new", "running", "closing", "closed"


@dataclasses.dataclass
class WorkItem:
    key: str
    fn: Optional[Callable[[], Any]] = None
    attempts: int = 0
    started_at: Optional[float] = None
    # Called exactly once, as fn's first completion (or permanent failure,
    # with the Exception as the value) is recorded. Runs on the Manager's
    # pump thread, outside the Manager lock.
    callback: Optional[Callable[[str, Any], None]] = None
    # Picklable task description for backends that cross a process
    # boundary (transport.Lease ships it; fn never leaves this process).
    spec: Optional[tuple] = None
    # Attempt number this key's CURRENT lifecycle started from. Nonzero
    # only after a forgotten key is resubmitted while a prior lifecycle's
    # lease still ran: attempt numbers stay monotonic per key (so lease
    # ids never collide across lifecycles) and the retry budget is
    # measured from this base instead of zero.
    attempt_base: int = 0
    # Fair-share class (DESIGN.md §18): the dispatch queue deficit-round-
    # robins across tenants, so one tenant's backlog cannot starve another.
    # "" is the shared default class (single-study sessions stay pure FIFO).
    tenant: str = ""
    # Within-tenant dispatch priority: higher first, FIFO within a level.
    priority: int = 0
    # Content-addressed sharing (the service's cross-tenant reuse): a shared
    # submission of a key that is already pending SUBSCRIBES its callback to
    # the in-flight lifecycle instead of enqueueing a duplicate execution,
    # and a shared submission of a settled key is served the memoised value
    # immediately. Requires keys derived from task CONTENT, so identical
    # keys always denote identical pure work.
    shared: bool = False
    # Cost class for the heartbeat-expiry and straggler heuristics: a lease
    # is judged only against completed durations of its own kind, and a
    # kind with no completion yet is never age-expired or cloned — its
    # first bucket may be a multi-minute jit compile. The streaming
    # executor passes the stage name, so a cheap stage's median cannot
    # condemn a costly stage's bucket.
    kind: str = ""

    def next_attempt(self) -> "WorkItem":
        """A queue entry for another attempt of this item (retry, expiry
        re-enqueue or backup clone): same work and class, no lease
        state and no callback (callbacks live in the Manager's table)."""
        return WorkItem(key=self.key, fn=self.fn, spec=self.spec,
                        attempt_base=self.attempt_base, tenant=self.tenant,
                        priority=self.priority, kind=self.kind)


class Manager:
    # Total Worker-pool sessions ever started in this process; the
    # differential suite uses deltas of this to prove execute_study spins up
    # ONE session per study instead of one per stage×input.
    sessions_started = 0

    def __init__(
        self,
        *,
        backend: Any = None,
        max_attempts: int = 3,
        heartbeat_timeout: float = 60.0,
        straggler_factor: float = 3.0,
        enable_backup_tasks: bool = True,
    ):
        self._backend = make_backend(backend)
        # worker_id -> attempt-seconds it has executed (all attempts, both
        # outcomes) — the per-worker occupancy the benchmark reports.
        self._worker_busy: Dict[int, float] = {}  # guard: _lock
        self._n_workers = 0  # guard: _lock
        self._pump_busy = 0.0  # guard: _lock — pump seconds spent doing work
        # Idle-pool accounting (DESIGN.md §18): seconds the pump has
        # spent parked on the condition variable with zero pending work, and
        # the start of an in-progress park — scheduler_stats subtracts this
        # from wall time so idle fractions stay honest across the many-job
        # lifetime of a long-lived service session.
        self._pump_parked = 0.0  # guard: _lock
        self._parked_since: Optional[float] = None  # guard: _lock
        self._session_t0: Optional[float] = None  # guard: _lock
        self._session_t1: Optional[float] = None  # guard: _lock
        self._queue: FairQueue = FairQueue()  # guard: _lock
        self._results: Dict[str, Any] = {}  # guard: _lock
        self._running: Dict[str, WorkItem] = {}  # guard: _lock
        self._attempt_seq: Dict[str, int] = {}  # guard: _lock — highest attempt # issued per key
        # key -> callbacks subscribed to its first completion. A list, not a
        # single slot: shared (content-addressed) submissions subscribe many
        # jobs to one lifecycle; every callback fires exactly once.
        self._callbacks: Dict[str, List[Callable[[str, Any], None]]] = {}  # guard: _lock
        self._pending: set = set()  # guard: _lock — keys submitted, no result yet
        # Keys forgotten while still holding a lease: their bookkeeping is
        # kept for first-completion-wins dedup and released when the last
        # lease settles (drained in _settle), so a long-lived fleet session
        # stays bounded even when forget() races in-flight attempts.
        self._deferred_forget: set = set()  # guard: _lock
        # Lease ids stranded by a key's resubmission (a new lifecycle began
        # while the old lifecycle's attempt still ran): their completions
        # must not settle the new lifecycle, so they are dropped on arrival.
        self._orphaned: set = set()  # guard: _lock
        # Per-kind recent windows of winning-attempt durations for the
        # straggler / heartbeat heuristics: bounded so a session spanning
        # thousands of inputs never grows the median computation, with each
        # sorted median cached between appends (the pump polls every tick).
        self._durations: Dict[str, "collections.deque[float]"] = {}  # guard: _lock
        self._median_cache: Dict[str, float] = {}  # guard: _lock
        self._busy_total = 0.0  # guard: _lock — lifetime sum (the efficiency numerator)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._pump_thread: Optional[threading.Thread] = None
        self._state = _NEW  # guard: _lock
        self.max_attempts = max_attempts
        self.heartbeat_timeout = heartbeat_timeout
        self.straggler_factor = straggler_factor
        self.enable_backup_tasks = enable_backup_tasks
        self.retries = 0  # guard: _lock
        self.backups_launched = 0  # guard: _lock
        self.heartbeat_expiries = 0  # guard: _lock
        self.cancelled = 0  # guard: _lock — keys revoked via cancel()
        # Leases handed to each backend (keyed by backend name) over this
        # Manager's lifetime — the per-backend dispatch accounting surfaced
        # by study summaries.
        self.dispatch_counts: Dict[str, int] = {}  # guard: _lock
        # Leases minted per fair-share tenant — the service/benchmark proof
        # that deficit-round-robin actually shares the dispatch path.
        self.tenant_dispatch: Dict[str, int] = {}  # guard: _lock

    @property
    def backend(self):
        """The WorkerBackend this session dispatches through."""
        return self._backend

    @property
    def backend_name(self) -> str:
        return getattr(self._backend, "name", type(self._backend).__name__)

    @property
    def is_running(self) -> bool:
        """True between ``start`` and the completion of ``close`` — i.e.
        the session can still execute work."""
        # analysis: ok[locks] deliberately lock-free status probe; _state is
        # a small int and a stale answer is as good as one a tick later
        return self._state in (_RUNNING, _CLOSING)

    @property
    def busy_seconds(self) -> float:
        """Sum of winning-attempt wall-times — the useful-work numerator of
        the parallel-efficiency accounting. Where tasks launch programs on
        an asynchronous device (a TPU), an attempt's wall-time is the launch
        plus any wait on PJRT's limit on programs in flight, not device
        work; the device time of each task comes from a profiler trace
        joined to the task spans (``sa/task/*``)."""
        with self._lock:
            return self._busy_total

    def scheduler_stats(self) -> Dict[str, Any]:
        """Snapshot of the scheduler's health: pump occupancy (the fraction
        of active wall-time the pump spent doing scheduling work), parked
        and active wall seconds, and per-worker busy seconds / mean idle
        fraction. ``worker_busy_seconds`` sums attempt wall-times, which on
        an asynchronous device time the launch plus any wait on PJRT's
        in-flight limit, not device work (see :attr:`busy_seconds`)."""
        now = time.monotonic()
        with self._lock:
            t0 = self._session_t0
            t1 = self._session_t1 if self._session_t1 is not None else now
            wall = max(t1 - t0, 1e-9) if t0 is not None else 0.0
            parked = self._pump_parked
            if self._parked_since is not None and self._session_t1 is None:
                parked += now - self._parked_since
            # Idle fractions are measured against ACTIVE wall — session
            # wall minus the time the pump sat parked with zero pending
            # work — so a long-lived session that served three jobs over
            # an hour reports how busy the workers were while there WAS
            # work, not how empty the hour was.
            active = max(wall - parked, 0.0)
            denom = active if active > 1e-9 else wall
            worker_busy = dict(self._worker_busy)
            n_workers = max(1, self._n_workers)
            stats: Dict[str, Any] = {
                "pump_occupancy": self._pump_busy / denom if denom else 0.0,
                "pump_parked_seconds": parked,
                "active_wall_seconds": active,
                "worker_busy_seconds": worker_busy,
                "worker_idle_fraction": (
                    min(
                        1.0,
                        max(
                            0.0,
                            1.0
                            - sum(worker_busy.values()) / (denom * n_workers),
                        ),
                    )
                    if denom
                    else 0.0
                ),
                "wall_seconds": wall,
                "cancelled": self.cancelled,
                "tenant_dispatch": dict(self.tenant_dispatch),
                "tenant_depths": self._queue.depths(),
            }
        return stats

    def _record_duration_locked(self, kind: str, dur: float) -> None:
        window = self._durations.get(kind)
        if window is None:
            window = self._durations[kind] = collections.deque(maxlen=512)
        window.append(dur)
        self._median_cache.pop(kind, None)

    def _median_locked(self, kind: str) -> Optional[float]:
        """Median completed duration of ``kind``; None before its first
        completion."""
        window = self._durations.get(kind)
        if not window:
            return None
        if kind not in self._median_cache:
            ordered = sorted(window)
            self._median_cache[kind] = ordered[len(ordered) // 2]
        return self._median_cache[kind]

    # ------------------------------------------------------------------
    # Session lifecycle
    # ------------------------------------------------------------------
    def start(self, n_workers: int) -> None:
        """Spawn the Worker pool through the backend and start the pump.
        One session may span many stages and many inputs; submitting while
        Workers run is the intended usage."""
        with self._cond:
            if self._state in (_RUNNING, _CLOSING):
                raise RuntimeError("Manager session already started")
            prev = self._state
            self._state = _RUNNING
        try:
            self._backend.start(max(1, n_workers))
        except BaseException:
            with self._cond:  # roll back: no zombie "running" session with
                self._state = prev  # no pump to ever settle submissions
                self._cond.notify_all()
            raise
        Manager.sessions_started += 1
        n_live = len(self._backend.heartbeat_view())
        with self._lock:
            self._n_workers = n_live or max(1, n_workers)
            self._session_t0 = time.monotonic()
            self._session_t1 = None
        self._pump_thread = threading.Thread(target=self._pump, daemon=True)
        self._pump_thread.start()

    def submit(self, item: WorkItem) -> None:
        """Enqueue work; legal before ``start`` and while Workers run.
        Re-submitting a key that already has a result is a no-op — EXCEPT
        when that result is a stale memo retained only for a forgotten
        key's still-running lease (deferred forget): the caller has ended
        that lifecycle, so this submission starts a NEW one. The stale
        memo is released, the old lifecycle's leases are orphaned (their
        completions are dropped on arrival — they may have run under a
        different scope, so their values must never settle this
        lifecycle), and attempt numbering continues from the old high
        water mark so lease ids stay unique across lifecycles.

        ``item.shared`` opts into **content-addressed sharing** (DESIGN.md
        §18): a shared submission of a key already pending subscribes its
        callback to the in-flight lifecycle (no duplicate execution), and
        a shared submission of a settled key is served the memoised value
        immediately — the mechanism by which N tenants submitting
        identical pure work pay for it once."""
        memo_value: Any = None
        serve_memo = False
        with self._cond:
            if self._state in (_CLOSING, _CLOSED):
                raise RuntimeError("Manager session is closed")
            if item.key in self._deferred_forget:
                self._deferred_forget.discard(item.key)
                self._results.pop(item.key, None)
                self._callbacks.pop(item.key, None)
                for lid in [
                    lid for lid, it in self._running.items() if it.key == item.key
                ]:
                    self._orphaned.add(lid)
                    del self._running[lid]
                # queued duplicates (heartbeat-expiry re-enqueues racing in
                # after forget) carry the OLD lifecycle's closure — purge them
                self._queue.remove_keys({item.key})
                item.attempt_base = self._attempt_seq.get(item.key, 0)
            if item.key in self._results:
                if item.shared and item.callback is not None:
                    # served the live memo below, OUTSIDE the lock — the
                    # callback may re-enter submit()
                    serve_memo = True
                    memo_value = self._results[item.key]
                # historical contract: non-shared resubmit of a settled
                # key is a silent no-op
            elif (
                item.shared
                and item.key in self._pending
            ):
                # subscribe to the in-flight lifecycle: exactly-once per
                # subscriber, zero duplicate execution
                if item.callback is not None:
                    self._callbacks.setdefault(item.key, []).append(
                        item.callback
                    )
            else:
                if item.callback is not None:
                    if item.shared:
                        self._callbacks.setdefault(item.key, []).append(
                            item.callback
                        )
                    else:
                        # historical single-slot semantics: the latest
                        # non-shared submission's callback wins
                        self._callbacks[item.key] = [item.callback]
                self._pending.add(item.key)
                self._queue.append(item)
                self._cond.notify_all()
        if serve_memo:
            item.callback(item.key, memo_value)

    def drain(self) -> None:
        """Block until every submitted key has a result (success or
        permanent failure). Workers stay alive — more work may follow.

        When the backend acknowledges completions ahead of their disk
        commit (``async_commit``), drain is also the durability point: it
        invokes the backend's ``barrier()`` so that after it returns, every
        result is resolvable from the store by any process — the same
        contract callers had when workers committed synchronously."""
        with self._cond:
            while self._pending:
                self._cond.wait(_IDLE_TICK)
        barrier = getattr(self._backend, "barrier", None)
        if barrier is not None:
            barrier()

    def close(self) -> None:
        """Retire the Worker pool. Completes everything already submitted
        first (in-flight attempts and queued work all settle), then shuts
        the backend down.

        Idempotent and thread-safe: a second ``close`` — from any thread,
        including one racing ``drain`` — observes the guarded state
        transition and simply waits for the first closer to finish instead
        of double-joining the pool."""
        with self._cond:
            if self._state in (_NEW, _CLOSED):
                self._state = _CLOSED
                self._cond.notify_all()
                return
            if self._state == _CLOSING:
                # another thread owns the shutdown: wait it out
                while self._state != _CLOSED:
                    self._cond.wait(_IDLE_TICK)
                return
            self._state = _CLOSING
            self._cond.notify_all()
            pump = self._pump_thread
        if pump is not None:
            pump.join()
        self._backend.shutdown()
        with self._cond:
            if self._session_t0 is not None and self._session_t1 is None:
                self._session_t1 = time.monotonic()
            self._state = _CLOSED
            self._pump_thread = None
            self._cond.notify_all()

    def results(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._results)

    def forget(self, keys) -> None:
        """Release memoised results + attempt bookkeeping for keys whose
        lifecycle is over (drained, consumed). A long-lived session would
        otherwise retain every settled WorkItem's value for its whole life
        — the streaming executor calls this per study when sharing a
        session across an adaptive study's rounds.

        Two races are closed under the lock: stale queued duplicates of a
        forgotten key (heartbeat-expiry re-enqueues) are purged — without
        their memoised result they would re-execute — and a key whose
        losing attempt (straggler backup / presumed-dead original) still
        holds a lease keeps its result, so the late completion dedups via
        first-completion-wins instead of resurrecting a value. Such keys
        join the deferred-forget set and are released when their last lease
        settles."""
        with self._cond:
            keyset = set(keys)
            if not keyset:
                return
            self._queue.remove_keys(keyset)
            leased = {it.key for it in self._running.values()}
            # Keys with an outstanding ORPHANED lease are held too: their
            # drop-marker carries a lease id minted from the key's attempt
            # sequence, so releasing the sequence now would let a future
            # lifecycle re-mint a colliding id and have its completion
            # silently dropped. They drain when the orphan settles/dies.
            orphan_keys = {
                lid.rsplit("#", 1)[0] for lid in self._orphaned
            }
            self._deferred_forget |= keyset & (leased | orphan_keys)
            for k in keyset - leased - orphan_keys:
                self._results.pop(k, None)
                self._attempt_seq.pop(k, None)
                self._callbacks.pop(k, None)

    def cancel(self, keys) -> List[str]:
        """Revoke submitted-but-unsettled keys (DESIGN.md §18): queued
        work is purged from the queue, live
        leases are poisoned (their ids join the orphan set, so the
        worker's eventual completion is dropped on arrival — the worker
        itself is not interrupted mid-task), and each revoked key settles
        exactly once with :class:`TaskCancelled` as its value, firing its
        callbacks like any other permanent failure. Keys already settled
        or never submitted are left untouched. Returns the keys actually
        cancelled.

        After cancel, ``forget`` + re-``submit`` of the same key starts a
        clean new lifecycle: attempt numbering continues from the high
        water mark, so a straggling poisoned lease can never collide with
        — or settle — the new lifecycle."""
        cancelled: List[str] = []
        with self._cond:
            keyset = set(keys)
            if not keyset:
                return cancelled
            live = {
                k for k in keyset
                if k in self._pending and k not in self._results
            }
            if not live:
                return cancelled
            self._queue.remove_keys(live)
            for lid, it in list(self._running.items()):
                if it.key in live:
                    self._orphaned.add(lid)
                    del self._running[lid]
            cancelled = sorted(live)
            self.cancelled += len(cancelled)
        # settle outside the lock: callbacks may re-enter submit()
        for key in cancelled:
            self._settle(key, 0, TaskCancelled(f"cancelled: {key!r}"), None)
        return cancelled

    def set_tenant_weight(self, tenant: str, weight: float) -> None:
        """Set a fair-share tenant's dispatch quantum (default 1.0; higher
        drains proportionally faster, floor-clamped so every tenant keeps
        making progress)."""
        with self._lock:
            self._queue.set_weight(tenant, weight)

    def _drain_deferred_locked(self, key: str) -> None:
        """Release a deferred-forgotten key's bookkeeping once its LAST
        lease has been returned (caller holds the lock and has already
        popped its own lease). While any other attempt is still in flight
        — including a poisoned orphan whose drop-marker was minted from
        this key's attempt sequence — the bookkeeping must survive so the
        late completion dedups instead of colliding."""
        if key not in self._deferred_forget:
            return
        if any(it.key == key for it in self._running.values()):
            return
        if any(lid.rsplit("#", 1)[0] == key for lid in self._orphaned):
            return
        self._deferred_forget.discard(key)
        self._results.pop(key, None)
        self._attempt_seq.pop(key, None)
        self._callbacks.pop(key, None)

    # ------------------------------------------------------------------
    # Scheduling (pump-side)
    # ------------------------------------------------------------------
    def _next_locked(self) -> Optional[WorkItem]:
        # Dequeue and lease registration are atomic under one lock: a peer
        # observing (queue empty, no leases) under that lock can therefore
        # conclude the system is idle — there is no window where an item has
        # left the queue but is not yet visible in ``_running``. Items whose
        # key already has a result (a raced retry/backup) are dropped here,
        # before any lease exists, so they can never leak one.
        while True:
            if not self._queue:
                item = self._maybe_backup_locked()
                if item is None:
                    return None
                break
            item = self._queue.popleft()
            if item.key not in self._results:
                break
        self._lease_locked(item)
        return item

    def _lease_locked(self, item: WorkItem) -> None:
        """Mint a lease for ``item`` under the Manager lock. Attempt
        numbers are issued here and ONLY here, so concurrent attempts of one
        key (original + backup clone) always hold distinct leases."""
        item.started_at = time.monotonic()
        item.attempts = self._attempt_seq.get(item.key, 0) + 1
        self._attempt_seq[item.key] = item.attempts
        self._running[f"{item.key}#{item.attempts}"] = item
        self.tenant_dispatch[item.tenant] = (
            self.tenant_dispatch.get(item.tenant, 0) + 1
        )

    def _unlease_locked(self, item: WorkItem) -> None:
        """Revert ``_next_locked`` for a lease no worker accepted (a slot
        vanished between the demand snapshot and the offer — e.g. a worker
        died). The attempt number is returned too: nothing outside this
        process ever observed it."""
        lid = f"{item.key}#{item.attempts}"
        if lid in self._orphaned:
            # the lease was cancelled/orphaned between minting and the
            # rejected offer: the drop-marker has done its job (nothing
            # was ever dispatched) — discard it WITHOUT reverting the
            # attempt sequence, so the marker's id can never be re-minted
            # by the key's next lifecycle.
            self._orphaned.discard(lid)
            self._drain_deferred_locked(item.key)
            return
        self._running.pop(lid, None)
        if self._attempt_seq.get(item.key) == item.attempts:
            self._attempt_seq[item.key] = item.attempts - 1
        if item.key not in self._results:
            self._queue.appendleft(item)

    def _expire_dead_locked(
        self, view: Dict[int, WorkerStatus], to_settle: List
    ) -> None:
        """Re-enqueue leases held by provably-dead workers (a worker
        process that no longer exists). Unlike age-based expiry this is
        immediate — there is no ambiguity to adapt a deadline around. A key
        out of attempts with no other live lease settles as a permanent
        failure (appended to ``to_settle``; the caller settles outside the
        lock)."""
        for status in view.values():
            if status.alive:
                continue
            for lease_id in status.inflight:
                item = self._running.pop(lease_id, None)
                if item is None:
                    # an orphaned lease dies with its worker: no completion
                    # will ever arrive to drain its drop-marker
                    if lease_id in self._orphaned:
                        self._orphaned.discard(lease_id)
                        self._drain_deferred_locked(
                            lease_id.rsplit("#", 1)[0]
                        )
                    continue
                self.heartbeat_expiries += 1
                if item.key in self._results:
                    self._drain_deferred_locked(item.key)
                    continue
                if (
                    self._attempt_seq.get(item.key, 0) - item.attempt_base
                    < self.max_attempts
                ):
                    self.retries += 1
                    self._queue.append(item.next_attempt())
                    self._cond.notify_all()
                elif not any(
                    it.key == item.key for it in self._running.values()
                ):
                    to_settle.append(
                        (
                            item.key,
                            item.attempts,
                            RemoteTaskError(
                                f"worker died holding the last attempt of "
                                f"{item.key!r}"
                            ),
                        )
                    )

    def _expire_heartbeats_locked(
        self, view: Optional[Dict[int, WorkerStatus]] = None
    ) -> None:
        """Re-enqueue leases whose Worker missed the heartbeat deadline.
        The lease is released; if the presumed-dead attempt does return
        later, first-completion-wins dedups it.

        In-process Workers cannot prove liveness while inside a task fn, so
        a long bucket is indistinguishable from a dead Worker by age alone.
        The deadline therefore adapts to observed bucket times of the
        lease's own kind — ``max(heartbeat_timeout, straggler_factor ×
        median(kind))`` — and a lease whose kind has no completed history
        yet (e.g. the first segmentation bucket is a multi-minute jit
        compile, while cheap normalization buckets have already finished)
        is never expired.

        ``view`` is passed only by backends whose heartbeats PROVE liveness
        mid-task (the RPC backend's workers sign life from a side thread):
        a lease held by a worker seen alive within ``_LIVENESS_FRESH``
        seconds is never age-expired — a long bucket on a live remote
        worker gets a straggler backup clone, not a revoked lease. A
        wedged worker whose heartbeats stop re-enters age-based expiry.
        (Provably-dead workers are handled separately and immediately by
        ``_expire_dead_locked``.)"""
        now = time.monotonic()
        proven_live: set = set()
        if view is not None:
            for status in view.values():
                if status.alive and now - status.last_seen <= _LIVENESS_FRESH:
                    proven_live.update(status.inflight)
        for lease, it in list(self._running.items()):
            if it.key in self._results:
                continue
            if lease in proven_live:
                continue
            median = self._median_locked(it.kind)
            if median is None:
                continue
            deadline = max(self.heartbeat_timeout, self.straggler_factor * median)
            started = it.started_at or now
            if now - started <= deadline:
                continue
            if (
                self._attempt_seq.get(it.key, 0) - it.attempt_base
                >= self.max_attempts
            ):
                continue
            del self._running[lease]
            self.heartbeat_expiries += 1
            self.retries += 1
            self._queue.append(it.next_attempt())
            self._cond.notify_all()

    def _maybe_backup_locked(self) -> Optional[WorkItem]:
        """Clone the longest-running bucket that looks like a straggler
        against its own kind (at least two completions of that kind, and
        older than ``straggler_factor`` × their median). Caller holds
        ``self._lock``. At most one backup of a key is in flight at a time:
        while original + clone both run, the key holds two leases and is
        skipped."""
        if not self.enable_backup_tasks or not self._running:
            return None
        now = time.monotonic()
        stragglers = [
            it
            for it in self._running.values()
            if it.key not in self._results
            and len(self._durations.get(it.kind, ())) >= 2
            and now - (it.started_at or now)
            > self.straggler_factor * max(self._median_locked(it.kind), 1e-3)
            and sum(1 for other in self._running.values() if other.key == it.key) < 2
            and self._attempt_seq.get(it.key, 0) - it.attempt_base
            < self.max_attempts
        ]
        if not stragglers:
            return None
        worst = max(stragglers, key=lambda it: now - (it.started_at or now))
        self.backups_launched += 1
        return worst.next_attempt()

    def _settle(
        self, key: str, attempt: int, value: Any, duration: Optional[float]
    ) -> None:
        """Record a final value (result or permanent failure) for a key and
        fire its callback exactly once. The key stays in ``_pending`` until
        the callback returns, so ``drain`` cannot observe a momentarily-empty
        pending set while a callback is still about to submit downstream
        work (the per-input stage edge of the streaming executor)."""
        cbs: Optional[List[Callable[[str, Any], None]]] = None
        won = False
        with self._cond:
            item = self._running.pop(f"{key}#{attempt}", None)
            if key not in self._results:  # first completion wins
                won = True
                self._results[key] = value
                if duration is not None and not isinstance(value, Exception):
                    self._busy_total += duration
                    # an attempt whose lease expired (its worker presumed
                    # dead) has left _running, and with it the kind its
                    # duration would be filed under
                    if item is not None:
                        self._record_duration_locked(item.kind, duration)
                cbs = self._callbacks.pop(key, None)
            self._drain_deferred_locked(key)
            self._cond.notify_all()
        if not won:  # raced duplicate: the winner owns callback + pending
            return
        try:
            if cbs:
                # every subscriber of the lifecycle fires exactly once —
                # shared submissions fan one completion out to many jobs
                for cb in cbs:
                    cb(key, value)
        finally:
            with self._cond:
                self._pending.discard(key)
                self._cond.notify_all()

    def _handle_completion(self, comp: Completion) -> None:
        with self._cond:
            if comp.lease_id in self._orphaned:
                # a lease stranded by its key's resubmission or
                # cancellation (new lifecycle): the value may be from
                # another scope — drop it. The marker may have been the
                # last thing pinning a deferred-forgotten key.
                self._orphaned.discard(comp.lease_id)
                self._drain_deferred_locked(comp.key)
                return
            item = self._running.get(comp.lease_id)
            if comp.worker_id is not None and comp.duration:
                self._worker_busy[comp.worker_id] = (
                    self._worker_busy.get(comp.worker_id, 0.0) + comp.duration
                )
        if comp.ok:
            self._settle(comp.key, comp.attempt, comp.value, comp.duration)
            return
        err = comp.exc if comp.exc is not None else RemoteTaskError(
            comp.error or "remote task failed"
        )
        # Lease release and re-enqueue happen under one lock so peers never
        # observe (queue empty, no leases) while a retry is still in flight.
        with self._cond:
            self._running.pop(comp.lease_id, None)
            if (
                item is not None
                and item.attempts - item.attempt_base < self.max_attempts
                and item.key not in self._results
            ):
                self.retries += 1
                # attempt numbers are issued by _next_locked at lease time
                self._queue.append(item.next_attempt())
                self._cond.notify_all()
                return
            if item is None and comp.key not in self._results:
                # the lease was already expired and re-driven; this late
                # failure report must not settle the key under the retry
                return
            if any(it.key == comp.key for it in self._running.values()):
                # an out-of-attempts failure must not condemn the key while
                # another attempt (straggler original / backup clone) is
                # still live — first COMPLETION wins, and if that attempt
                # also fails, ITS failure settles (it will find no live
                # peer then). Same guard _expire_dead_locked applies.
                return
        self._settle(comp.key, comp.attempt, err, None)

    def _pump(self) -> None:
        """The scheduling loop: one thread drives completions, expiry and
        dispatch for the whole session, leaving execution entirely to the
        backend. A structural backend failure fails the session's pending
        work loudly instead of leaving ``drain`` waiting on a dead pump."""
        try:
            self._pump_loop()
        except BaseException as pump_err:  # noqa: BLE001 — fail pending work
            with self._cond:
                stranded = {
                    it.key
                    for it in list(self._queue) + list(self._running.values())
                } | set(self._pending)
                self._queue.clear()
                self._running.clear()
            for key in stranded:
                self._settle(
                    key, 0,
                    RemoteTaskError(f"dispatch pump failed: {pump_err!r}"),
                    None,
                )
            with self._cond:  # keys that already had results stay settled
                self._pending -= set(self._results)
                self._cond.notify_all()
            raise
        finally:
            with self._cond:
                if self._session_t1 is None:
                    self._session_t1 = time.monotonic()
                if self._parked_since is not None:
                    self._pump_parked += (
                        time.monotonic() - self._parked_since
                    )
                    self._parked_since = None
                self._cond.notify_all()

    def _pump_loop(self) -> None:
        backend = self._backend
        while True:
            # Idle-pool parking (DESIGN.md §18): with zero pending work —
            # nothing queued anywhere, no leases in flight — a long-lived
            # session's pump parks on the condition variable instead of
            # busy-polling the backend every tick. submit()/close() wake
            # it with notify_all; the first post-wake completion poll is
            # non-blocking so freshly submitted work dispatches
            # immediately instead of riding out a sleeping poll (this is
            # the adaptive driver's round-boundary stall).
            just_woke = False
            with self._cond:
                if (
                    self._state == _RUNNING
                    and not self._pending
                    and not self._running
                    and not self._orphaned
                    and not self._queue
                ):
                    if self._parked_since is None:
                        self._parked_since = time.monotonic()
                    # Timed, not indefinite: while parked the pump still
                    # owes the backend a slow drain (heartbeat frames
                    # carry worker stats; a lease orphaned moments before
                    # the pool went idle completes late and its dropped
                    # completion must still be consumed). submit()/close()
                    # notify_all for the instant-wake path.
                    self._cond.wait(_PARK_TICK)
                    just_woke = True
                if self._parked_since is not None:
                    self._pump_parked += (
                        time.monotonic() - self._parked_since
                    )
                    self._parked_since = None
            comps = backend.poll_completions(0.0 if just_woke else _IDLE_TICK)
            t_work = time.monotonic()
            self._pump_tick(backend, comps)
            with self._cond:
                self._pump_busy += time.monotonic() - t_work
                if (
                    self._state == _CLOSING
                    and not self._pending
                    and not self._running
                    and not self._queue
                ):
                    return

    def _pump_tick(self, backend, comps: List[Completion]) -> None:
        """One pump tick's work: settle ``comps``, expire dead and stale
        leases, then offer leases to free workers. The tick runs inside a span
        ``sa/dispatch`` (``completions``, ``leases``), opened at its first
        completion or else at its first lease; a tick that settles no
        completion and mints no lease records none."""
        leases = 0
        with contextlib.ExitStack() as tick:
            mark = None

            def open_tick() -> None:
                nonlocal mark
                if mark is None:
                    mark = tick.enter_context(
                        span("sa/dispatch", completions=len(comps))
                    )

            if comps:
                open_tick()
            for comp in comps:
                self._handle_completion(comp)
            view = backend.heartbeat_view()
            to_settle: List = []
            with self._cond:
                self._expire_dead_locked(view, to_settle)
                self._expire_heartbeats_locked(
                    view
                    if getattr(backend, "heartbeats_prove_liveness", False)
                    else None
                )
                if view and not any(st.alive for st in view.values()):
                    # the whole pool is gone (every worker process died):
                    # nothing can ever complete — fail what's left instead
                    # of spinning forever
                    for item in (
                        list(self._queue) + list(self._running.values())
                    ):
                        if item.key not in self._results:
                            to_settle.append(
                                (
                                    item.key,
                                    item.attempts,
                                    RemoteTaskError(
                                        "every worker died; "
                                        f"{item.key!r} can never complete"
                                    ),
                                )
                            )
                    self._queue.clear()
                    self._running.clear()
            for key, attempt, err in to_settle:
                self._settle(key, attempt, err, None)
            # demand-driven dispatch: free slots = per-worker queue
            # depth (slots_per_worker > 1 when the backend batches
            # frames — a worker holds a small backlog so it never
            # idles between round trips; 1 for the historical
            # one-lease-per-worker)
            slots = max(1, int(getattr(backend, "slots_per_worker", 1)))
            free = sum(
                max(0, slots - len(st.inflight))
                for st in view.values()
                if st.alive
            )
            offer_batch = getattr(backend, "offer_batch", None)
            if offer_batch is not None:
                leases = self._dispatch_batched(offer_batch, free, open_tick)
            else:
                while free > 0:
                    with self._cond:
                        item = self._next_locked()
                    if item is None:
                        break
                    open_tick()
                    lease = Lease(
                        key=item.key, attempt=item.attempts, fn=item.fn,
                        spec=item.spec,
                    )
                    if backend.offer(lease):
                        with self._cond:
                            self.dispatch_counts[self.backend_name] = (
                                self.dispatch_counts.get(self.backend_name, 0)
                                + 1
                            )
                        free -= 1
                        leases += 1
                    else:  # slot vanished since snapshot (worker death)
                        with self._cond:
                            self._unlease_locked(item)
                        break
            if mark is not None:
                mark.set_metadata(leases=leases)

    def _dispatch_batched(
        self, offer_batch, free: int, on_batch: Callable[[], None]
    ) -> int:
        """Batched dispatch (DESIGN.md §14): lease up to ``free`` items in
        one pass and hand them to the backend as a single ``offer_batch``
        call — the backend coalesces each worker's share into one frame.
        Rejected leases (slots vanished since the demand snapshot) are
        unleased in reverse lease order, restoring queue position and
        attempt numbers exactly as the one-at-a-time path would.
        ``on_batch`` runs once a batch is leased, before it is offered.
        Returns the number of leases the backend accepted."""
        offered = 0
        while free > 0:
            batch: List = []
            with self._cond:
                while len(batch) < free:
                    item = self._next_locked()
                    if item is None:
                        break
                    batch.append(item)
            if not batch:
                return offered
            on_batch()
            leases = [
                Lease(key=it.key, attempt=it.attempts, fn=it.fn, spec=it.spec)
                for it in batch
            ]
            rejected = {lease.lease_id for lease in offer_batch(leases)}
            accepted = len(batch) - len(rejected)
            offered += accepted
            if accepted:
                with self._cond:
                    self.dispatch_counts[self.backend_name] = (
                        self.dispatch_counts.get(self.backend_name, 0) + accepted
                    )
            if rejected:
                with self._cond:
                    for it in reversed(batch):
                        if f"{it.key}#{it.attempts}" in rejected:
                            self._unlease_locked(it)
                return offered
            free -= accepted
        return offered

    # ------------------------------------------------------------------
    # One-shot batch mode (the pre-streaming API, kept verbatim)
    # ------------------------------------------------------------------
    def run(self, n_workers: int, *, expected: int) -> Dict[str, Any]:
        """Run until ``expected`` distinct results exist."""
        self.start(n_workers)
        try:
            with self._cond:
                while len(self._results) < expected and self._pending:
                    self._cond.wait(_IDLE_TICK)
        finally:
            self.close()
        # analysis: ok[locks] close() joined the pump: no writer is left
        return dict(self._results)


def run_study_distributed(
    buckets: List[Any],
    execute_bucket: Callable[[Any], Dict[int, Any]],
    *,
    n_workers: int = 2,
    manager: Optional[Manager] = None,
) -> Dict[int, Any]:
    """Execute merged-stage buckets across Workers; returns run_id -> output."""
    mgr = manager or Manager()
    for i, b in enumerate(buckets):
        mgr.submit(WorkItem(key=f"bucket{i}", fn=lambda b=b: execute_bucket(b)))
    per_bucket = mgr.run(n_workers, expected=len(buckets))
    out: Dict[int, Any] = {}
    for v in per_bucket.values():
        if isinstance(v, Exception):
            raise v
        out.update(v)
    return out
