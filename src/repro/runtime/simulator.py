"""Discrete-event simulator of the Manager-Worker cluster at paper scale
(256 nodes × 28 cores) — drives the fig8 multi-node scalability benchmark.

Cost model: per-bucket compute times come from *measured* JAX task
wall-times composed over the bucket's merged task tree (the same model the
paper's gains rest on: reuse changes WHICH tasks run, not how fast a task
is). Per-bucket dispatch latency and per-tile I/O are charged per the RTF's
demand-driven protocol; node_speed jitter injects stragglers.
"""

from __future__ import annotations

import collections
import dataclasses
import heapq
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "ClusterSim",
    "simulate_cluster",
    "StreamSim",
    "simulate_stream",
    "AutotuneResult",
    "autotune_stream",
]


@dataclasses.dataclass
class ClusterSim:
    makespan: float
    busy_time: float
    n_nodes: int
    cores_per_node: int

    @property
    def parallel_efficiency(self) -> float:
        return self.busy_time / (self.makespan * self.n_nodes * self.cores_per_node)


def simulate_cluster(
    bucket_costs: Sequence[float],
    *,
    n_nodes: int,
    cores_per_node: int = 28,
    dispatch_latency: float = 2e-3,
    io_per_bucket: float = 0.05,
    node_speed_sigma: float = 0.03,
    seed: int = 0,
) -> ClusterSim:
    """Demand-driven list scheduling of buckets onto node-cores.

    Each core pulls the next bucket when free (the RTF protocol). Node speed
    is jittered (shared-memory/I-O contention, the paper's §IV-D explanation
    for sub-ideal multicore speedups is modelled as a per-node slowdown).
    """
    rng = np.random.default_rng(seed)
    speeds = 1.0 + rng.normal(0, node_speed_sigma, n_nodes).clip(-0.2, 0.2)
    # executor heap: (free_time, core_id); cores indexed node-major
    n_cores = n_nodes * cores_per_node
    heap = [(0.0, i) for i in range(n_cores)]
    heapq.heapify(heap)
    busy = 0.0
    makespan = 0.0
    for cost in sorted(bucket_costs, reverse=True):  # LPT demand-driven
        t, core = heapq.heappop(heap)
        node = core // cores_per_node
        dur = cost / speeds[node] + io_per_bucket
        end = t + dispatch_latency + dur
        busy += dur
        makespan = max(makespan, end)
        heapq.heappush(heap, (end, core))
    return ClusterSim(
        makespan=makespan,
        busy_time=busy,
        n_nodes=n_nodes,
        cores_per_node=cores_per_node,
    )


@dataclasses.dataclass
class StreamSim:
    """Result of :func:`simulate_stream` — the streaming dataset executor at
    paper scale (many tiles through one multi-stage plan), including the
    pump's occupancy."""

    makespan: float
    busy_time: float
    n_inputs: int
    n_nodes: int
    cores_per_node: int
    # scheduling-event seconds accumulated by the pump — the serialization
    # metric; occupancy near 1.0 means the pump, not the cores, is the
    # bottleneck.
    pump_busy: float = 0.0

    @property
    def parallel_efficiency(self) -> float:
        from repro.core.metrics import parallel_efficiency

        return parallel_efficiency(
            self.busy_time, self.makespan, self.n_nodes * self.cores_per_node
        )

    @property
    def throughput(self) -> float:
        from repro.core.metrics import throughput

        return throughput(self.n_inputs, self.makespan)

    @property
    def pump_occupancy(self) -> float:
        """The pump's scheduling-work fraction of the makespan."""
        return self.pump_busy / self.makespan if self.makespan else 0.0

    @property
    def worker_idle_fraction(self) -> float:
        """Mean fraction of the makespan a core spent idle."""
        return 1.0 - self.parallel_efficiency


def simulate_stream(
    stage_bucket_costs: Sequence[Sequence[float]],
    n_inputs: int,
    *,
    n_nodes: int,
    cores_per_node: int = 28,
    dispatch_latency: float = 2e-3,
    io_per_bucket: float = 0.05,
    node_speed_sigma: float = 0.03,
    input_cost_sigma: float = 0.05,
    seed: int = 0,
    barrier: bool = False,
    pump_service: float = 0.0,
) -> StreamSim:
    """Discrete-event model of ``execute_study`` at paper scale.

    ``stage_bucket_costs[s]`` is the per-bucket compute cost list of stage
    *s* of ONE input's plan (the frozen schedules' makespans); every input
    replays the same plan with a per-input cost jitter (tile content
    varies). Dependency structure mirrors the executor: with
    ``barrier=False`` (streaming), stage *s+1* buckets of input *i* become
    ready when input *i* finishes stage *s* — inputs pipeline freely across
    stages. With ``barrier=True`` (the pre-streaming global barrier), stage
    *s+1* opens only after EVERY input finished stage *s* — the idle tail
    this executor removed. Cores pull ready buckets demand-driven (RTF).

    **Pump model.** Every scheduling event — a dispatch *or* a completion
    settle — occupies the Manager's single pump for ``pump_service``
    seconds (the measured per-event cost of the Python pump: poll, lock,
    lease bookkeeping, callback). A bucket's start is therefore delayed
    behind the pump's backlog: with thousands of cores the pump queue, not
    the workers, sets the makespan. ``pump_service=0`` models a free pump.
    """
    stage_bucket_costs = [list(s) for s in stage_bucket_costs]
    if any(not s for s in stage_bucket_costs):
        # an empty stage would stall its dependents silently (no completion
        # event ever opens stage s+1) — reject degenerate plans loudly
        raise ValueError("every stage needs at least one bucket cost")
    rng = np.random.default_rng(seed)
    speeds = 1.0 + rng.normal(0, node_speed_sigma, n_nodes).clip(-0.2, 0.2)
    jitter = 1.0 + rng.normal(0, input_cost_sigma, n_inputs).clip(-0.5, 0.5)
    n_stages = len(stage_bucket_costs)
    n_cores = n_nodes * cores_per_node

    ready: "collections.deque" = collections.deque()
    idle: "collections.deque" = collections.deque(range(n_cores))
    pump_free = 0.0  # time the pump is next available
    pump_busy = 0.0  # scheduling-event seconds

    remaining = np.zeros((n_inputs, n_stages), dtype=np.int64)
    stage_open = np.zeros(n_stages, dtype=np.int64)  # inputs not yet done (barrier)

    def enqueue(i: int, s: int) -> None:
        for c in stage_bucket_costs[s]:
            ready.append((i, s, c * jitter[i]))
        remaining[i, s] = len(stage_bucket_costs[s])

    for s in range(n_stages):
        stage_open[s] = n_inputs
    for i in range(n_inputs):
        enqueue(i, 0)

    running: List = []  # (end_time, tiebreak, input, stage, core)
    t = 0.0
    busy = 0.0
    tiebreak = 0

    def dispatch() -> None:
        nonlocal busy, tiebreak, pump_free, pump_busy
        while idle and ready:
            i, s, cost = ready.popleft()
            core = idle.popleft()
            dur = cost / speeds[core // cores_per_node] + io_per_bucket
            start = max(t, pump_free)  # pump serialization point
            pump_free = start + pump_service
            pump_busy += pump_service
            busy += dur
            tiebreak += 1
            heapq.heappush(
                running,
                (start + pump_service + dispatch_latency + dur,
                 tiebreak, i, s, core),
            )

    dispatch()
    while running:
        t, _, i, s, core = heapq.heappop(running)
        idle.append(core)
        # the settle is a scheduling event too: it occupies the pump
        pump_free = max(pump_free, t) + pump_service
        pump_busy += pump_service
        remaining[i, s] -= 1
        if remaining[i, s] == 0 and s + 1 < n_stages:
            if barrier:
                stage_open[s] -= 1
                if stage_open[s] == 0:  # last input closes the global barrier
                    for j in range(n_inputs):
                        enqueue(j, s + 1)
            else:
                enqueue(i, s + 1)  # per-input dependency edge
        dispatch()

    return StreamSim(
        makespan=t,
        busy_time=busy,
        n_inputs=n_inputs,
        n_nodes=n_nodes,
        cores_per_node=cores_per_node,
        pump_busy=pump_busy,
    )


@dataclasses.dataclass
class AutotuneResult:
    """Outcome of :func:`autotune_stream`: the bucket size with the
    smallest simulated makespan, plus the full search table."""

    bucket_size: int
    sim: StreamSim
    # (bucket_size, makespan, parallel_efficiency) per candidate
    table: List[Tuple[int, float, float]]


def autotune_stream(
    costs_by_bucket_size: Dict[int, Sequence[Sequence[float]]],
    n_inputs: int,
    *,
    n_nodes: int,
    **sim_kwargs,
) -> AutotuneResult:
    """Autotune the bucket size on the validated stream model.

    ``costs_by_bucket_size`` maps a candidate ``max_bucket_size`` to the
    re-planned ``stage_bucket_costs`` it produces (the caller re-plans;
    bucket size changes WHICH schedules exist, so it cannot be derived
    here). Every bucket size is simulated and the smallest makespan wins —
    the trade this searches is real: small buckets load-balance better but
    multiply scheduling events (pump-bound at high core counts), large
    buckets starve the pump less but serialise more work per bucket."""
    if not costs_by_bucket_size:
        raise ValueError("need at least one bucket-size candidate")
    best: Optional[Tuple[int, StreamSim]] = None
    table: List[Tuple[int, float, float]] = []
    for bucket_size in sorted(costs_by_bucket_size):
        sim = simulate_stream(
            costs_by_bucket_size[bucket_size], n_inputs, n_nodes=n_nodes,
            **sim_kwargs,
        )
        table.append((bucket_size, sim.makespan, sim.parallel_efficiency))
        if best is None or sim.makespan < best[1].makespan:
            best = (bucket_size, sim)
    assert best is not None
    return AutotuneResult(bucket_size=best[0], sim=best[1], table=table)
