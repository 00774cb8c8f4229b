"""Scheduler conformance battery for the Manager's single pump.

The pump's contract: dispatch is a pure *scheduling* concern. For ANY
workflow, ANY parameter sets, ANY input set and every policy, outputs must
be **bit-identical** to the straight-line oracle, with the accounting
identity ``tasks_executed + cache_hits == plan.tasks_executed × n_inputs``
intact and exactly one Manager session per study. The battery also pins
the scheduler-stats surface, the process-backend path, SA-index equality
through the adaptive driver, and the simulator's calibration against real
measured runs.
"""

import random
import time

import pytest

from repro.engine import ClusterSpec, execute_plan, execute_study, plan_study
from repro.engine.types import POLICIES
from repro.runtime import Manager, ProcessRpcBackend, simulate_stream

from study_gen import (
    mix_study_build,
    naive_outputs,
    random_layout,
    random_param_sets,
    random_workflow,
    sleep_workflow,
    workflow_from_layout,
)

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# Differential conformance: execute_plan across the policy matrix (the
# execute_study matrix is tests/test_policy_differential.py)
# ---------------------------------------------------------------------------


def _random_case(seed: int):
    rng = random.Random(seed)
    wf, names, cards = random_workflow(rng)
    sets = random_param_sets(rng, names, cards, rng.randint(2, 16))
    inputs = [rng.randrange(1 << 40) for _ in range(rng.randint(1, 3))]
    plan_kwargs = {
        "max_bucket_size": rng.choice([1, 2, 3, None]),
        "active_paths": rng.choice([1, 2, None]),
    }
    return wf, sets, inputs, plan_kwargs


@pytest.mark.parametrize("policy", POLICIES)
def test_execute_plan_hierarchy_matches_flat(policy):
    """The single-input executor drives the same Manager session as
    execute_study — oracle outputs and the accounting identity, policy by
    policy, in one session."""
    wf, sets, inputs, plan_kwargs = _random_case(9900)
    oracle = naive_outputs(wf, sets, inputs[0])
    plan = plan_study(wf, sets, policy=policy, **plan_kwargs)
    before = Manager.sessions_started
    res = execute_plan(plan, inputs[0], cluster=ClusterSpec(n_workers=4))
    assert Manager.sessions_started - before == 1
    assert res.outputs == oracle
    assert res.tasks_executed + res.cache_hits == plan.tasks_executed


# The keys the service and its tests read from Manager.scheduler_stats().
STATS_KEYS = {
    "pump_occupancy", "pump_parked_seconds", "active_wall_seconds",
    "worker_busy_seconds", "worker_idle_fraction", "wall_seconds",
    "cancelled", "tenant_dispatch", "tenant_depths",
}


def test_scheduler_stats_surface():
    """The stats snapshot is coherent: exactly the flat key set, counters
    non-negative, every worker accounted, every lease minted under the
    default tenant, and the wall clock real."""
    rng = random.Random(606)
    wf, names, cards = random_workflow(rng, max_stages=3)
    sets = random_param_sets(rng, names, cards, 24)
    inputs = [rng.randrange(1 << 40) for _ in range(4)]
    plan = plan_study(wf, sets, policy="hybrid", max_bucket_size=1)
    stream = execute_study(plan, inputs, cluster=ClusterSpec(n_workers=4))
    sched = stream.scheduler
    assert set(sched) == STATS_KEYS
    assert sched["wall_seconds"] > 0
    assert 0.0 <= sched["active_wall_seconds"] <= sched["wall_seconds"]
    assert sched["pump_parked_seconds"] >= 0.0
    assert 0.0 <= sched["pump_occupancy"]
    assert 0 < len(sched["worker_busy_seconds"]) <= 4
    assert all(v > 0.0 for v in sched["worker_busy_seconds"].values())
    assert 0.0 <= sched["worker_idle_fraction"] <= 1.0
    assert sched["cancelled"] == 0
    # every settled bucket was leased at least once (retries/backups may
    # add more leases, never fewer), all under the default tenant
    assert set(sched["tenant_dispatch"]) == {""}
    assert sched["tenant_dispatch"][""] >= plan.bucket_count() * len(inputs)


# ---------------------------------------------------------------------------
# Process backend: the flat pump dispatches through RPC worker processes
# ---------------------------------------------------------------------------


def test_hierarchy_bit_identical_on_process_backend(tmp_path):
    """Two RPC worker processes behind one external session: batched
    offers across both policies' calls; results must equal the oracle
    exactly."""
    rng = random.Random(1177)
    layout, names, cards = random_layout(rng, max_stages=2)
    wf = workflow_from_layout(layout)
    sets = random_param_sets(rng, names, cards, 10)
    inputs = [5, 13]
    oracles = [naive_outputs(wf, sets, x) for x in inputs]

    mgr = Manager(
        backend=ProcessRpcBackend(
            build=mix_study_build,
            build_kwargs={"layout": layout, "inputs": inputs},
            store_dir=str(tmp_path / "store"),
            heartbeat_interval=0.05,
        ),
        enable_backup_tasks=False,
    )
    mgr.start(2)
    try:
        for policy in ("stage", "hybrid"):
            plan = plan_study(wf, sets, policy=policy, max_bucket_size=2)
            stream = execute_study(
                plan, inputs, manager=mgr, key_prefix=f"{policy}:"
            )
            assert stream.backend == "process"
            assert stream.manager_sessions == 0
            for i in range(len(inputs)):
                assert stream.outputs[i] == oracles[i], (policy, i)
            assert (
                stream.tasks_executed + stream.cache_hits
                == plan.tasks_executed * len(inputs)
            ), policy
    finally:
        mgr.close()


# ---------------------------------------------------------------------------
# SA indices through the adaptive driver: reuse changes nothing
# ---------------------------------------------------------------------------


def _objective(leaf, _i):
    return float(leaf % 9973) / 9973.0


def test_sa_indices_bit_identical_flat_vs_hierarchical():
    """The adaptive driver's rounds under the hybrid policy (cross-round
    reuse through one session and cache) give exactly the objectives,
    indices and decisions of rounds whose every delta is evaluated under
    the ``none`` policy, each run re-executed unmerged."""
    from repro.core import ParamSpace
    from repro.study import StudyDriver

    layout = [
        [("s0t0", (), 1.0, 64)],
        [
            ("s1t0", ("p0",), 1.0, 64),
            ("s1t1", ("p1",), 1.0, 64),
            ("s1t2", ("p2",), 1.0, 64),
        ],
    ]
    space = ParamSpace.from_dict({f"p{i}": [0, 1, 2] for i in range(3)})
    inputs = [417, 9001]
    wf = workflow_from_layout(layout)
    cluster = ClusterSpec(n_workers=4)

    def evaluate_unmerged(delta):
        plan = plan_study(wf, delta, policy="none")
        stream = execute_study(plan, inputs, cluster=cluster)
        y = {}
        for rid, ps in enumerate(delta):
            vals = [
                float(_objective(stream.outputs[i][rid], i))
                for i in range(len(inputs))
            ]
            y[ps] = sum(vals) / len(vals)
        return y, {"tasks_executed": stream.tasks_executed}

    def run(evaluate_delta):
        driver = StudyDriver(
            wf,
            space,
            inputs,
            objective=_objective,
            seed=5,
            engine_policy="hybrid",
            cluster=cluster,
            n_boot=8,
            evaluate_delta=evaluate_delta,
        )
        try:
            return driver.run(max_rounds=2)
        finally:
            driver.close()

    hybrid_state = run(None)
    none_state = run(evaluate_unmerged)
    assert hybrid_state.evaluated == none_state.evaluated
    assert len(hybrid_state.rounds) == len(none_state.rounds) == 2
    for hr, nr in zip(hybrid_state.rounds, none_state.rounds):
        assert hr.outputs == nr.outputs
        assert hr.analysis == nr.analysis  # indices + CIs, exact floats
        assert hr.decision == nr.decision
        # reuse did work: the hybrid rounds executed fewer tasks
        assert hr.tasks_executed < nr.tasks_executed
    assert hybrid_state.active == none_state.active


# ---------------------------------------------------------------------------
# Hypothesis layer: shrinkable conformance over the same contract
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    class TestHypothesisHierarchyConformance:
        @given(
            seed=st.integers(min_value=0, max_value=2**20),
            n_runs=st.integers(min_value=1, max_value=12),
            n_workers=st.sampled_from([1, 2, 4]),
        )
        @settings(max_examples=10, deadline=None)
        def test_hierarchy_bit_identical(self, seed, n_runs, n_workers):
            rng = random.Random(seed)
            wf, names, cards = random_workflow(rng)
            sets = random_param_sets(rng, names, cards, n_runs)
            inputs = [rng.randrange(1 << 40) for _ in range(rng.randint(1, 2))]
            oracles = [naive_outputs(wf, sets, x) for x in inputs]
            plan = plan_study(
                wf, sets, policy=rng.choice(list(POLICIES)),
                max_bucket_size=rng.choice([1, 2, None]),
            )
            stream = execute_study(
                plan, inputs, cluster=ClusterSpec(n_workers=n_workers)
            )
            for i in range(len(inputs)):
                assert stream.outputs[i] == oracles[i], i
            assert (
                stream.tasks_executed + stream.cache_hits
                == plan.tasks_executed * len(inputs)
            )


# ---------------------------------------------------------------------------
# Simulator calibration: simulate_stream vs MEASURED ThreadBackend runs
# ---------------------------------------------------------------------------


def _calibration_case():
    """A sleep workflow whose declared costs ARE wall-seconds, planned so
    the per-stage bucket makespans feed simulate_stream directly."""
    wf = sleep_workflow([0.02, 0.03])
    sets = [((f"sp0", i % 4), (f"sp1", i % 3)) for i in range(8)]
    plan = plan_study(wf, sets, policy="stage", max_bucket_size=2)
    costs = [
        [b.schedule.makespan for b in stage.buckets] for stage in plan.stages
    ]
    return wf, sets, plan, costs


class TestSimulatorCalibration:
    """``simulate_stream`` is the autotuner's oracle, so its predictions
    must track reality. Tolerance (stated): a measured ThreadBackend run
    must land in ``[0.85 × predicted, 1.6 × predicted + 0.5 s]`` — the
    lower bound catches a simulator that over-charges (sleeps are real
    lower bounds on wall time), the upper bound catches one that ignores
    scheduling costs, with generous slack for loaded CI machines."""

    TOL_LOW = 0.85
    TOL_HIGH = 1.6
    TOL_SLACK = 0.5

    def _measure(self, wf, sets, plan, *, workers):
        t0 = time.perf_counter()
        stream = execute_study(
            plan,
            [101, 202],
            cluster=ClusterSpec(n_workers=workers, enable_backup_tasks=False),
        )
        measured = time.perf_counter() - t0
        assert stream.outputs[0] == naive_outputs(wf, sets, 101)
        return measured

    def _predict(self, costs, *, workers):
        sim = simulate_stream(
            costs,
            2,
            n_nodes=1,
            cores_per_node=workers,
            dispatch_latency=0.0,
            io_per_bucket=0.0,
            node_speed_sigma=0.0,
            input_cost_sigma=0.0,
        )
        return sim.makespan

    @pytest.mark.parametrize("workers", [1, 4])
    def test_predicted_wall_time_tracks_measured(self, workers):
        wf, sets, plan, costs = _calibration_case()
        predicted = self._predict(costs, workers=workers)
        assert predicted > 0.05  # a real workload, not a degenerate case
        measured = self._measure(wf, sets, plan, workers=workers)
        assert measured >= self.TOL_LOW * predicted, (measured, predicted)
        assert measured <= self.TOL_HIGH * predicted + self.TOL_SLACK, (
            measured,
            predicted,
        )

    def test_fewer_workers_predictably_slower(self):
        """Calibration is relative too: the simulator's 1-worker/4-worker
        makespan ratio must match the measured ratio's direction."""
        wf, sets, plan, costs = _calibration_case()
        p1 = self._predict(costs, workers=1)
        p4 = self._predict(costs, workers=4)
        assert p1 > p4 * 1.5  # the model scales with workers
        m1 = self._measure(wf, sets, plan, workers=1)
        m4 = self._measure(wf, sets, plan, workers=4)
        assert m1 > m4, (m1, m4)
