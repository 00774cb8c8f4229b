"""SA-as-a-service suite (DESIGN.md §18, ISSUE 10).

Acceptance scenario and unit coverage for the multi-tenant study server:

* **bit-identical** — a job's objective vector equals the naive oracle
  computed outside the service (exact integer workloads, `==` not ≈);
* **executes once** — two tenants submitting equal-signature specs
  concurrently share one execution (combined dispatch < sum asserted);
* **cross-tenant reuse** — an overlapping later spec reuses the shared
  ResultCache (fewer misses than a standalone run of the same plan);
* **cancellation** — cancelling one tenant's job mid-study frees its
  queued work without perturbing the other tenant's results;
* **fair share** — a low-weight tenant's small job completes while a
  heavy tenant's backlog is still draining (monotonic progress, no
  starvation), plus FairQueue unit laws;
* **quotas, wire protocol, timeouts, idle-pool accounting.**
"""

import threading
import time

import pytest

from repro.core.params import ParamSpace
from repro.engine import ClusterSpec, ResultCache, execute_study, plan_study
from repro.engine.streaming import study_task_keys
from repro.runtime import Manager, WorkItem
from repro.runtime.fairshare import FairQueue
from repro.service import (
    QuotaExceeded,
    ServiceClient,
    ServiceError,
    SpecError,
    StudyServer,
    StudySpec,
    TenantQuota,
)

from study_gen import naive_outputs, sleep_workflow, workflow_from_layout

pytestmark = pytest.mark.filterwarnings("ignore::ResourceWarning")


# ---------------------------------------------------------------------------
# Fixtures: fast integer-mixing service + a sleepy one for race windows
# ---------------------------------------------------------------------------

_LAYOUT = [
    [("s0t0", ("a",), 1.0, 64), ("s0t1", ("b",), 1.0, 64)],
    [("s1t0", ("c", "d"), 1.0, 64)],
]
_SPACE = ParamSpace.from_dict(
    {"a": [0, 1, 2], "b": [0, 1, 2], "c": [0, 1], "d": [0, 1, 2]}
)
_INPUTS = [3, 8]

_SLEEP_SPACE = ParamSpace.from_dict(
    {"sp0": [0, 1, 2, 3], "sp1": [0, 1, 2, 3]}
)


def _int_objective(leaf, input_index):
    return float(leaf % 997)


def _oracle_objective(workflow, runs, inputs):
    """Expected per-run objective vector, straight-line, outside the
    engine entirely."""
    per_input = [naive_outputs(workflow, runs, x) for x in inputs]
    return [
        sum(_int_objective(per_input[i][rid], i) for i in range(len(inputs)))
        / len(inputs)
        for rid in range(len(runs))
    ]


@pytest.fixture
def server():
    srv = StudyServer(
        workflow=workflow_from_layout(_LAYOUT),
        space=_SPACE,
        inputs=_INPUTS,
        objective=_int_objective,
        n_workers=2,
    )
    yield srv
    srv.close()


@pytest.fixture
def sleepy_server():
    srv = StudyServer(
        workflow=sleep_workflow([0.03, 0.03]),
        space=_SLEEP_SPACE,
        inputs=[5],
        objective=_int_objective,
        n_workers=2,
    )
    yield srv
    srv.close()


# ---------------------------------------------------------------------------
# StudySpec: validation, wire form, signature semantics
# ---------------------------------------------------------------------------


def test_spec_validation_rejects_bad_specs():
    for bad in [
        StudySpec(sampler="nope"),
        StudySpec(sampler="explicit", param_sets=None),
        StudySpec(policy="nope"),
        StudySpec(priority=99),
        StudySpec(sampler="moat", n_trajectories=0),
        StudySpec(bounds={"ghost": [1]}),
        StudySpec(bounds={"a": []}),
        StudySpec(sampler="explicit", param_sets=[{"ghost": 1}]),
        StudySpec(sampler="grid", names=["ghost"]),
    ]:
        with pytest.raises(SpecError):
            bad.resolve(_SPACE)


def test_spec_wire_form_roundtrip_and_unknown_fields():
    spec = StudySpec(
        sampler="grid", names=["a", "c"], bounds={"a": [0, 2]}, priority=3
    )
    assert StudySpec.from_json(spec.to_json()) == spec
    with pytest.raises(SpecError):
        StudySpec.from_json({"sampler": "grid", "warp_speed": 9})


def test_spec_resolution_fills_defaults_and_caps_runs():
    runs = StudySpec(
        sampler="explicit", param_sets=[{"a": 2}, {"a": 1, "d": 2}]
    ).resolve(_SPACE)
    defaults = dict(_SPACE.default())
    assert dict(runs[0])["a"] == 2
    assert dict(runs[0])["b"] == defaults["b"]
    assert dict(runs[1])["d"] == 2
    # grid over bounded sub-space
    grid = StudySpec(sampler="grid", names=["a", "c"], bounds={"a": [0, 1]})
    assert len(grid.resolve(_SPACE)) == 2 * 2
    # the run-count guardrail fires before anything is planned
    wide = ParamSpace.from_dict({f"w{i}": list(range(10)) for i in range(5)})
    with pytest.raises(SpecError):
        StudySpec(sampler="grid").resolve(wide)


def test_spec_signature_content_addressing():
    base = StudySpec(sampler="grid", names=["a", "b"])
    same_work = StudySpec(
        sampler="grid", names=["a", "b"], priority=5, timeout_s=9.0,
        metrics=["objective", "per_input"], poll_s=1.0,
    )
    # dispatch-only fields do not change WHAT is computed
    assert base.signature(_SPACE) == same_work.signature(_SPACE)
    for different in [
        StudySpec(sampler="grid", names=["a", "c"]),
        StudySpec(sampler="grid", names=["a", "b"], policy="rmsr"),
        StudySpec(sampler="grid", names=["a", "b"], bounds={"a": [0, 1]}),
        StudySpec(sampler="grid", names=["a", "b"], max_bucket_size=2),
    ]:
        assert base.signature(_SPACE) != different.signature(_SPACE)
    # explicit spec listing the same runs the grid denotes = same work
    grid_runs = base.resolve(_SPACE)
    explicit = StudySpec(
        sampler="explicit", param_sets=[dict(ps) for ps in grid_runs]
    )
    assert explicit.signature(_SPACE) == base.signature(_SPACE)


# ---------------------------------------------------------------------------
# The job API: results bit-identical to the oracle
# ---------------------------------------------------------------------------


def test_job_lifecycle_and_bit_identical_objective(server):
    spec = StudySpec(
        sampler="explicit",
        param_sets=[{"a": 0, "b": 1}, {"a": 2, "c": 1, "d": 2}, {}],
        metrics=["objective", "per_input"],
    )
    job = server.submit("alice", spec)
    assert job == "alice/j0"
    snap = server.result(job, wait=True, timeout=120)
    assert snap["state"] == "DONE"
    assert snap["done_tasks"] == snap["total_tasks"] > 0

    runs = spec.resolve(_SPACE)
    expected = _oracle_objective(workflow_from_layout(_LAYOUT), runs, _INPUTS)
    assert snap["result"]["objective"] == expected  # exact, not approx
    assert len(snap["result"]["per_input"]) == len(runs)
    assert snap["result"]["n_inputs"] == len(_INPUTS)
    # the registry released every key at job end
    assert server.registry.stats()["live_keys"] == 0
    jobs = server.list_jobs("alice")
    assert [j["job_id"] for j in jobs] == [job]


def test_identical_specs_execute_once_combined_lt_sum(sleepy_server):
    srv = sleepy_server
    mgr = srv.manager
    # Baseline: one tenant alone, a same-shape different-signature spec.
    warm = StudySpec(sampler="grid", bounds={"sp0": [0, 1], "sp1": [0, 1]})
    d0 = sum(mgr.dispatch_counts.values())
    assert srv.result(
        srv.submit("alice", warm), wait=True, timeout=120
    )["state"] == "DONE"
    single = sum(mgr.dispatch_counts.values()) - d0
    assert single > 0

    # Two tenants, equal signature, concurrent: one execution, two jobs.
    spec = StudySpec(sampler="grid", bounds={"sp0": [2, 3], "sp1": [2, 3]})
    d1 = sum(mgr.dispatch_counts.values())
    ja = srv.submit("alice", spec)
    jb = srv.submit("bob", spec)
    ra = srv.result(ja, wait=True, timeout=120)
    rb = srv.result(jb, wait=True, timeout=120)
    combined = sum(mgr.dispatch_counts.values()) - d1
    assert ra["state"] == "DONE" and rb["state"] == "DONE"
    assert ra["result"]["objective"] == rb["result"]["objective"]
    assert ra["signature"] == rb["signature"]
    # the tentpole claim: combined tasks < sum of independent submissions
    assert combined < 2 * single, (combined, single)


def test_overlapping_specs_reuse_shared_cache(server):
    rows_a = [{"a": i, "b": 0} for i in range(3)]
    rows_b = [{"a": i, "b": 0} for i in range(2)] + [{"a": 0, "b": 1}]
    spec_a = StudySpec(sampler="explicit", param_sets=rows_a)
    spec_b = StudySpec(sampler="explicit", param_sets=rows_b)
    assert spec_a.signature(_SPACE) != spec_b.signature(_SPACE)

    assert server.result(
        server.submit("alice", spec_a), wait=True, timeout=120
    )["state"] == "DONE"
    misses_before = server.cache.misses
    hits_before = server.cache.hits
    rb = server.result(server.submit("bob", spec_b), wait=True, timeout=120)
    assert rb["state"] == "DONE"
    service_misses = server.cache.misses - misses_before

    # Standalone: the same plan against a COLD cache.
    runs_b = spec_b.resolve(_SPACE)
    plan_b = plan_study(
        server.workflow, runs_b, cluster=server.cluster,
        policy=spec_b.policy, active_paths=spec_b.active_paths,
    )
    cold = ResultCache(1 << 20)
    stream = execute_study(
        plan_b, _INPUTS, cluster=ClusterSpec(n_workers=2), cache=cold,
        input_keys=server.input_keys,
    )
    # bit-identical across the reuse boundary, and cheaper than standalone
    assert rb["result"]["objective"] == _oracle_objective(
        server.workflow, runs_b, _INPUTS
    )
    assert stream.cache_misses == cold.misses
    assert service_misses < cold.misses, (service_misses, cold.misses)
    assert server.cache.hits > hits_before


# ---------------------------------------------------------------------------
# Cancellation: frees the pool without perturbing the other tenant
# ---------------------------------------------------------------------------


def test_cancel_mid_study_leaves_other_tenant_unperturbed(sleepy_server):
    srv = sleepy_server
    big = StudySpec(sampler="grid")  # 16 runs of sleepy tasks
    small = StudySpec(
        sampler="explicit",
        param_sets=[{"sp0": 0, "sp1": 0}, {"sp0": 1, "sp1": 1}],
    )
    ja = srv.submit("hog", big)
    jb = srv.submit("mouse", small)
    # let the big job actually get airborne, then revoke it
    deadline = time.monotonic() + 30
    while srv.status(ja)["state"] == "QUEUED":
        assert time.monotonic() < deadline
        time.sleep(0.005)
    time.sleep(0.05)
    cancelled_snap = srv.cancel(ja)
    assert cancelled_snap["state"] in ("RUNNING", "CANCELLED")

    ra = srv.result(ja, wait=True, timeout=60)
    rb = srv.result(jb, wait=True, timeout=120)
    assert ra["state"] == "CANCELLED"
    assert ra["result"] is None
    # the other tenant's study is untouched — exact oracle agreement
    assert rb["state"] == "DONE"
    assert rb["result"]["objective"] == _oracle_objective(
        srv.workflow, small.resolve(_SLEEP_SPACE), [5]
    )
    # cancel is idempotent
    assert srv.cancel(ja)["state"] == "CANCELLED"
    # the pool is actually free: no pending backlog, refs all released
    deadline = time.monotonic() + 10
    while srv.manager.scheduler_stats()["tenant_depths"]:
        assert time.monotonic() < deadline, "queued work never freed"
        time.sleep(0.02)
    assert srv.registry.stats()["live_keys"] == 0
    assert srv.manager.scheduler_stats()["cancelled"] > 0


def test_timeout_cancels_job(sleepy_server):
    spec = StudySpec(sampler="grid", timeout_s=0.15)
    job = sleepy_server.submit("t", spec)
    snap = sleepy_server.result(job, wait=True, timeout=60)
    assert snap["state"] == "CANCELLED"


# ---------------------------------------------------------------------------
# Fair share: the low-weight tenant still progresses
# ---------------------------------------------------------------------------


def test_fair_share_small_tenant_finishes_under_heavy_backlog(sleepy_server):
    srv = sleepy_server
    srv.set_tenant_weight("hog", 1.0)
    srv.set_tenant_weight("mouse", 0.25)
    # three distinct-signature grid jobs = a real backlog for the hog
    hog_jobs = [
        srv.submit("hog", StudySpec(sampler="grid")),
        srv.submit("hog", StudySpec(sampler="grid", bounds={"sp0": [0, 1, 2]})),
        srv.submit("hog", StudySpec(sampler="grid", bounds={"sp1": [1, 2, 3]})),
    ]
    mouse_job = srv.submit(
        "mouse",
        StudySpec(
            sampler="explicit",
            param_sets=[{"sp0": 0, "sp1": 0}, {"sp0": 3, "sp1": 3}],
        ),
    )
    rm = srv.result(mouse_job, wait=True, timeout=120)
    assert rm["state"] == "DONE"
    # monotonic progress: the mouse's 2 runs finished while (or before)
    # the hog's ~48-run backlog drained — never starved behind it
    hogs = [srv.result(j, wait=True, timeout=240) for j in hog_jobs]
    assert all(r["state"] == "DONE" for r in hogs)
    assert rm["finished_at"] <= max(r["finished_at"] for r in hogs)
    dispatch = srv.manager.scheduler_stats()["tenant_dispatch"]
    assert dispatch.get("mouse", 0) > 0 and dispatch.get("hog", 0) > 0


def test_fairqueue_unit_laws():
    class Item:
        def __init__(self, key, tenant="", priority=0):
            self.key, self.tenant, self.priority = key, tenant, priority

    # single tenant degenerates to exact FIFO
    q = FairQueue()
    for i in range(5):
        q.append(Item(f"k{i}"))
    assert [q.popleft().key for _ in range(5)] == [f"k{i}" for i in range(5)]

    # equal weights interleave 1:1
    q = FairQueue()
    for i in range(6):
        q.append(Item(f"a{i}", "A"))
    for i in range(6):
        q.append(Item(f"b{i}", "B"))
    order = [q.popleft().tenant for _ in range(12)]
    for window in range(0, 12, 2):
        assert set(order[window:window + 2]) == {"A", "B"}, order

    # 2:1 weight drains twice as fast, low weight still progresses
    q = FairQueue()
    q.set_weight("A", 2.0)
    q.set_weight("B", 0.25)
    for i in range(12):
        q.append(Item(f"a{i}", "A"))
    for i in range(3):
        q.append(Item(f"b{i}", "B"))
    order = [q.popleft().tenant for _ in range(15)]
    assert order.index("B") <= 8  # no starvation
    assert order.count("A") == 12 and order.count("B") == 3

    # priority beats FIFO within one tenant
    q = FairQueue()
    q.append(Item("lo", "T", priority=0))
    q.append(Item("hi", "T", priority=5))
    assert q.popleft().key == "hi"

    # appendleft refunds the spent deficit; remove_keys purges exactly
    q = FairQueue()
    for i in range(4):
        q.append(Item(f"x{i}", "X"))
    head = q.popleft()
    q.appendleft(head)
    assert q.popleft().key == head.key
    assert q.remove_keys({"x1", "x3"}) == 2  # x0 already consumed
    assert len(q) == 1
    assert q.depths() == {"X": 1}


# ---------------------------------------------------------------------------
# Quotas
# ---------------------------------------------------------------------------


def test_quota_rejection_is_atomic(sleepy_server):
    srv = sleepy_server
    srv.set_tenant_quota("q", TenantQuota(max_live_jobs=1))
    j0 = srv.submit("q", StudySpec(sampler="grid"))
    with pytest.raises(QuotaExceeded):
        srv.submit("q", StudySpec(sampler="grid", bounds={"sp0": [0]}))
    srv.cancel(j0)
    assert srv.result(j0, wait=True, timeout=60)["state"] == "CANCELLED"
    # terminal jobs free their live-job slot
    j2 = srv.submit("q", StudySpec(sampler="grid", bounds={"sp0": [0]}))
    assert srv.result(j2, wait=True, timeout=120)["state"] == "DONE"

    srv.set_tenant_quota("tiny", TenantQuota(max_live_tasks=1))
    with pytest.raises(QuotaExceeded):
        srv.submit("tiny", StudySpec(sampler="grid"))
    # other tenants are not affected by 'tiny's budget
    j3 = srv.submit("other", StudySpec(sampler="grid", bounds={"sp1": [1]}))
    assert srv.result(j3, wait=True, timeout=120)["state"] == "DONE"


def test_study_task_keys_matches_execution_exactly(server):
    """The registry's admission-time key list is exactly the key set the
    executor submits (quota accounting and cancellation both hang off
    this equality)."""
    spec = StudySpec(sampler="explicit", param_sets=[{"a": 1}, {"b": 2}])
    runs = spec.resolve(_SPACE)
    plan = plan_study(
        server.workflow, runs, cluster=server.cluster, policy=spec.policy,
        active_paths=spec.active_paths,
    )
    keys = study_task_keys(plan, len(_INPUTS), "svc:x:")
    assert len(keys) == len(set(keys))
    mgr = Manager()
    mgr.start(2)
    try:
        execute_study(
            plan, _INPUTS, manager=mgr, key_prefix="svc:x:",
            input_keys=server.input_keys,
        )
        # every submitted key was enumerated, nothing extra
        assert set(mgr.results()) == set()  # executor forgets on exit
        dispatched = sum(mgr.dispatch_counts.values())
        assert dispatched <= len(keys)
    finally:
        mgr.close()


# ---------------------------------------------------------------------------
# Wire protocol
# ---------------------------------------------------------------------------


def test_wire_client_roundtrip(server):
    addr = server.serve_background("127.0.0.1:0")
    alice = ServiceClient(addr, "alice")
    bob = ServiceClient(addr, "bob")
    try:
        spec = StudySpec(
            sampler="explicit", param_sets=[{"a": 1}, {"c": 1}]
        )
        job = alice.submit(spec)
        snap = alice.status(job)
        assert snap["tenant"] == "alice"
        res = alice.result(job, timeout=120, poll_s=0.02)
        assert res["state"] == "DONE"
        assert res["result"]["objective"] == _oracle_objective(
            server.workflow, spec.resolve(_SPACE), _INPUTS
        )
        # bob sees only his own jobs unless he asks for all
        assert bob.list_jobs() == []
        assert [j["job_id"] for j in bob.list_jobs(all_tenants=True)] == [job]
        bob.set_tenant_weight(2.0)
        stats = bob.server_stats()
        assert stats["registry"]["jobs"] == 1
        assert "scheduler" in stats and "cache" in stats

        # error frames surface as ServiceError, connection stays usable
        with pytest.raises(ServiceError):
            alice.status("alice/ghost")
        with pytest.raises(ServiceError):
            alice.submit(StudySpec(sampler="grid", names=["ghost"]))
        assert alice.status(job)["state"] == "DONE"
    finally:
        alice.close()
        bob.close()


def test_wire_cancel_and_quota_over_socket(sleepy_server):
    addr = sleepy_server.serve_background("127.0.0.1:0")
    sleepy_server.set_tenant_quota("w", TenantQuota(max_live_jobs=1))
    client = ServiceClient(addr, "w")
    try:
        job = client.submit(StudySpec(sampler="grid"))
        with pytest.raises(ServiceError) as err:
            client.submit(StudySpec(sampler="grid", bounds={"sp0": [0]}))
        assert "QuotaExceeded" in str(err.value)
        snap = client.cancel(job)
        assert snap["state"] in ("RUNNING", "CANCELLED", "QUEUED")
        assert client.result(job, timeout=60)["state"] == "CANCELLED"
    finally:
        client.close()


def test_submit_rejects_bad_tenant_and_closed_server():
    srv = StudyServer(
        workflow=workflow_from_layout(_LAYOUT),
        space=_SPACE,
        inputs=_INPUTS,
        objective=_int_objective,
        n_workers=1,
    )
    with pytest.raises(SpecError):
        srv.submit("", StudySpec(sampler="grid", names=["a"]))
    with pytest.raises(SpecError):
        srv.submit("a/b", StudySpec(sampler="grid", names=["a"]))
    srv.close()
    with pytest.raises(RuntimeError):
        srv.submit("alice", StudySpec(sampler="grid", names=["a"]))


# ---------------------------------------------------------------------------
# Idle-pool accounting (ISSUE 10 satellite): parked pumps, honest stats
# ---------------------------------------------------------------------------


def test_idle_pool_parks_and_stats_report_active_wall():
    mgr = Manager()
    mgr.start(2)
    try:
        done = threading.Event()
        mgr.submit(
            WorkItem(key="w0", fn=lambda: 1, callback=lambda k, v: done.set())
        )
        assert done.wait(30)
        mgr.drain()
        time.sleep(0.4)  # a multi-job lifetime's idle gap
        stats = mgr.scheduler_stats()
        # the pump parked for (nearly) the whole idle window instead of
        # spinning, and idle time is excluded from the occupancy base
        assert stats["pump_parked_seconds"] > 0.25
        assert stats["active_wall_seconds"] < stats["wall_seconds"]
        assert 0.0 <= stats["worker_idle_fraction"] <= 1.0
        assert stats["pump_occupancy"] <= 1.5  # sane against ACTIVE wall

        # a second job after the idle gap still executes immediately
        t0 = time.monotonic()
        mgr.submit(WorkItem(key="w1", fn=lambda: 2))
        mgr.drain()
        assert time.monotonic() - t0 < 5.0
        assert mgr.results()["w1"] == 2
        parked_after = mgr.scheduler_stats()["pump_parked_seconds"]
        assert parked_after >= stats["pump_parked_seconds"] - 1e-6
    finally:
        mgr.close()
