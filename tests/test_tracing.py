"""Program spans (``repro.tracing``) in a real profiler trace, on the CPU.

A two-tile 128² ``hybrid`` study on two thread workers is traced under
``jax.profiler``; the spans are read back from the ``.xplane.pb`` with
their stats and host line (one line per Python thread)."""

import glob
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.app.pipeline import TABLE1_SPACE, build_workflow, synthetic_tile
from repro.core import halton_sequence
from repro.engine import ClusterSpec, ResultCache, execute_study, plan_study
from repro.engine.streaming import study_task_keys

SIZE = 128
N_RUNS = 6


def _traced(log_dir, fn):
    """``fn()``'s result and the ``sa/*`` host spans recorded while it ran,
    as ``(name, line, start_ns, end_ns, stats)``."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(log_dir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("sa/"):
                    spans.append((ev.name, li, int(ev.start_ns), int(ev.end_ns), dict(ev.stats)))
    return out, spans


@pytest.fixture(scope="module")
def study(tmp_path_factory):
    wf = build_workflow(SIZE, SIZE)
    sets = TABLE1_SPACE.quantise(halton_sequence(N_RUNS, TABLE1_SPACE.dim))
    tiles = [{"raw": jax.device_put(synthetic_tile(SIZE, SIZE, seed=s))} for s in (1, 2)]
    cluster = ClusterSpec(n_workers=2, enable_backup_tasks=False)
    cache = ResultCache(1 << 30)

    def run(prefix):
        plan = plan_study(wf, sets, cluster=cluster, policy="hybrid", max_bucket_size=4)
        res = execute_study(plan, tiles, cluster=cluster, cache=cache, key_prefix=prefix)
        jax.block_until_ready([o["mask"] for per in res.outputs.values() for o in per.values()])
        return plan, res

    first = _traced(tmp_path_factory.mktemp("first"), lambda: run("g0:"))
    second = _traced(tmp_path_factory.mktemp("second"), lambda: run("g1:"))
    return first, second


def test_one_task_span_per_executed_task(study):
    ((plan, res), spans) = study[0]
    tasks = [s for s in spans if s[0].startswith("sa/task/")]
    assert res.tasks_executed > 0
    assert len(tasks) == res.tasks_executed
    names = {t.name for st in plan.workflow.stages for t in st.tasks}
    assert {s[0][len("sa/task/"):] for s in tasks} <= names
    for _, _, _, _, stats in tasks:
        assert set(stats) == {"bucket", "depth", "leaf", "runs"}
        assert stats["leaf"] in (0, 1) and stats["runs"] >= 1


def test_task_spans_name_their_bucket_and_lie_inside_it(study):
    ((plan, res), spans) = study[0]
    keys = set(study_task_keys(plan, 2, "g0:"))
    buckets = [s for s in spans if s[0] == "sa/bucket"]
    assert {b[4]["bucket"] for b in buckets} == keys
    for name, line, t0, t1, stats in spans:
        if not name.startswith("sa/task/"):
            continue
        assert stats["bucket"] in keys
        assert any(
            b[1] == line and b[2] <= t0 and t1 <= b[3] and b[4]["bucket"] == stats["bucket"]
            for b in buckets
        ), name
    # the leaves of the last stage carry every evaluation once per tile
    last = plan.stages[-1].stage.name
    leaf_runs = sum(
        s[4]["runs"] for s in spans
        if s[0].startswith("sa/task/") and s[4]["leaf"] and f":{last}:" in s[4]["bucket"]
    )
    assert leaf_runs == 2 * N_RUNS


def test_engine_and_scheduler_spans(study):
    ((plan, res), spans) = study[0]
    n_buckets = 2 * sum(len(sp.buckets) for sp in plan.stages)
    (p,) = [s for s in spans if s[0] == "sa/plan"]
    assert p[4] == {"runs": N_RUNS, "policy": "hybrid"}
    stages = [s for s in spans if s[0] == "sa/submit_stage"]
    assert len(stages) == 2 * len(plan.stages)
    assert sum(s[4]["buckets"] for s in stages) == n_buckets
    assert {s[4]["prefix"] for s in stages} == {"g0:"}
    assert sorted(s[4]["bucket"] for s in spans if s[0] == "sa/settle") == sorted(
        study_task_keys(plan, 2, "g0:")
    )
    ticks = [s[4] for s in spans if s[0] == "sa/dispatch"]
    # every completion is settled, and every lease offered, in a tick's span
    assert sum(t["completions"] for t in ticks) == n_buckets
    assert sum(t["leases"] for t in ticks) == n_buckets
    assert all(t["completions"] or t["leases"] for t in ticks)


def test_cache_hits_run_no_task_span(study):
    ((_, res), spans) = study[1]
    assert res.tasks_executed == 0 and res.cache_hits > 0
    assert not [s for s in spans if s[0].startswith("sa/task/")]
    assert [s for s in spans if s[0] == "sa/bucket"]


def test_manager_runs_without_jax():
    code = textwrap.dedent(
        """
        import sys
        from repro.runtime.manager import Manager, WorkItem

        mgr = Manager()
        mgr.start(2)
        for i in range(4):
            mgr.submit(WorkItem(key=f"k{i}", fn=lambda i=i: i * i))
        mgr.drain()
        assert sorted(mgr.results().values()) == [0, 1, 4, 9]
        mgr.close()
        print("jax" in sys.modules)
        """
    )
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env = {**os.environ, "PYTHONPATH": src}
    proc = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "False"
