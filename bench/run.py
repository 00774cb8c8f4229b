"""The on-chip benchmark of the SA engine: one cell, one seed, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, in one process that holds the chip. The
cell is an entry of ``BENCHMARK.json``'s ``workloads``; its configuration
(``bench/configs/<config>.json``), its traffic (``bench/traffic/<traffic>.json``)
and every metric's reader (``bench/metrics/<metric>.py``) are found by name.

Set-up (``setup_s``): check the chip, place the compile cache, make the
tiles from the seed, compute each tile's default-parameter mask, run every
static variant once, and start one Manager session with one shared
``ResultCache``. The window then feeds the study to
``repro.engine.execute_study`` one group of parameter sets per call, as
``run_dataset_study`` would plan it: the planner solves the active paths
from the device's memory. Each call's masks are compared with their tile's
default mask (Dice), and the group is complete when every mask and every
Dice is ready on the device. Groups are fed one after another until
``--seconds`` have passed; the window runs from the first group's start to
the last group's completion, so it holds whole groups only, and
``evals_per_s`` is their evaluations over its length. With ``--trace 1``
the profiler records the window's first ``--seconds`` and the per-layer
metrics are read from that span.

After the window a sample of the last group's evaluations, drawn from the
seed, and each tile's default mask are recomputed by the plain reference
(``bench/reference.py``) and compared (``bench/check.py``). The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``),
and ``compared`` last. Without a TPU whose kind is in ``bench/peaks.json``,
or with fewer chips than the cell asks for, it exits 3 and prints no result.
"""

from __future__ import annotations

T_START = __import__("time").perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402
from typing import Any, Dict, List  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_DIR = os.path.join(BENCH, ".trace")
SPANS = ("submit_group", "dice", "wait_masks", "traced")

sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))  # the program under test
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp

import numpy as np  # noqa: E402

import check  # noqa: E402
import gen  # noqa: E402
import trace as tr  # noqa: E402


class NoChip(RuntimeError):
    """The machine lacks the chip the cell needs: no result is printed."""


def _json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Dict[str, Any]:
    """The cell's entry, its configuration, its traffic and its metrics,
    all found by name from ``BENCHMARK.json``."""
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = _json(os.path.join(root, cfg_entry["file"]))
    traffic = _json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))

    def applies(m):
        return name in m.get("workloads", [name])

    return {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
    }


def reader(metric: str):
    """``bench/metrics/<metric>.py``'s ``read(ctx)``."""
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chip(chips: int):
    """The first device, if the default backend is a TPU of a kind in the
    peaks table with at least ``chips`` devices; else :class:`NoChip`."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise NoChip(f"no TPU: JAX's default backend is {backend!r}")
    devices = jax.devices()
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    peaks = _json(os.path.join(BENCH, "peaks.json"))
    kind = devices[0].device_kind
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return devices[0], peaks[kind]


class Study:
    """Set-up: the tiles, the design, the default masks and one started
    Manager session with its shared result cache."""

    def __init__(self, config, traffic, seed: int):
        import jax
        from repro.app.pipeline import build_workflow
        from repro.core import dice
        from repro.engine import ClusterSpec, MemoryBudget, ResultCache, execute_study, plan_study
        from repro.runtime.manager import Manager

        seed %= 2**63  # any whole number; numpy's seeds are non-negative
        self.traffic, self.seed = traffic, seed
        edge = config["tile_px"]
        space = gen.SPACES[config["space"]]
        n_tiles = config["n_tiles"]
        self.tiles = [gen.synthetic_tile(edge, edge, seed=s) for s in gen.tile_seeds(seed, n_tiles)]
        self.raws = [{"raw": jax.device_put(t)} for t in self.tiles]
        self.keys = [f"tile{k}" for k in range(n_tiles)]
        # the same design in every run: the seed draws the tiles and the sample
        self.groups = gen.design(
            config["design"], space, config["design_size"], seed=config["design_seed"]
        )

        dev = jax.devices()[0]
        limit = (dev.memory_stats() or {}).get("bytes_limit")
        # "device": the budget a deployment on this chip passes, its bytes_limit
        self.memory = MemoryBudget(bytes={"device": limit}[config["memory_budget"]])
        self.cluster = ClusterSpec(n_workers=traffic["workers"])
        self.wf = build_workflow(edge, edge)
        self.max_bucket_size = config.get("max_bucket_size")
        self.default = gen.default_params(space)
        # the default masks, and every static variant of the task functions
        # (FH / RC / WConn are 4 or 8; the default takes the 8s)
        ref_plan = plan_study(self.wf, [self.default], policy="rmsr", active_paths=1)
        refs = execute_study(ref_plan, self.raws, cluster=self.cluster)
        self.ref_masks = [refs.outputs[i][0]["mask"] for i in range(n_tiles)]
        variant = gen.paramset({**dict(self.default), "FH": 4, "RC": 4, "WConn": 4})
        var_plan = plan_study(self.wf, [variant], policy="rmsr", active_paths=1)
        warm = execute_study(var_plan, self.raws[:1], cluster=self.cluster).outputs[0][0]["mask"]
        jax.block_until_ready([dice(m, m) for m in self.ref_masks + [warm]])
        del refs, warm

        self.plan_study, self.execute_study, self.dice = plan_study, execute_study, dice
        self.cache = ResultCache(self.memory.effective_cache_bytes)
        self.manager = Manager(
            max_attempts=self.cluster.max_attempts,
            heartbeat_timeout=self.cluster.heartbeat_timeout,
            straggler_factor=self.cluster.straggler_factor,
            enable_backup_tasks=self.cluster.enable_backup_tasks,
        )
        self.manager.start(self.cluster.n_workers)

    def plan(self, g: int):
        """Group ``g``'s plan, as ``run_dataset_study`` makes it: the active
        paths solved from the memory budget."""
        return self.plan_study(
            self.wf, self.groups[g], memory=self.memory, cluster=self.cluster,
            policy=self.traffic["policy"], max_bucket_size=self.max_bucket_size,
        )

    def close(self) -> None:
        self.manager.close()
        self.cache = None


class Window:
    """Feeds whole groups to ``execute_study``, one call at a time, until
    ``seconds`` have passed, and waits for each group's masks and Dice on
    the device. The window is the first group's start to the last group's
    completion.

    Traced, the profiler records the window's first ``seconds``, marked by
    the host span ``traced``, and is stopped on a thread of its own, so that
    writing the trace out overlaps the rest of the window."""

    def __init__(self, study: Study, seconds: float, traced: bool = False):
        self.study, self.seconds, self.traced = study, seconds, traced
        self.calls: List[Dict[str, Any]] = []
        self.answers: Dict[tuple, tuple] = {}  # (tile, run) -> (mask, Dice) of the last group
        self.attempted = 0
        self.t_open = self.t_close = 0.0
        self.trace_stop_s = 0.0

    def _trace(self, cut: threading.Event) -> None:
        import jax

        with jax.profiler.TraceAnnotation("traced"):
            cut.wait(self.seconds)
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.trace_stop_s = time.perf_counter() - t0

    def run(self) -> None:
        import jax

        cut = threading.Event()
        tracer = threading.Thread(target=self._trace, args=(cut,), name="trace")
        if self.traced:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(TRACE_DIR)
        self.t_open = time.perf_counter()
        if self.traced:
            tracer.start()
        try:
            self._groups()
        finally:
            cut.set()
            if self.traced:
                tracer.join()

    def _groups(self) -> None:
        import jax

        st = self.study
        g = 0
        while g == 0 or time.perf_counter() < self.t_open + self.seconds:
            if g >= len(st.groups):
                raise RuntimeError("the study's design ran out inside the window")
            n_evals = len(st.raws) * len(st.groups[g])
            self.attempted += n_evals
            with jax.profiler.TraceAnnotation("submit_group"):
                t_plan = time.perf_counter()
                plan = st.plan(g)
                t0 = time.perf_counter()
                res = st.execute_study(
                    plan, st.raws, cache=st.cache, manager=st.manager,
                    input_keys=st.keys, key_prefix=f"g{g}:",
                )
                t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("dice"):
                answers = {
                    (i, r): (res.outputs[i][r]["mask"], st.dice(res.outputs[i][r]["mask"], st.ref_masks[i]))
                    for i in range(len(st.raws))
                    for r in range(len(st.groups[g]))
                }
            with jax.profiler.TraceAnnotation("wait_masks"):
                jax.block_until_ready(list(answers.values()))
            t2 = time.perf_counter()
            self.calls.append({
                "t_plan": t_plan, "t0": t0, "t1": t1, "t2": t2, "evals": n_evals, "group": g,
                "tasks": res.tasks_executed, "hits": res.cache_hits,
                "retries": res.retries, "backups": res.backups_launched,
            })
            self.answers = answers
            del res
            g += 1
        self.t_close = time.perf_counter()

    def evals(self) -> int:
        return sum(c["evals"] for c in self.calls)

    def evals_per_s(self) -> float:
        return self.evals() / (self.t_close - self.t_open)


def reduce_trace() -> Dict[str, Any] | None:
    """The traced span's device numbers (``bench/trace.py``); the trace
    itself is deleted once read."""
    device, spans = tr.load(tr.find_xplane(TRACE_DIR), SPANS)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    marks = [s for s in spans if s[0] == "traced"]
    if not device or not marks:
        return None
    events = device[min(device)]
    lo, hi = marks[0][1], marks[0][2]
    return {
        "window_ns": hi - lo,
        "busy_ns": tr.busy_ns(events, lo, hi),
        "modules": tr.per_module(events, lo, hi),
        "breakdown": tr.breakdown(events, [s for s in spans if s[0] != "traced"], lo, hi),
    }


def run(workload: str, seed: int, seconds: float, traced: bool) -> Dict[str, Any]:
    """One run of one cell; returns the result line as a dict."""
    spec = load_cell(workload)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    dev, peaks = require_chip(cell["chips"])
    import jax
    from repro import device as program_device

    program_device.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    study = Study(config, traffic, seed)
    setup_s = time.perf_counter() - T_START

    window = Window(study, seconds, traced)
    window.run()
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    t_read = time.perf_counter()
    trace = reduce_trace() if traced else None
    trace_read_s = time.perf_counter() - t_read

    # the program's answers to compare, then free the program's state
    last = window.calls[-1]["group"]
    picked = check.sample_ids(study.seed, last, len(study.tiles), len(study.groups[last]),
                              traffic["check_per_tile"])
    prog_defaults = [np.asarray(m) for m in study.ref_masks]
    prog_sample = [(np.asarray(window.answers[e][0]), float(window.answers[e][1])) for e in picked]
    sample = [(i, dict(study.groups[last][r])) for i, r in picked]
    tiles, default = study.tiles, dict(study.default)
    study.close()
    del study
    window.answers = {}
    ref_defaults, ref_sample = check.reference_answers(tiles, default, sample, np.float32)
    numbers = check.compare(prog_defaults, prog_sample, ref_defaults, ref_sample)
    correct, shown = check.judge(numbers, config["limits"])

    ctx = {
        "setup_s": setup_s,
        "window": {"evals_per_s": window.evals_per_s(), "seconds": window.t_close - window.t_open},
        "calls": window.calls,
        "trace": trace,
        "tile_px": config["tile_px"] ** 2,
        "peaks": peaks,
        "device": {"memory_peak_bytes": peak},
    }
    metrics = {}
    for m in spec["per_layer"] if traced else spec["end_to_end"]:
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device_out = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": jax.device_count(),
        "memory_peak_bytes": peak,
    }
    out = {
        "correct": correct,
        "attempted": window.attempted,
        "failed": window.attempted - window.evals(),
        "metrics": metrics,
        "device": device_out,
    }
    if trace is not None:
        device_out["busy_s"] = trace["busy_ns"] / 1e9
        device_out["window_s"] = trace["window_ns"] / 1e9
        out["breakdown"] = trace["breakdown"]
    out["detail"] = {
        "window_s": window.t_close - window.t_open,
        "groups": len(window.calls),
        "group_s": [c["t2"] - c["t_plan"] for c in window.calls],
        "return_s": [c["t1"] - c["t0"] for c in window.calls],
        "retries": sum(c["retries"] for c in window.calls),
        "backups": sum(c["backups"] for c in window.calls),
        "cache_hits": sum(c["hits"] for c in window.calls),
        "sample": [[last, i, r] for i, r in picked],
    }
    if traced:
        out["detail"].update(trace_stop_s=window.trace_stop_s, trace_read_s=trace_read_s)
    out["compared"] = shown
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr, flush=True)
        return 3
    for name, c in out["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
