"""The join from device programs to task spans (``bench/spans.py``), on
tuples made by hand and on a two-thread trace recorded on a v5e
(``data/threads.xplane.pb``, made by ``record_threads_trace.py``)."""

import json
import os

import pytest

import spans as sp
import trace as tr

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "tests", "data", "threads.xplane.pb")


def task(name, line, s, e, **stats):
    return (f"sa/task/{name}", line, s, e, {"bucket": "g0:in0:1:seg:0", "runs": 1, **stats})


# two threads (lines 1 and 2); the traced span is [100, 1000)
SPANS = [
    ("sa/bucket", 1, 90, 700, {"bucket": "g0:in0:1:seg:0", "nodes": 3}),
    task("a", 1, 95, 300),  # cut by the window's start
    task("b", 1, 300, 500),
    task("c", 1, 500, 690, leaf=1, runs=4),
    task("a", 2, 150, 400),
    task("c", 2, 400, 1100, leaf=1, runs=2),  # cut by the window's end
]
# (line, launch time, flow); the module of flow f is launched at that mark
LAUNCHES = [
    (1, 96, 1), (1, 120, 2), (1, 310, 3), (1, 320, 4), (1, 510, 5),
    (2, 160, 6), (2, 410, 7), (2, 990, 8), (2, 1050, 9),
    (0, 200, 10),  # the main thread: no task span
]
SITES = {f: (line, t) for line, t, f in LAUNCHES}
MODULES = [
    ("jit_a", 98, 110, 1),  # starts before the window
    ("jit_a", 110, 130, 2),
    ("jit_b", 320, 340, 3),
    ("jit_b", 340, 360, 4),
    ("jit_c", 520, 900, 5),
    ("jit_a", 170, 200, 6),
    ("jit_c", 420, 600, 7),
    ("jit_c", 995, 1200, 8),  # straddles the window's end: counted whole
    ("jit_c", 1060, 1090, 9),  # starts after it
    ("jit_dice", 210, 230, 10),  # launched outside any task span
]


def test_join_by_hand():
    j = sp.join(MODULES, SITES, SPANS, LAUNCHES, 100, 1000)
    assert j["tasks"] == {
        "a": [2, 2, 20 + 30],
        "b": [1, 2, 40],
        "c": [2, 3, 380 + 180 + 205],
    }
    assert j["module_ns"] == 20 + 40 + 380 + 30 + 180 + 205 + 20
    assert j["unclaimed_ns"] == 20
    # line 1's leaf ended at 900 with every program recorded; line 2's last
    # program ended after the window
    assert j["done"] == [[800, 4, "g0:in0:1:seg:0"]]


def test_a_leaf_with_a_program_missing_has_no_completion():
    j = sp.join(MODULES[:4], SITES, SPANS, LAUNCHES, 100, 1000)
    assert j["done"] == []
    spans = [task("c", 1, 500, 690, leaf=1)]
    launches = [(1, 510, 5), (1, 520, 11)]  # the second program was not recorded
    assert sp.join([MODULES[4]], SITES, spans, launches, 100, 1000)["done"] == []
    assert sp.join([MODULES[4]], SITES, spans, launches[:1], 100, 1000)["done"] == [
        [800, 1, "g0:in0:1:seg:0"]
    ]


def test_launch_outside_every_span_is_unclaimed():
    j = sp.join(MODULES[-1:], SITES, SPANS, LAUNCHES, 100, 1000)
    assert j == {"tasks": {}, "module_ns": 20, "unclaimed_ns": 20, "done": []}
    # a module whose launch left no linkage in the trace is unclaimed too
    assert sp.join(MODULES[:2], {}, SPANS, LAUNCHES, 100, 1000)["unclaimed_ns"] == 20


def test_innermost_span_claims_and_nodes_count():
    spans = [
        ("sa/task/outer", 1, 0, 100, {"nodes": 3}),
        ("sa/task/inner", 1, 10, 20, {}),
    ]
    sites = {1: (1, 15), 2: (1, 50)}
    j = sp.join([("m", 0, 5, 1), ("m", 5, 9, 2)], sites, spans, [], 0, 10)
    assert j["tasks"] == {"inner": [1, 1, 5], "outer": [3, 1, 4]}


def test_launch_sites_follow_the_sequenced_event_to_its_launch():
    linkages = [(5, 100, 71), (6, 200, 72)]
    executes = [(1, 101, 190, 71), (2, 201, 290, 72)]
    systems = [(1, 150, 81), (2, 250, 82)]
    # thread 1's sequenced event holds its own enqueue and then thread 2's
    sequenced = [(1, 160, 170, 81), (1, 171, 180, 82)]
    enqueues = [(1, 165, 901), (1, 175, 902), (2, 260, 903)]
    sites = sp.launch_sites(linkages, executes, systems, sequenced, enqueues)
    # 903 lies in no sequenced event: it belongs to the launch that holds it
    assert sites == {901: (5, 100), 902: (6, 200), 903: (6, 200)}


def test_program_spans_name_gaps_innermost_first():
    program = [("sa/bucket", 1, 0, 100, {}), ("sa/task/x", 1, 10, 20, {})]
    named = sp.gap_spans(program) + [("submit_group", 0, 200)]
    assert tr.attribute((12, 18), named) == "sa/task/x"
    assert tr.attribute((30, 40), named) == "sa/bucket"
    assert tr.attribute((150, 160), named) == "submit_group"


def test_summary():
    s = sp.summary(sp.join(MODULES, SITES, SPANS, LAUNCHES, 100, 1000))
    assert s["task_device_ms"] == pytest.approx((50 + 40 + 765) / 5 / 1e6)
    assert s["launches_per_task"] == pytest.approx(7 / 5)
    assert s["unattributed_device_share"] == pytest.approx(20 / 875)
    assert s["tasks"]["b"] == {"tasks": 1, "launches": 2, "device_s": 40 / 1e9}
    empty = sp.summary(sp.join([], {}, [], [], 100, 1000))
    assert empty == {
        "tasks": {}, "task_device_ms": None, "launches_per_task": None,
        "unattributed_device_share": None,
    }


def test_recorded_two_thread_trace():
    loaded = sp.load(DATA)
    _, hs = tr.load(DATA, ("window",))
    (window,) = hs
    lo, hi = window[1], window[2]
    assert len(loaded["modules"]) == 12
    sites = sp.launch_sites(
        loaded["linkages"], loaded["executes"], loaded["systems"], loaded["sequenced"],
        loaded["enqueues"],
    )
    # every module is tied to a launch on one of the two workers' lines
    assert len(sites) == 12
    lines = {s[1] for s in loaded["spans"]}
    assert len(lines) == 2 and {line for line, _ in sites.values()} == lines
    # the profile puts device events about 1.3 ms early against host spans
    j = sp.reduce(loaded, lo - 2_000_000, hi)
    assert {k: v[:2] for k, v in j["tasks"].items()} == {
        "one": [2, 2], "two": [2, 4], "three": [2, 6],
    }
    assert j["unclaimed_ns"] == 0 and j["module_ns"] > 0
    assert sum(v[2] for v in j["tasks"].values()) == j["module_ns"]
    # each leaf's completion is the end of its thread's last program, sweep
    sweeps = sorted(e for name, _, e, _ in loaded["modules"] if "sweep" in name)
    assert [d[0] + lo - 2_000_000 for d in j["done"]] == sweeps
    assert sorted(d[2] for d in j["done"]) == ["w0", "w1"]
    assert all(d[1] == 2 for d in j["done"])


def test_cli_on_recorded_trace(capsys):
    assert sp.main([DATA, "--window", "window"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert {k: v["launches"] for k, v in out["tasks"].items()} == {"one": 2, "two": 4, "three": 6}
    assert out["launches_per_task"] == pytest.approx(2.0)
    assert out["unattributed_device_share"] == 0.0
    assert 0 < out["busy_s"] <= out["window_s"]
    assert sorted(d[2] for d in out["done"]) == ["w0", "w1"]
    assert all(name.startswith("sa/") or name == "idle" for name, _ in out["breakdown"]["idle_gaps"])
    # without a window: the modules' extent
    assert sp.main([os.path.dirname(DATA)]) == 0
    assert json.loads(capsys.readouterr().out)["unattributed_device_share"] == 0.0
    assert sp.main([DATA, "--window", "nowhere"]) == 1
