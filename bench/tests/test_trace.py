"""The reduction from trace to metrics (``bench/trace.py``), on intervals
made by hand and on a small trace recorded on a v5e
(``data/small.xplane.pb``, made by ``record_trace.py``)."""

import os

import pytest

import roofline
import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small.xplane.pb")

EVENTS = [
    ("jit_fill_holes(11)", 0, 40),
    ("jit_morph_reconstruct_ref(7)", 30, 50),  # overlaps the first
    ("jit_fill_holes(11)", 70, 90),
    ("jit_subtract(3)", 90, 95),  # touches the one before
    ("jit_dice(5)", 120, 125),
]
SPANS = [("submit_group", 0, 60), ("dice", 92, 118), ("submit_group", 100, 119)]


def test_module_name():
    assert tr.module_name("jit_fill_holes(13653761556386091551)") == "fill_holes"
    assert tr.module_name("jit_morph_reconstruct_ref(1)") == "morph_reconstruct_ref"
    assert tr.module_name("custom-call") == "custom-call"


def test_union_and_busy():
    assert tr.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3), (5, 10)]
    assert tr.busy_ns(EVENTS, 0, 130) == 50 + 25 + 5
    # clipped to the window
    assert tr.busy_ns(EVENTS, 35, 80) == 15 + 10


def test_gaps():
    assert tr.gaps(EVENTS, 0, 130) == [(50, 70), (95, 120), (125, 130)]
    assert tr.gaps([], 0, 10) == [(0, 10)]


def test_per_module_counts_events_that_start_in_the_window():
    mods = tr.per_module(EVENTS, 0, 100)
    assert mods == {"fill_holes": (2, 60), "morph_reconstruct_ref": (1, 20), "subtract": (1, 5)}


def test_attribution_takes_the_span_that_overlaps_most():
    assert tr.attribute((95, 120), SPANS) == "dice"  # 23 ns against 19
    assert tr.attribute((50, 70), SPANS) == "submit_group"
    assert tr.attribute((125, 130), SPANS) == "idle"
    b = tr.breakdown(EVENTS, SPANS, 0, 130, top=2)
    assert b["device_ops"] == [["fill_holes", 60e-9], ["morph_reconstruct_ref", 20e-9]]
    assert b["idle_gaps"] == [["dice", 25e-9], ["submit_group", 20e-9]]


def test_roofline_share():
    mods = {"fill_holes": (2, 2_000_000_000), "morph_reconstruct_ref": (1, 1_000_000_000)}
    least = {"fill_holes": 100, "morph_reconstruct_ref": 600, "watershed_split": 7}
    # (2 * 100 + 600) bytes at 800 B/s is 1 s, over 3 s of device time
    assert tr.roofline_share(mods, least, 800.0) == pytest.approx(100 / 3)
    assert tr.roofline_share({"subtract": (1, 5)}, least, 800.0) is None


def test_least_bytes_at_4096():
    least = roofline.least_bytes(4096 * 4096)
    assert least["fill_holes"] == 2 * 4096 * 4096
    assert least["morph_reconstruct_ref"] == 12 * 4096 * 4096  # two f32 planes in, one out


def test_recorded_trace():
    device, spans = tr.load(DATA, ("window", "dice"))
    assert list(device) == [0]
    events = device[0]
    (window,) = [s for s in spans if s[0] == "window"]
    (dice,) = [s for s in spans if s[0] == "dice"]
    lo, hi = window[1], window[2]
    # the profile puts device events about 1.3 ms early against the host's
    # spans: nothing at the scale of a 51 s window, everything at this one's
    mods = tr.per_module(events, lo - 2_000_000, hi)
    assert mods["fill_holes"][0] == 4
    assert mods["morph_reconstruct_ref"][0] == 2
    busy = tr.busy_ns(events, lo, hi)
    assert 0 < busy < hi - lo
    # the 0.3 s sleep in "dice" is the longest idle gap, and is named for it
    b = tr.breakdown(events, [dice], lo, hi)
    name, seconds = b["idle_gaps"][0]
    assert name == "dice" and 0.29 < seconds < 0.31
    assert sum(t for _, t in b["device_ops"]) == pytest.approx(
        sum(e - s for _, s, e in events if lo <= s < hi) / 1e9
    )
    share = tr.roofline_share(mods, {"fill_holes": 2 * 512 * 512}, 819e9)
    assert 0 < share < 100
