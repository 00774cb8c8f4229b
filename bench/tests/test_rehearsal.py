"""CPU rehearsal of a whole run at a small tile: the stream of whole
groups, the window's rate, the comparison and the shape of the last line."""

import json
import os
import subprocess
import sys

import pytest

from helpers import ROOT, last_line, load_harness, make_tree

LIMITS = {"mask_diff_share": 1e-6, "dice_gap": 1e-6}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return make_tree(tmp_path_factory.mktemp("rehearsal"), size=128, limits=LIMITS)


def test_window_rate_is_whole_groups_over_the_window():
    import run

    w = run.Window.__new__(run.Window)
    w.t_open, w.t_close = 10.0, 14.0
    w.calls = [{"evals": 32}, {"evals": 32}]
    assert w.evals() == 64
    assert w.evals_per_s() == pytest.approx(16.0)


def test_sample_covers_each_half_of_each_tile():
    import check

    ids = check.sample_ids(2**31 + 5, 0, 2, 17, 4)
    assert len(ids) == 8 and len(set(ids)) == 8
    for tile in (0, 1):
        runs = sorted(r for i, r in ids if i == tile)
        assert len(runs) == 4
        assert sum(r < 17 / 2 for r in runs) >= 2 and sum(r >= 17 / 2 for r in runs) >= 1
    assert ids == check.sample_ids(2**31 + 5, 0, 2, 17, 4)


@pytest.mark.parametrize("cell", ["moat800-t1-4k.hybrid", "vbd7990-t1-4k.hybrid"])
def test_run_end_to_end(tree, monkeypatch, capsys, cell):
    h = load_harness(tree, monkeypatch)
    assert h.main(["--workload", cell, "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"]) == 0
    out, err = capsys.readouterr()
    line = last_line(out)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "detail", "compared"]
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["detail"]["groups"] >= 1 and line["detail"]["window_s"] >= 1.0
    assert len(line["detail"]["sample"]) == 8
    assert set(line["metrics"]) == {"evals_per_s", "setup_s"}
    assert line["metrics"]["evals_per_s"]["value"] > 0
    assert line["metrics"]["evals_per_s"]["unit"] == "evals/s"
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(line["compared"]) == set(LIMITS)
    tail = err.strip().splitlines()[-2:]
    assert [t.split(":")[0] for t in tail] == ["compared mask_diff_share", "compared dice_gap"]


def test_traced_run_reports_per_layer_metrics(tree, monkeypatch, capsys):
    h = load_harness(tree, monkeypatch)
    assert h.main(["--workload", "moat800-t1-4k.hybrid", "--seed", "9", "--seconds", "1", "--trace", "1"]) == 0
    line = last_line(capsys.readouterr()[0])
    # the CPU trace has no TPU plane, so the device readers find nothing
    assert set(line["metrics"]) == {"tasks_per_eval", "host_ms_per_eval"}
    assert line["metrics"]["tasks_per_eval"]["value"] > 0


def test_no_tpu_no_result(tree, capsys):
    h = load_harness(tree)
    assert h.main(["--workload", "moat800-t1-4k.hybrid", "--seed", "1", "--seconds", "1"]) == 3
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err


def test_bench_alone_exits_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and bench/ prints no result."""
    import shutil

    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "moat800-t1-4k.hybrid",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0 and proc.stdout == ""
