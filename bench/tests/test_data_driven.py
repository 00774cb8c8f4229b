"""A new configuration, traffic mix and metric, each a file of its own plus
entries in BENCHMARK.json, make a new cell without editing any file the
benchmark already has."""

import hashlib
import json
import os

from helpers import last_line, load_harness, make_tree


def _digests(root):
    out = {}
    for dirpath, _dirs, files in os.walk(os.path.join(root, "bench")):
        for name in files:
            path = os.path.join(dirpath, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(f.read()).hexdigest()
    return out


def test_new_files_make_a_new_cell(tmp_path, monkeypatch, capsys):
    tree = make_tree(tmp_path, size=96)
    before = _digests(tree)
    bench = os.path.join(tree, "bench")
    with open(os.path.join(bench, "configs", "moat800-t1-4k.json")) as f:
        cfg = json.load(f)
    cfg.update(name="moat-small-budget", max_bucket_size=4, n_tiles=1)
    with open(os.path.join(bench, "configs", "moat-small-budget.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "one-tile.json"), "w") as f:
        json.dump({"policy": "rtma", "workers": 1, "check_per_tile": 1}, f)
    with open(os.path.join(bench, "metrics", "groups_per_run.py"), "w") as f:
        f.write('def read(ctx):\n    return float(len(ctx["calls"]))\n')

    spec_path = os.path.join(tree, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "moat-small-budget", "source": "https://arxiv.org/abs/1910.14548",
                            "file": "bench/configs/moat-small-budget.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": "moat-small-budget.one-tile", "config": "moat-small-budget",
                              "traffic": "one-tile", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "groups_per_run", "unit": "groups", "better": "higher",
                              "source": "host_clock", "layer": "scheduler and host",
                              "moves": "evals_per_s", "workloads": ["moat-small-budget.one-tile"]})
    with open(spec_path, "w") as f:
        json.dump(spec, f)

    h = load_harness(tree, monkeypatch)
    assert h.main(["--workload", "moat-small-budget.one-tile", "--seed", "4",
                   "--seconds", "1", "--trace", "1"]) == 0
    line = last_line(capsys.readouterr()[0])
    assert line["correct"] is True
    assert line["metrics"]["groups_per_run"]["value"] >= 1
    # an older cell does not report the metric that names only the new one
    assert h.main(["--workload", "moat800-t1-4k.hybrid", "--seed", "4",
                   "--seconds", "1", "--trace", "1"]) == 0
    assert "groups_per_run" not in last_line(capsys.readouterr()[0])["metrics"]

    after = _digests(tree)
    assert {k: v for k, v in after.items() if k in before} == before
