"""A copy of the benchmark at a CPU-sized tile, and its harness loaded from
that copy with the chip check stubbed (the harness itself has no flag for it)."""

import importlib.util
import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def make_tree(dst, size=128, design_size=50, limits=None):
    """Copy ``BENCHMARK.json`` and ``bench/`` under ``dst``, link the program,
    and cut every configuration to ``size``-pixel tiles."""
    dst = str(dst)
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns(".trace", "__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    os.symlink(os.path.join(ROOT, "src"), os.path.join(dst, "src"))
    cdir = os.path.join(dst, "bench", "configs")
    for name in os.listdir(cdir):
        path = os.path.join(cdir, name)
        with open(path) as f:
            cfg = json.load(f)
        cfg["tile_px"], cfg["design_size"] = size, design_size
        if limits is not None:
            cfg["limits"] = limits
        with open(path, "w") as f:
            json.dump(cfg, f)
    return dst


def load_harness(tree, monkeypatch=None):
    """``bench/run.py`` of ``tree`` as a module; with ``monkeypatch``, its
    chip check accepts this process's first device as a v5e."""
    path = os.path.join(tree, "bench", "run.py")
    spec = importlib.util.spec_from_file_location(f"bench_run_{abs(hash(tree))}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if monkeypatch is not None:
        import jax

        # the program would put its compile cache in the checkout
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", os.path.join(tree, ".jax_cache"))

        with open(os.path.join(tree, "bench", "peaks.json")) as f:
            peaks = json.load(f)["TPU v5 lite"]
        monkeypatch.setattr(mod, "require_chip", lambda chips: (jax.devices()[0], peaks))
    return mod


def last_line(text):
    return json.loads(text.strip().splitlines()[-1])
