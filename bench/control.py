"""The control of the comparison that decides ``correct``.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

Run on the chip, at the cell's own sizes. For each seed it makes the run's
tiles, draws the evaluations of the study's first group that a run
compares (at 4096² a run's window holds that group alone), and computes
each tile's default mask and the sampled masks with the plain reference
twice: in float32, as the configuration states, and in
bfloat16, the nearest precision below. The bfloat16 answers take the
program's place in ``bench/check.py``'s comparison; one JSON line per seed
gives the numbers that comparison reads, beside the cell's limits. A sound
comparison rejects every seed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import check
import gen
import run  # also puts the program's src/ on the import path


def control_numbers(config, traffic, seed: int):
    import jax.numpy as jnp

    edge = config["tile_px"]
    space = gen.SPACES[config["space"]]
    n_tiles = config["n_tiles"]
    tiles = [gen.synthetic_tile(edge, edge, seed=s) for s in gen.tile_seeds(seed, n_tiles)]
    group = gen.design(config["design"], space, config["design_size"], seed=config["design_seed"])[0]
    picked = check.sample_ids(seed, 0, n_tiles, len(group), traffic["check_per_tile"])
    sample = [(i, dict(group[r])) for i, r in picked]
    default = dict(gen.default_params(space))
    f32 = check.reference_answers(tiles, default, sample, jnp.float32)
    bf16 = check.reference_answers(tiles, default, sample, jnp.bfloat16)
    return check.compare(bf16[0], bf16[1], f32[0], f32[1]), picked


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    dev, _ = run.require_chip(spec["cell"]["chips"])
    from repro import device as program_device

    program_device.use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        numbers, picked = control_numbers(spec["config"], spec["traffic"], seed)
        correct, shown = check.judge(numbers, spec["config"]["limits"])
        print(json.dumps({"workload": args.workload, "seed": seed, "control_correct": correct,
                          "sample": [[0, i, r] for i, r in picked], "seconds": time.perf_counter() - t0,
                          "device": dev.device_kind, "compared": shown}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
