"""Benchmark harness — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV lines (plus the roofline summary if a
dry-run JSON is present), and writes one machine-readable
``BENCH_<module>.json`` artifact per executed module next to the CSV
(``--out-dir``, default CWD) so the perf trajectory accumulates run over
run. A failed module still produces its artifact (``"ok": false`` + the
traceback) and makes the harness exit non-zero after the remaining modules
finish.

Run: PYTHONPATH=src python -m benchmarks.run
     [--only fig6,fig7,table2,fig8,streaming,adaptive,fleet,rpc,net,service,analysis]
     [--out-dir DIR]
     [--quick]   (the CI smoke profile: shrinks sizes, same pipeline;
                  equivalent to REPRO_BENCH_SMOKE=1)

Modules are imported lazily, one by one, so a selection that needs no
accelerator stack (``--only analysis``, the static-analysis gate) runs in
a bare environment without jax installed.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
import traceback
from typing import List

from repro import device


def _rows_to_json(rows: List[str]) -> List[dict]:
    out = []
    for row in rows:
        name, us, derived = (row.split(",", 2) + ["", ""])[:3]
        try:
            us_val: object = float(us)
        except ValueError:
            us_val = us
        out.append({"name": name, "us_per_call": us_val, "derived": derived})
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only",
        default=None,
        help=(
            "comma list: fig6,fig7,table2,fig8,streaming,adaptive,fleet,"
            "rpc,net,service,analysis"
        ),
    )
    ap.add_argument(
        "--out-dir", default=".", help="where BENCH_<module>.json artifacts land"
    )
    ap.add_argument(
        "--quick",
        action="store_true",
        help="smoke profile (reduced sizes; numbers not comparable to full runs)",
    )
    args = ap.parse_args()
    if args.quick:
        # must precede the benchmarks.* imports: common.SMOKE reads it once
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    wanted = set(args.only.split(",")) if args.only else None
    out_dir = pathlib.Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)

    # names only — each module is imported when (and only when) selected,
    # so jax-free selections (--only analysis) run in a bare environment
    module_names = [
        "analysis", "fig6", "fig7", "table2", "fig8", "streaming",
        "adaptive", "fleet", "rpc", "net", "service",
    ]
    if wanted:
        unknown = wanted - set(module_names) - {"roofline"}
        if unknown:
            ap.error(f"unknown modules in --only: {sorted(unknown)}")
    import importlib

    csv: List[str] = ["name,us_per_call,derived"]
    failed: List[str] = []
    for name in module_names:
        if wanted and name not in wanted:
            continue
        t0 = time.time()
        start = len(csv)
        payload = {"module": name, "ok": True}
        try:
            if name != "analysis":  # the static-analysis gate runs without jax
                device.use_compile_cache()
            mod = importlib.import_module(f"benchmarks.{name}")
            mod.run(csv)
            print(f"# {name}: ok ({time.time()-t0:.1f}s)", file=sys.stderr)
        except Exception:  # noqa: BLE001
            err = traceback.format_exc()
            print(f"# {name}: FAILED\n{err}", file=sys.stderr)
            csv.append(f"{name}_FAILED,0,error")
            payload.update(ok=False, error=err)
            failed.append(name)
        payload.update(
            seconds=round(time.time() - t0, 3),
            rows=_rows_to_json(csv[start:]),
        )
        (out_dir / f"BENCH_{name}.json").write_text(json.dumps(payload, indent=1))

    # roofline summary from the dry-run, when present
    dj = pathlib.Path("experiments/dryrun.json")
    if dj.exists() and (wanted is None or "roofline" in wanted):
        for r in json.loads(dj.read_text()):
            if r.get("status") != "ok":
                continue
            csv.append(
                f"roofline_{r['arch']}_{r['shape']}_{r['mesh']},"
                f"{max(r['compute_s'], r['memory_s'], r['collective_s'])*1e6:.0f},"
                f"dom={r['dominant'].replace('_s','')}"
                f"_cf={r['roofline_fraction_compute']:.2f}"
                f"_useful={r.get('useful_flops_ratio', 0):.2f}"
            )
    print("\n".join(csv))
    if failed:
        print(f"# failing modules: {','.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
