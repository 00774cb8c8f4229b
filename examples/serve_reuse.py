"""The paper's technique as an LM-serving feature (core/sa_serve.py).

An SA study over a serving pipeline's parameters — prompt choice, decoding
controls, acceptance threshold — executed with reuse-tree merging + RMSR
memory-bounded scheduling: parameter sets sharing a prompt share ONE prefill
(derived prefix caching); the activePaths bound caps live KV caches against
the HBM budget.

    PYTHONPATH=src python examples/serve_reuse.py
"""

import itertools

import jax
import numpy as np

from repro import device
from repro.configs import get_config, reduced_config
from repro.core.sa_serve import run_sa_serve
from repro.models import init_params


def main() -> None:
    device.use_compile_cache()
    cfg = reduced_config(get_config("gemma3_1b"))
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = {
        pid: rng.integers(0, cfg.vocab_size, (1, 12)).astype(np.int32)
        for pid in range(3)
    }
    # the SA grid: 3 prompts × 2 penalties × 2 top-k × 3 thresholds = 36 sets
    sets = [
        tuple(sorted({
            "prompt_id": pid, "rep_penalty": rp, "top_k": tk, "threshold": th,
        }.items()))
        for pid, rp, tk, th in itertools.product(
            range(3), (1.0, 1.3), (4, 16), (0.1, 0.3, 0.5)
        )
    ]
    out = run_sa_serve(
        cfg, params, prompts, sets, gen_len=6, max_len=32,
        hbm_budget_bytes=1 << 28, policy="rmsr",
    )
    print(
        f"{len(sets)} parameter sets -> {out['tasks_executed']}/{out['tasks_total']} "
        f"pipeline tasks executed ({out['reuse_fraction']*100:.0f}% reuse): "
        f"3 prefills, {out['tasks_executed']-3-len(sets)//1} generates deduped"
    )
    print(f"engine(rmsr) active_paths={out['active_paths']} "
          f"peak={out['peak_bytes']/1e6:.1f}MB")
    rates = out["accept_rate"]
    print("accept rates by (prompt, rp, top_k, thr):")
    for rid, ps in enumerate(sets[:6]):
        print(f"  {dict(ps)} -> {rates[rid]:.2f}")


if __name__ == "__main__":
    main()
