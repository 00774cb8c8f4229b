"""End-to-end LM training driver with fault tolerance.

Trains a reduced-config model on the deterministic synthetic pipeline,
checkpointing asynchronously every --ckpt-every steps, and AUTO-RESUMES from
the latest checkpoint (kill it mid-run and restart to see). At production
scale the same step function runs under the (16,16)/(2,16,16) meshes via
launch/dryrun.py shardings.

    PYTHONPATH=src python examples/train_lm.py --arch yi_6b --steps 30
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro import device
from repro.checkpoint import Checkpointer
from repro.configs import SHAPES, get_config, reduced_config
from repro.data import TokenPipeline
from repro.launch.steps import make_train_step
from repro.models import init_params
from repro.optim import OptConfig, adamw_init


def main() -> None:
    device.use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_6b")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    args = ap.parse_args()

    cfg = reduced_config(get_config(args.arch))
    shape = dataclasses.replace(
        SHAPES["train_4k"], seq_len=args.seq, global_batch=args.batch
    )
    pipe = TokenPipeline(cfg, shape, seed=0)
    ckpt = Checkpointer(args.ckpt_dir, keep=2)

    params = init_params(cfg, jax.random.key(0))
    opt_state = adamw_init(params)
    start = 0
    if ckpt.latest_step() is not None:  # fault-tolerant auto-resume
        (params, opt_state), meta = ckpt.restore((params, opt_state))
        pipe.restore(meta["pipeline"])
        start = meta["pipeline"]["step"]
        print(f"resumed from step {start}")

    step_fn = jax.jit(make_train_step(cfg, None, OptConfig(lr=1e-3, warmup_steps=10)))
    t0 = time.time()
    for step in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(step).items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        pipe.step = step + 1
        if (step + 1) % args.ckpt_every == 0 or step + 1 == args.steps:
            ckpt.save_async(
                step + 1, (params, opt_state), metadata={"pipeline": pipe.state()}
            )
        if step % 5 == 0 or step + 1 == args.steps:
            print(
                f"step {step:4d} loss {float(metrics['loss']):.4f} "
                f"gnorm {float(metrics['grad_norm']):.3f} "
                f"({(time.time()-t0)/(step-start+1):.2f}s/step)"
            )
    ckpt.wait()
    print(f"done; checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
