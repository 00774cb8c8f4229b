"""Record the small TPU trace that ``test_trace.py`` reduces.

    python3 bench/tests/record_trace.py <out.xplane.pb>

Run on a machine with one TPU. Inside a host span ``window`` it runs the
jitted ``fill_holes`` three times and ``morph_reconstruct_ref`` twice on
512x512 planes, then sleeps 0.3 s inside a host span ``dice`` while the
device has nothing to do, then runs ``fill_holes`` once more. The functions
stand in for the program's operators of those names; only their names,
calls and shapes matter to the reduction.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


@jax.jit
def fill_holes(x):
    def body(c):
        m, _ = c
        new = jnp.maximum(m, jnp.roll(m, 1, 0))
        return new, jnp.any(new != m)

    return jax.lax.while_loop(lambda c: c[1], body, (x, jnp.bool_(True)))[0] > 0.5


@jax.jit
def morph_reconstruct_ref(marker, mask):
    return jnp.minimum(jnp.maximum(marker, jnp.roll(marker, 1, 1)), mask)


def main(out_path: str) -> None:
    assert jax.default_backend() == "tpu", jax.default_backend()
    x = jnp.zeros((512, 512), jnp.float32).at[0, :].set(1.0)
    fill_holes(x).block_until_ready()
    morph_reconstruct_ref(x, x).block_until_ready()
    tmp = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out_path)))
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            fill_holes(x).block_until_ready()
        for _ in range(2):
            morph_reconstruct_ref(x, x).block_until_ready()
        with jax.profiler.TraceAnnotation("dice"):
            time.sleep(0.3)
        fill_holes(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(path, out_path)
    shutil.rmtree(tmp)
    print(out_path, os.path.getsize(out_path))


if __name__ == "__main__":
    main(sys.argv[1])
