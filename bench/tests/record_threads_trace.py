"""Record the small two-thread TPU trace that ``test_spans.py`` joins.

    python3 bench/tests/record_threads_trace.py <out.xplane.pb>

Run on a machine with one TPU. Inside a host span ``window`` on the main
thread, two worker threads each open a span ``sa/bucket`` (stats ``bucket``
``w<k>``, ``nodes`` 3) and inside it three task spans in turn, as the
program's engine does: ``sa/task/one`` launches one jitted program,
``sa/task/two`` two and ``sa/task/three`` (the leaf, ``runs`` 2) three, the
last of them the long ``sweep``. No program is launched outside a task
span, and no thread waits for a program inside one: the main thread waits
for both threads' results after they have finished launching, so the
device runs the programs while the host goes on launching.
"""

import glob
import os
import shutil
import sys
import tempfile
import threading

import jax
import jax.numpy as jnp

LAUNCHES = {"one": 1, "two": 2, "three": 3}


@jax.jit
def step(x):
    return jnp.tanh(x) * 0.5 + 0.25


@jax.jit
def sweep(x):
    def body(c):
        m, i = c
        return jnp.maximum(m, jnp.roll(m, 1, 0)) * 0.999, i + 1

    return jax.lax.while_loop(lambda c: c[1] < 200, body, (x, 0))[0]


def worker(k: int, x, out: list) -> None:
    with jax.profiler.TraceAnnotation("sa/bucket", bucket=f"w{k}", nodes=len(LAUNCHES)):
        for depth, (name, n) in enumerate(LAUNCHES.items()):
            leaf = int(depth == len(LAUNCHES) - 1)
            with jax.profiler.TraceAnnotation(
                f"sa/task/{name}", bucket=f"w{k}", depth=depth, leaf=leaf, runs=2 if leaf else 1
            ):
                for i in range(n):
                    x = sweep(x) if leaf and i == n - 1 else step(x)
    out.append(x)


def main(out_path: str) -> None:
    assert jax.default_backend() == "tpu", jax.default_backend()
    x = jnp.zeros((1024, 1024), jnp.float32).at[0, :].set(1.0)
    xs = [x + k for k in range(2)]
    jax.block_until_ready([step(x), sweep(x)] + xs)
    tmp = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out_path)))
    jax.profiler.start_trace(tmp)
    out: list = []
    with jax.profiler.TraceAnnotation("window"):
        threads = [threading.Thread(target=worker, args=(k, xs[k], out)) for k in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        jax.block_until_ready(out)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(path, out_path)
    shutil.rmtree(tmp)
    print(out_path, os.path.getsize(out_path))


if __name__ == "__main__":
    main(sys.argv[1])
