"""What the program needs to know about the device it runs on.

* :func:`on_tpu` — whether JAX's default backend is a TPU.
* :func:`refuse_child_processes_on_tpu` — a TPU chip belongs to one
  process. A parent that has touched JAX holds it, and a child process
  that builds JAX state then fails or hangs, so the paths that spawn such
  children refuse to start on a TPU host.
* :func:`use_compile_cache` — where JAX keeps its persistent compilation
  cache; entry points call it, library imports and tests do not.

JAX is imported inside the functions, so entry points that run without it
(the static-analysis gate) can import this module.
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["on_tpu", "refuse_child_processes_on_tpu", "use_compile_cache"]

# src/repro/device.py -> the checkout root
_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def refuse_child_processes_on_tpu(what: str) -> None:
    """Raise before ``what`` spawns worker processes that build JAX state,
    when this process's default backend is a TPU."""
    if on_tpu():
        raise RuntimeError(
            f"{what} spawns worker processes that build JAX state, but a TPU "
            "chip belongs to one process at a time and this process already "
            "holds it (one process per chip); use the thread backend"
        )


def use_compile_cache() -> None:
    """Put JAX's persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads the variable itself, so
    nothing is set here), or else at ``<checkout>/.jax_cache``: a fixed
    path, because the path is part of what a later run must find again."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT / ".jax_cache"))
