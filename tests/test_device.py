"""The program's contract with the device it runs on (``repro.device``):

* paths that spawn JAX child processes refuse to start on a TPU host,
  before anything is spawned (a chip belongs to one process);
* a Pallas kernel asked for off a TPU is an error, never a silent switch
  to the interpreter;
* the persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
  says, else to ``<checkout>/.jax_cache``;
* ``chip_smoke.py`` fails, and prints no result, without a TPU or without
  the rest of the repository.
"""

import multiprocessing
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import device
from repro.app import ops as app_ops
from repro.kernels import ops as kops
from repro.kernels.morph_recon import morph_reconstruct_pallas
from repro.kernels.ref import morph_reconstruct_ref
from repro.runtime import ProcessRpcBackend, SocketBackend
from repro.study import run_fleet_study

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def fake_tpu(monkeypatch):
    monkeypatch.setattr(device, "on_tpu", lambda: True)


def _never_called(**_kw):
    raise AssertionError("the fleet build ran before the refusal")


def test_process_backend_refuses_on_tpu(fake_tpu, tmp_path):
    before = multiprocessing.active_children()
    backend = ProcessRpcBackend(store_dir=str(tmp_path / "store"))
    with pytest.raises(RuntimeError, match="one process per chip"):
        backend.start(2)
    assert backend.worker_pids() == []
    assert multiprocessing.active_children() == before


def test_socket_backend_local_workers_refuse_on_tpu(fake_tpu, tmp_path):
    before = multiprocessing.active_children()
    backend = SocketBackend(store=str(tmp_path / "store"))
    with pytest.raises(RuntimeError, match="one process per chip"):
        backend.start(2)
    assert backend.address is None  # nothing was even bound
    assert multiprocessing.active_children() == before


def test_fleet_study_refuses_on_tpu(fake_tpu, tmp_path):
    before = multiprocessing.active_children()
    with pytest.raises(RuntimeError, match="one process per chip"):
        run_fleet_study(_never_called, n_procs=2, store_dir=str(tmp_path / "s"))
    assert multiprocessing.active_children() == before


def _recon_case():
    rng = np.random.default_rng(0)
    mask = jnp.asarray(rng.uniform(0, 100, (16, 16)).astype(np.float32))
    return jnp.maximum(mask - 30.0, 0.0), mask


def _attention_case():
    q = jnp.ones((1, 16, 2, 8), jnp.float32)
    return q, q, q


def _ssm_case():
    x = jnp.ones((1, 16, 2, 4), jnp.float32)
    a = jnp.full((1, 16, 2), 0.5, jnp.float32)
    b = jnp.ones((1, 16, 2, 3), jnp.float32)
    return x, a, b, b


@pytest.mark.parametrize(
    "name,case",
    [
        ("morph_reconstruct", _recon_case),
        ("flash_attention", _attention_case),
        ("ssm_scan", _ssm_case),
    ],
)
def test_kernel_wrappers_refuse_the_kernel_off_tpu(name, case):
    assert jax.default_backend() != "tpu"
    with pytest.raises(RuntimeError, match="off a TPU"):
        getattr(kops, name)(*case(), use_kernel=True)


def test_kernel_runs_off_tpu_only_when_the_interpreter_is_named():
    marker, mask = _recon_case()
    ref = morph_reconstruct_ref(marker, mask, conn=8)
    got = morph_reconstruct_pallas(marker, mask, conn=8, block=(8, 8), interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # by default the kernel is compiled for the chip, never interpreted
    with pytest.raises(ValueError, match="interpret"):
        morph_reconstruct_pallas(marker, mask, conn=8, block=(8, 8))
    # a wrapper with no kernel asked for off a TPU: the XLA reference
    np.testing.assert_array_equal(
        np.asarray(kops.morph_reconstruct(marker, mask)), np.asarray(ref)
    )


def test_pipeline_morphology_is_the_same_xla_loop_on_a_tpu(fake_tpu):
    # with the backend reported as a TPU, a bare call still takes the XLA
    # reference (a kernel call would fail here: no TPU is attached)
    marker, mask = _recon_case()
    got = app_ops.morph_reconstruct(marker, mask, conn=4)
    ref = morph_reconstruct_ref(marker, mask, conn=4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        device.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_lands_where_the_variable_says(tmp_path):
    cache = tmp_path / "cc"
    env = dict(
        os.environ, JAX_COMPILATION_CACHE_DIR=str(cache), JAX_PLATFORMS="cpu",
        PYTHONPATH=str(ROOT / "src"),
    )
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro import device\n"
        "device.use_compile_cache()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == str(cache)
    assert any(cache.iterdir()), "no compiled program was cached"


def _run_smoke(cwd, env):
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=str(cwd), env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_chip_smoke_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = _run_smoke(ROOT, env)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


def test_chip_smoke_fails_without_the_repository(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = _run_smoke(tmp_path, env)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
