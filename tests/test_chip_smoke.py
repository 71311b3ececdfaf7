"""CPU tests for ``chip_smoke.py`` and the compile-cache helper.

The smoke's serving-and-check bodies run here on a tiny grid (one device,
and four virtual devices in a subprocess); the script itself must refuse to
run, printing no ok line, wherever JAX finds no TPU.
"""
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import compile_cache
from repro.graphs import grid_road
from repro.kernels import config as kcfg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def grid():
    return grid_road(12, 12, seed=0)


def test_serve_and_check_on_tiny_grid(grid):
    stats = chip_smoke.serve_and_check(grid, chip_smoke.make_traffic(grid.n, 0))
    assert stats["requests"] == 32
    assert stats["engine_full"] == 16 and stats["engine_point"] == 4
    assert stats["cache_hits"] == 8 and stats["coalesced"] == 4
    assert len(stats["wave_s"]) == 2
    # early exit: an s->t lane stops no later than its source's full solve
    assert max(stats["phases_point"]) <= max(stats["phases_full"])


@pytest.mark.parametrize("corrupt", ["row", "point", "outcome"])
def test_check_answers_rejects_a_wrong_answer(grid, corrupt):
    reqs, _ = chip_smoke.serve(chip_smoke.StaticBackend(grid, point_queries=True),
                               chip_smoke.make_traffic(grid.n, 0))
    chip_smoke.check_answers(grid, reqs)  # the served answers pass
    full = next(r for r in reqs if r.target is None)
    point = next(r for r in reqs if r.target is not None
                 and not r.cache_hit and not r.coalesced)
    if corrupt == "row":
        bad = full.dist.copy()
        bad[np.flatnonzero(np.isfinite(bad) & (bad > 0))[0]] *= 1.5
        full.dist = bad
    elif corrupt == "point":
        bad = point.dist.copy()
        bad[point.target] = np.nextafter(bad[point.target], np.float32(np.inf))
        point.dist = bad
    else:
        full.outcome = "failed"
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_answers(grid, reqs)


def test_sharded_phase_on_four_virtual_devices():
    script = (
        "import os, sys\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import jax, chip_smoke as cs\n"
        "g = cs.grid_road(12, 12, seed=0)\n"
        "waves = cs.make_traffic(g.n, 0, full_only=True,\n"
        "                        sources=cs.SHARDED_SOURCES)\n"
        "s = cs.serve_sharded_and_check(g, waves, jax.devices()[:4])\n"
        "assert s['requests'] == 14 and s['engine_full'] == 8, s\n"
        "assert s['cache_hits'] == 4 and s['coalesced'] == 2, s\n"
        "print('SHARDED-SMOKE-PASS')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SHARDED-SMOKE-PASS" in out.stdout, out.stdout + out.stderr


def _run_smoke(path, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, path], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


def test_chip_smoke_refuses_without_a_tpu():
    out = _run_smoke(os.path.join(REPO, "chip_smoke.py"), REPO)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_chip_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run_smoke(str(tmp_path / "chip_smoke.py"), tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_env_overrides_lists_every_repro_variable(monkeypatch):
    for k in list(os.environ):
        if k.startswith("REPRO_"):
            monkeypatch.delenv(k)
    assert kcfg.env_overrides() == {}
    monkeypatch.setenv("REPRO_TUNING_LEDGER", "/nonexistent/ledger.json")
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    assert set(kcfg.env_overrides()) == {"REPRO_TUNING_LEDGER",
                                         "REPRO_KERNEL_MODE"}


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_leaves_a_set_directory_alone(monkeypatch, tmp_path,
                                                    cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == cache_config


def test_compile_cache_defaults_to_the_repo(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
