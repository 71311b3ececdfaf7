"""Multi-device integration tests (run in a subprocess with 8 fake host
devices so the main pytest session keeps its single-device jax config)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
from repro.core import dijkstra_numpy, run_phased
from repro.core.distributed import run_distributed
from repro.graphs import uniform_gnp, grid_road
from repro.runtime.train_loop import train
from repro.data.pipeline import DataConfig
from repro.optim.adamw import OptConfig
from repro.configs import get_smoke

mesh = jax.make_mesh((4, 2), ("data", "model"))

# --- distributed phased SSSP: both exchange schedules, phases must match the
# single-device engine exactly
for g in [uniform_gnp(300, 8/300, seed=3), grid_road(12, 14, seed=4)]:
    ref = dijkstra_numpy(g, 0)
    base = run_phased(g, 0, "instatic|outstatic")
    for sched in ("allreduce", "reduce_scatter"):
        d, ph = run_distributed(g, mesh, ("data", "model"), 0, schedule=sched)
        d = np.asarray(d)
        fin = np.isfinite(ref)
        assert (np.isfinite(d) == fin).all(), sched
        assert np.allclose(d[fin], ref[fin], rtol=1e-5), sched
        assert int(ph) == int(base.phases), (sched, int(ph), int(base.phases))

# --- sharded training with EP MoE on the mesh: loss finite and falling (the
# LM layers need the Auto-typed mesh the launch helpers build)
from repro.launch.mesh import make_host_mesh
lm_mesh = make_host_mesh(tp=2)
cfg = get_smoke("qwen3_moe_235b")
r = train(cfg, lm_mesh, steps=16, dcfg=DataConfig(seed=0, batch=4, seq_len=64),
          opt_cfg=OptConfig(lr=1e-2, warmup_steps=3, total_steps=16))
assert all(np.isfinite(r.losses)), r.losses
assert min(r.losses[8:]) < r.losses[0] + 0.02, r.losses
print("DISTRIBUTED-SUITE-PASS")
"""


@pytest.mark.slow
def test_distributed_suite():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True,
        text=True, timeout=900,
    )
    assert "DISTRIBUTED-SUITE-PASS" in out.stdout, out.stdout + out.stderr
