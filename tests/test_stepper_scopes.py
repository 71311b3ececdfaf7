"""The step program's named scopes survive compilation.

Each stage of a phase carries a ``jax.named_scope``; the compiled step
program's HLO metadata (``op_name``) has to name each stage the policy
runs, so a device trace can split a phase's time by stage.
"""
import re

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import to_ell_in, to_ell_in_sliced, to_ell_out_sliced
from repro.core.policies import policy_for
from repro.core.static_engine import _step_batch, init_batch_state
from repro.graphs import uniform_gnp

STAGES = {"sssp.thresholds", "sssp.settle", "sssp.relax", "sssp.update"}


@pytest.mark.parametrize("criterion, layout, extra", [
    # static keys only: no out-scan, no in-side keys to prime
    ("instatic|outstatic", "sliced", set()),
    # dynamic keys on both sides: the out-scan and the once-per-chunk prime
    ("in|out", "sliced", {"sssp.out_scan", "sssp.prime"}),
    # the weight-gated ELL split is the delta policy's per-chunk preparation
    ("delta", "padded", {"sssp.prime"}),
])
def test_each_stage_names_its_ops(criterion, layout, extra):
    g = uniform_gnp(200, 6 / 200, seed=0)
    kw = {"delta": 0.5} if criterion == "delta" else {}
    state = init_batch_state(g, np.arange(4, dtype=np.int32),
                             criterion=criterion, **kw)
    sliced = layout == "sliced"
    ell = to_ell_in_sliced(g) if sliced else to_ell_in(g)
    ell_out = (to_ell_out_sliced(g)
               if policy_for(criterion).needs_out_adjacency else None)
    text = _step_batch.lower(g, ell, ell_out, state, jnp.int32(4), False,
                             True).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    scopes = {p for path in paths for p in path.split("/")
              if p.startswith("sssp.")}
    assert scopes == STAGES | extra
    # the sliced layout's gathers and their merge run under sssp.relax
    if sliced:
        assert any("sssp.relax" in p and "gather" in p for p in paths)
