"""Compile the served step programs for a described TPU v5e, at real size.

Nothing runs: the TPU compiler, which is installed alongside JAX, compiles
for a ``v5e:2x2`` topology that is described, not attached. That refuses
what the chip would refuse — an unlowerable kernel, a program that does not
fit the 16 GiB of HBM, a sharding the partitioner cannot place — at no chip
time. The topology is described inside a fixture, never at import: only the
test worker that runs this file loads the TPU library.
"""
import dataclasses
import importlib.util
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.distributed import (
    _get_sharded_step,
    init_sharded_batch_state,
    shard_graph_batch,
)
from repro.core.graph import (
    from_coo,
    to_ell_in,
    to_ell_in_sliced,
    to_ell_out_sliced,
)
from repro.core.static_engine import (
    EMPTY_LANE,
    _step_batch_donate,
    init_batch_state,
)
from repro.graphs import grid_road, uniform_gnp

HBM_BYTES = 16 * 1024**3  # one v5e chip
LANES = 8
ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def road():
    return grid_road(512, 512, seed=0)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _compile_served_step(g, ell, ell_out, state, sharding):
    return _step_batch_donate.lower(
        _shapes(g, sharding), _shapes(ell, sharding),
        _shapes(ell_out, sharding), _shapes(state, sharding),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding),
        use_pallas=False, stop_on_lane_finish=True,
    ).compile()


def test_served_step_compiles_for_v5e(one_chip, road):
    """The donating step ContinuousBatcher runs, on the road-network graph
    with point-query lanes: XLA only (no Mosaic kernel), and it fits."""
    empty = np.full(LANES, EMPTY_LANE, np.int32)
    state = init_batch_state(road, empty, targets=empty)
    compiled = _compile_served_step(road, to_ell_in(road), None, state,
                                    one_chip)
    assert "tpu_custom_call" not in compiled.as_text()
    assert 0 < _device_bytes(compiled) < HBM_BYTES


def test_sliced_dynamic_criterion_step_compiles_for_v5e(one_chip):
    """The degree-sliced layout under ``in|out`` (both adjacency views)."""
    n = 1 << 16
    g = uniform_gnp(n, 16 / n, seed=0)
    state = init_batch_state(g, np.full(LANES, EMPTY_LANE, np.int32),
                             criterion="in|out")
    compiled = _compile_served_step(g, to_ell_in_sliced(g),
                                    to_ell_out_sliced(g), state, one_chip)
    assert "tpu_custom_call" not in compiled.as_text()
    assert 0 < _device_bytes(compiled) < HBM_BYTES


def _graph500_s16():
    """The benchmark's Graph500 scale-16 graph, from its own generator and
    configuration file (loaded by path: the generator needs only numpy)."""
    spec = importlib.util.spec_from_file_location(
        "perfbench_graphs", ROOT / "perfbench" / "graphs.py")
    graphs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(graphs)
    cfg = json.loads(
        (ROOT / "perfbench" / "configs" / "graph500-s16.json").read_text())
    src, dst, w, n, _ = graphs.build(cfg, seed=0)
    return from_coo(src, dst, w, n), cfg["criterion"]


def _gather_sources(text):
    """``(gather, layout of the array it gathers from)`` for every gather
    fusion of a compiled program's HLO text."""
    layouts = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+)", text,
                              re.M))
    return [(m[0], layouts.get(m[1], "")) for m in re.findall(
        r"^\s*(%[\w.\-]+) = \S+ fusion\((%[\w.\-]+),.*kind=kCustom.*/gather\"",
        text, re.M)]


def _relax_gather_outputs(text):
    """``(gather, its output shape and layout)`` for every gather fusion
    under the step's ``sssp.relax`` scope."""
    return re.findall(
        r"^\s*(%[\w.\-]+) = (\S+) fusion\(.*kind=kCustom.*"
        r"op_name=\"[^\"]*/sssp\.relax/[^\"]*/gather\"", text, re.M)


def _minor_dim(shape):
    """Size of the minor dimension of an HLO shape with its layout, such
    as ``f32[2022912,8]{1,0:T(8,128)}`` (8)."""
    dims, layout = re.match(r"\w+\[([\d,]*)\]\{([\d,]*)", shape).groups()
    dims = [int(d) for d in dims.split(",") if d]
    return dims[int(layout.split(",")[0])] if dims else None


# the served step's temp memory before the bucket gathers ran in row tiles:
# 1,245,367,296 B, nearly all the (8, 128)-tile padding of f32[R*D, 8] slabs
TEMP_BYTES_MAX = 400_000_000


def test_graph500_sliced_step_merges_through_the_heavy_table(one_chip):
    """The benchmark's served step on Graph500 scale 16: XLA only, it fits,
    and the slice->vertex merge gathers n + H * C entries per lane, so no
    array of the program holds a multiple of n * C elements. Every gather
    reads its source from VMEM (memory space S(1)): a bucket gather that
    reads the distance rows from HBM runs about 5x slower on the chip.
    Every gather of the relax writes its slots to VMEM, or lane-dense: an
    f32[R*D, 8] slab in HBM pads each slot's 8 lanes to 128, and the
    bucket gathers written and read back that way took 7.4 of an 8.3 ms
    phase on the chip."""
    g, criterion = _graph500_s16()
    ell = to_ell_in_sliced(g)
    heavy, chunks = ell.merge_heavy.shape
    assert 0 < heavy < g.n and chunks > 1  # hubs split, most vertices not
    state = init_batch_state(g, np.full(LANES, EMPTY_LANE, np.int32),
                             criterion=criterion)
    compiled = _compile_served_step(g, ell, None, state, one_chip)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert 0 < _device_bytes(compiled) < HBM_BYTES
    dense = g.n * chunks
    sizes = {int(np.prod([int(d) for d in dims.split(",") if d]))
             for dims in re.findall(r"\b[a-z]+\d*\[([\d,]*)\]", text)}
    assert not [s for s in sizes if s and s % dense == 0]
    sources = _gather_sources(text)
    assert len(sources) >= len(ell.slices) + 2  # buckets, heavy, first
    assert [g for g, layout in sources if "S(1)" not in layout] == []
    outputs = _relax_gather_outputs(text)
    assert len(outputs) >= len(ell.slices) + 2
    assert [(g, out) for g, out in outputs
            if "S(1)" not in out and _minor_dim(out) == LANES] == []
    assert compiled.memory_analysis().temp_size_in_bytes <= TEMP_BYTES_MAX


def test_sharded_step_compiles_for_four_chips(topo, road):
    """The sharded serving step on a mesh over the 4 described chips: the
    vertex exchange compiles to a collective and each chip holds a quarter
    of the graph and of the lane state."""
    mesh = jax.make_mesh((4,), ("v",), devices=topo.devices)
    sg = shard_graph_batch(road, 4, with_transpose=False)
    state = init_sharded_batch_state(sg, np.full(LANES, EMPTY_LANE, np.int32))

    def on(tree, spec):
        return _shapes(tree, NamedSharding(mesh, spec))

    edge, vert, lane, rep = P("v", None), P("v"), P(None, "v"), P()
    state_shapes = dataclasses.replace(
        on(state, rep), dist=on(state.dist, lane),
        status=on(state.status, lane))
    step = _get_sharded_step(mesh, ("v",), "reduce_scatter", True, True,
                             state.criterion)
    compiled = step.lower(
        state_shapes, on(sg.src_local, edge), on(sg.dst, edge),
        on(sg.w, edge), None, None, None, on(sg.in_min, vert),
        on(sg.out_min, vert), on(sg.out_deg, vert),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, rep)),
    ).compile()
    text = compiled.as_text()
    assert "all-to-all" in text and "tpu_custom_call" not in text
    assert 0 < _device_bytes(compiled) < HBM_BYTES
    # each chip holds a quarter of the edges and vertex blocks; only the
    # (B,)-sized counters are replicated
    sharded = (sg.src_local, sg.dst, sg.w, sg.in_min, sg.out_min, sg.out_deg,
               state.dist, state.status)
    quarter = sum(x.nbytes for x in sharded) // 4
    args = compiled.memory_analysis().argument_size_in_bytes
    assert quarter <= args < quarter + 65536
