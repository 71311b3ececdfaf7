"""Runs of a test-only four-chip cell on four host devices: once sound, then
once with each fault that a sharded cell can have planted under the served
path. One JSON line per run; a run that the harness refuses to build
(``perfbench/run.py``'s ``SHARDED_FIXED``) prints the refusal instead.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 tests/perfbench/run_sharded_faults.py WORKLOAD [--stand-in]

``--stand-in`` serves the cell through :func:`stand_in_backend` in place of
the program's ``ShardedBackend``. ``tests/perfbench/test_perfbench_sharded.py``
runs it in a subprocess: the device count has to be set before JAX is
imported.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(HERE)]

FAULTS = ("state_unchanged", "answer_altered", "half_lanes_dropped",
          "exchange_left_out")


def stand_in_backend():
    """``ShardedBackend`` as it will be once its constructor takes
    ``layout`` and ``point_queries``: it keeps both, serves ``"coo"``
    through the program's step, and every other layout through
    :func:`gather_stepper`'s, whose exchange is another than the
    program's."""
    from repro.serving import backends

    class ShardedBackend(backends.ShardedBackend):
        def __init__(self, g, mesh, axes, *, layout="coo",
                     point_queries=False, **kw):
            super().__init__(g, mesh, axes, **kw)
            self.layout, self.point_queries = layout, point_queries
            self._step = gather_stepper() if layout != "coo" else None

        def step(self, state, k_phases, *, stop_on_lane_finish=True,
                 donate=False):
            if self._step is None:
                return super().step(state, k_phases, donate=donate,
                                    stop_on_lane_finish=stop_on_lane_finish)
            return self._step(self.sg, state, self.mesh, self.axes,
                              k_phases, schedule=self.schedule,
                              stop_on_lane_finish=stop_on_lane_finish,
                              donate=donate)

    return ShardedBackend


def gather_min(contrib, axes, n_loc, schedule):
    """Every chip's (B, n_pad) candidates gathered, and the least over the
    chips of this chip's (B, n_loc) block: the same answer as the program's
    ``_exchange_min_batch``, through ``all_gather`` in place of its
    ``all_to_all``."""
    import jax
    import jax.numpy as jnp

    full = jax.lax.all_gather(contrib, axes)  # (chips, B, n_pad)
    idx = jax.lax.axis_index(axes)
    return jnp.min(jax.lax.dynamic_slice_in_dim(full, idx * n_loc, n_loc,
                                                axis=2), axis=0)


def gather_stepper():
    """The program's ``step_sharded_batch`` and the step it builds, copied
    with globals of their own in which the exchange is :func:`gather_min`:
    a step that never calls the program's ``_exchange_min_batch``."""
    import types

    from repro.core import distributed

    env = dict(vars(distributed), _exchange_min_batch=gather_min,
               _SHARDED_STEP_CACHE={})
    for name in ("step_sharded_batch", "_get_sharded_step"):
        f = getattr(distributed, name)
        copy = types.FunctionType(f.__code__, env, f.__name__,
                                  f.__defaults__, f.__closure__)
        copy.__kwdefaults__ = f.__kwdefaults__
        env[name] = copy
    return env["step_sharded_batch"]


# The collectives that move data between chips. The sums (``psum``,
# ``pmean``) stay: they carry counts and the loop's stop, not candidates,
# and under ``shard_map``'s replication check an output declared replicated
# has to come from a collective.
EXCHANGES = ("all_to_all", "all_gather", "psum_scatter", "ppermute",
             "pshuffle", "pmin", "pmax")


def _own(x, axes, axis, tiled):
    """The block of ``x`` along ``axis`` that this chip sends itself."""
    import jax

    idx = jax.lax.axis_index(axes)
    if tiled:
        size = x.shape[axis] // jax.lax.axis_size(axes)
        return jax.lax.dynamic_slice_in_dim(x, idx * size, size, axis)
    return jax.lax.dynamic_index_in_dim(x, idx, axis, keepdims=False)


def _copies(x, axes, axis, tiled):
    """As many copies of ``x`` as there are chips, along ``axis``: what a
    chip that hears only itself receives from all of them."""
    import jax
    import jax.numpy as jnp

    join = jnp.concatenate if tiled else jnp.stack
    return join([x] * jax.lax.axis_size(axes), axis=axis)


def _heard_only_itself(name):
    """A stand-in for the collective ``jax.lax.<name>`` in which each chip
    keeps only its own block and receives none of the others'."""
    import jax

    def all_to_all(x, axis_name, split_axis, concat_axis, *,
                   axis_index_groups=None, tiled=False):
        return jax.tree.map(lambda a: _copies(
            _own(a, axis_name, split_axis, tiled), axis_name, concat_axis,
            tiled), x)

    def all_gather(x, axis_name, *, axis_index_groups=None, axis=0,
                   tiled=False):
        return jax.tree.map(lambda a: _copies(a, axis_name, axis, tiled), x)

    def psum_scatter(x, axis_name, *, scatter_dimension=0,
                     axis_index_groups=None, tiled=False):
        return jax.tree.map(lambda a: _own(a, axis_name, scatter_dimension,
                                           tiled), x)

    def own(x, *args, **kw):
        return x

    return {"all_to_all": all_to_all, "all_gather": all_gather,
            "psum_scatter": psum_scatter}.get(name, own)


def leave_out_exchange():
    """Plant the fault under the collectives themselves, so that it reaches
    whatever exchange a sharded step runs: every collective of ``EXCHANGES``,
    in ``jax.lax`` and under any name a ``repro`` module holds it by, hears
    only its own chip. JAX's caches are cleared, so the step is traced
    anew."""
    import jax

    for name in EXCHANGES:
        orig = getattr(jax.lax, name)
        planted = _heard_only_itself(name)
        setattr(jax.lax, name, planted)
        for mod in list(sys.modules.values()):
            if getattr(mod, "__name__", "").startswith("repro"):
                for attr, value in list(vars(mod).items()):
                    if value is orig:
                        setattr(mod, attr, planted)
    jax.clear_caches()


def count_program_exchanges() -> list:
    """Count the calls of the program's ``_exchange_min_batch`` (each is
    made while a step is traced): a one-element list that grows, or None
    where the program has no such function."""
    from repro.core import distributed

    exchange = getattr(distributed, "_exchange_min_batch", None)
    if exchange is None:
        return None
    calls = [0]

    def counted(*args, **kw):
        calls[0] += 1
        return exchange(*args, **kw)

    distributed._exchange_min_batch = counted
    return calls


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--stand-in", action="store_true")
    args = ap.parse_args(argv)

    import repro.serving
    from perfbench import serve
    from perfbench.peaks import PEAKS
    from perfbench.run import run_cell
    from perfbench.spec import load_cell
    from test_perfbench_harness import BIG_SEED, DATA, _Broken

    if args.stand_in:
        repro.serving.ShardedBackend = stand_in_backend()
    cell = load_cell(args.workload, DATA)
    exchanges = count_program_exchanges()
    for mode in ("sound",) + FAULTS:
        seen = []

        def wrap(backend, mode=mode, seen=seen):
            seen.append(backend)
            if mode in ("sound", "exchange_left_out"):
                return backend
            return _Broken(backend, mode)

        if mode == "exchange_left_out":
            leave_out_exchange()
        if mode != "sound":
            serve.DRAIN_LIMIT_S = 1.0
        try:
            result, checks, info = run_cell(
                cell, BIG_SEED, 0.5, False, time.perf_counter(),
                PEAKS["TPU v5 lite"], wrap_backend=wrap)
        except ValueError as e:
            print(json.dumps({"mode": mode, "refused": str(e)}), flush=True)
            continue
        inner = seen[0]
        print(json.dumps({
            "mode": mode, "correct": result["correct"], "checks": checks,
            "attempted": result["attempted"], "failed": result["failed"],
            "backend": type(inner).__name__,
            "mesh_devices": int(inner.mesh.devices.size),
            "chips": info["chips"],
            "memory_peak_bytes_by_chip": info["memory_peak_bytes_by_chip"],
            "compiles_in_window": info["compiles_in_window"],
            "program_exchange_calls": exchanges and exchanges[0]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
