"""Execution configuration for the Pallas kernel layer.

Every kernel wrapper used to hard-code ``interpret=True`` (safe everywhere,
but it leaves a TPU running the Mosaic *emulator*); this module is the one
place that decides how a kernel actually executes:

  * **mode** — ``interpret`` (kernel body runs as plain XLA ops; bit-exact
    on CPU, the differential-testing surface) vs ``compiled`` (real Mosaic
    lowering). Resolution order: explicit ``interpret=`` argument >
    ``REPRO_KERNEL_MODE`` env var (``interpret`` / ``compiled`` / ``auto``)
    > backend default (compiled on TPU, interpret elsewhere).
  * **block_rows / block** — the tile sizes of the ELL row scans and the
    frontier reduction. Resolution order: explicit argument > tuning-ledger
    hit for this (kind, n, D, B, lanes) shape > the largest candidate whose
    working set fits the VMEM budget.
  * **autotuning** — :func:`autotune_block_rows` measures real kernel calls
    over the VMEM-feasible candidate set and records the winner in a
    persistable :class:`TuningLedger` (JSON), so a serving process tunes
    once per resident graph shape and every later engine build reads the
    ledger. :func:`autotune_slicing` does the same for degree-sliced ELL
    bucket boundaries (see ``repro.core.graph.to_ell_in_sliced``).
  * **launch timing** — :func:`measure_launch` is the one timed-kernel-call
    primitive: every measured repetition lands in the default metrics
    registry (``kernel.launch.<kind>`` histograms, see ``repro.obs``) as
    well as feeding the ledger entries the autotuner writes.

Tuning changes only *how* a reduction is tiled, never its value: f32
min-reductions are exact for any association order, so every choice this
module makes is bit-invisible to results (the property the differential
tests rely on).
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Callable

import jax

from repro.obs import timer as obs_timer
from repro.obs.registry import default_registry

# Candidate row-tile sizes. All are multiples of the 128-lane TPU vector
# width, which the fused two-sweep kernels additionally rely on to keep the
# gather index space lane-aligned (see ell_relax_keys.py).
BLOCK_ROWS_CANDIDATES = (128, 256, 512, 1024, 2048, 4096)
DEFAULT_BLOCK_ROWS = 256
DEFAULT_BLOCK = 2048  # frontier reduction column tile

# Per-core VMEM is ~16 MiB; leave headroom for Mosaic's own spills and the
# double-buffered input pipeline rather than planning to the byte.
VMEM_BYTES = 16 * 1024 * 1024
DEFAULT_VMEM_BUDGET = int(VMEM_BYTES * 0.75)

_MODE_ENV = "REPRO_KERNEL_MODE"
_LEDGER_ENV = "REPRO_TUNING_LEDGER"


def kernel_mode() -> str:
    """The effective execution mode: ``"interpret"`` or ``"compiled"``."""
    mode = os.environ.get(_MODE_ENV, "auto").strip().lower()
    if mode not in ("auto", "interpret", "compiled"):
        raise ValueError(
            f"{_MODE_ENV} must be 'auto', 'interpret' or 'compiled'; "
            f"got {mode!r}"
        )
    if mode == "auto":
        return "compiled" if jax.default_backend() == "tpu" else "interpret"
    return mode


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Resolve an ``interpret=`` argument (None = per-backend default)."""
    if interpret is not None:
        return bool(interpret)
    return kernel_mode() == "interpret"


def scan_fusion() -> str:
    """Scan-shape policy for dependent two-reduction adjacency scans:
    ``"auto"`` / ``"fused"`` / ``"split"`` (``REPRO_SCAN_FUSION`` env).

    ``fused`` runs the megakernels (``ell_relax_keys`` / ``ell_keys_dep``):
    ONE launch whose sweeps share tile loads — the shape that wins when
    launches and HBM tile re-streaming cost real time (compiled Mosaic).
    ``split`` decomposes the same math into single-sweep multi-vector calls
    (``ell_gather_min``) with the inter-sweep gate built as plain XLA in
    between — what the interpret machinery prefers for multi-tile grids,
    whose per-step emulation dwarfs the launch cost fusion would save.
    ``auto`` lets the wrappers decide per call site (compiled -> fused;
    interpret -> fused only for one-tile scans, whose megakernel body needs
    no predication/dynamic stores). Bit-identical either way (exact f32
    min), so this is pure execution policy; BENCH_fused.json measures the
    shapes against each other.
    """
    mode = os.environ.get("REPRO_SCAN_FUSION", "auto").strip().lower()
    if mode not in ("auto", "fused", "split"):
        raise ValueError(
            f"REPRO_SCAN_FUSION must be 'auto', 'fused' or 'split'; got {mode!r}"
        )
    return mode


def vmem_budget_bytes() -> int:
    """VMEM budget the tile-size resolution plans against (env-overridable)."""
    raw = os.environ.get("REPRO_VMEM_BUDGET_BYTES")
    return int(raw) if raw else DEFAULT_VMEM_BUDGET


def env_overrides() -> dict[str, str]:
    """Every ``REPRO_*`` variable set in the environment (the execution-mode,
    scan-shape and VMEM overrides and the tuning-ledger path): a run that
    must execute the program the source defines refuses to start while any
    is set."""
    return {k: v for k, v in os.environ.items() if k.startswith("REPRO_")}


def scan_vmem_bytes(n_idx: int, d_pad: int, b: int, block_rows: int,
                    vecs: int = 1, outs: int = 1) -> int:
    """Resident-VMEM estimate of one ELL row-scan grid step.

    ``vecs`` gather vectors of shape (B, n_idx) are mapped whole (the
    VMEM-resident gather trick), one (block_rows, D) cols tile (int32) plus
    one ws tile (f32) stream per step, ``outs`` output vectors stay
    resident for the fused two-sweep kernels (constant output index maps),
    and — the dominant term for wide tiles — the kernel bodies materialise
    the gathered ``(vecs, B, block_rows, D)`` intermediate before the
    row-min reduces it.
    """
    vec_bytes = 4 * vecs * b * n_idx
    tile_bytes = (4 + 4) * block_rows * d_pad
    out_bytes = 4 * outs * b * n_idx
    gather_bytes = 4 * vecs * b * block_rows * d_pad
    return vec_bytes + tile_bytes + out_bytes + gather_bytes


def feasible_block_rows(n: int, d_pad: int, b: int, vecs: int = 1,
                        outs: int = 1,
                        budget: int | None = None) -> tuple[int, ...]:
    """VMEM-feasible candidates (never empty: the smallest always returned —
    a graph whose *vectors* alone exceed VMEM must be sharded first, which
    is a partitioning decision, not a tile-size one).

    The budget binds only where VMEM exists: interpret mode (plain XLA on
    the host) returns every candidate unless an explicit ``budget`` forces
    the filter.
    """
    if budget is None:
        if kernel_mode() == "interpret":
            return BLOCK_ROWS_CANDIDATES
        budget = vmem_budget_bytes()
    ok = tuple(
        r for r in BLOCK_ROWS_CANDIDATES
        if scan_vmem_bytes(n, d_pad, b, r, vecs, outs) <= budget
    )
    return ok if ok else BLOCK_ROWS_CANDIDATES[:1]


# ---------------------------------------------------------------------------
# Tuning ledger
# ---------------------------------------------------------------------------


def ledger_key(kind: str, n: int, d_pad: int, b: int, lanes: int = 1) -> str:
    """Canonical ledger key for a kernel-call shape.

    ``kind`` names the call site ("relax", "relax_keys", "out_scan",
    "key_min", ...); the backend is part of the key because a tile size
    tuned under interpret mode says nothing about Mosaic.
    """
    return f"{kernel_mode()}:{kind}:n{n}:d{d_pad}:b{b}:l{lanes}"


def portfolio_ledger_key(family: str, b: int, policy: str, layout: str) -> str:
    """Ledger key for one measured serving engine configuration.

    Keyed by graph *family* (a degree-distribution bucket, not a concrete
    graph), lane count, policy spec and ELL layout — the decision the
    portfolio router makes at admission time. Unlike :func:`ledger_key`
    these records are backend-agnostic on purpose: they store end-to-end
    measured walls, not tile choices.
    """
    return f"portfolio:{family}:b{int(b)}:{policy}:{layout}"


def record_portfolio(ledger: "TuningLedger", family: str, b: int, policy: str,
                     layout: str, *, wall_s: float, phases: int, queries: int,
                     delta: float | None = None,
                     attribution: dict[str, int] | None = None) -> dict:
    """Write one measured portfolio entry and return it.

    The entry keeps the raw measurement (``wall_s`` for ``queries`` solves
    over ``phases`` total phases) plus the derived rates the router ranks
    by, and — when the probe ran with telemetry — the policy's
    ``settle_attribution`` term totals, so ``repro.obs dashboard`` can
    explain *why* a policy won (e.g. delta's light/heavy split vs a
    criterion plan's member shares).
    """
    entry: dict = {
        "wall_s": float(wall_s),
        "phases": int(phases),
        "queries": int(queries),
        "per_phase_s": float(wall_s) / max(int(phases), 1),
        "qps": float(queries) / max(float(wall_s), 1e-12),
    }
    if delta is not None:
        entry["delta"] = float(delta)
    if attribution is not None:
        entry["settle_attribution"] = {
            str(k): int(v) for k, v in attribution.items()
        }
    ledger.put(portfolio_ledger_key(family, b, policy, layout), entry)
    return entry


def portfolio_entries(ledger: "TuningLedger", family: str,
                      b: int) -> dict[tuple[str, str], dict]:
    """All recorded engine configs for one (family, lanes): (policy, layout)
    -> entry. Policy specs may themselves contain ``:``-free member names
    joined by ``|``, so only the final ``:`` splits policy from layout."""
    prefix = f"portfolio:{family}:b{int(b)}:"
    out: dict[tuple[str, str], dict] = {}
    for key, entry in ledger.entries.items():
        if key.startswith(prefix):
            policy, layout = key[len(prefix):].rsplit(":", 1)
            out[(policy, layout)] = entry
    return out


def slicing_ledger_key(side: str, n: int) -> str:
    """Ledger key for a graph's tuned slice boundaries.

    Keyed per adjacency side and vertex count only — the boundary choice is
    a property of the (graph-shaped) degree distribution, and the builders
    (``to_ell_in_sliced``) that consume it know nothing about batch sizes.
    """
    return f"{kernel_mode()}:slicing:{side}:n{n}"


class TuningLedger:
    """Persistable map from :func:`ledger_key` to measured tuning decisions.

    Entries are plain dicts (``{"block_rows": 512, "wall_s": 1.2e-4}`` or
    ``{"boundaries": [8, 32, 128], "split": 128, "wall_s": ...}``) so the
    JSON file is diffable and survives schema growth.
    """

    def __init__(self, path: str | None = None):
        self.path = path
        self.entries: dict[str, dict] = {}
        if path and os.path.exists(path):
            self.load(path)

    def get(self, key: str) -> dict | None:
        return self.entries.get(key)

    def put(self, key: str, entry: dict) -> None:
        self.entries[key] = dict(entry)

    def load(self, path: str) -> int:
        """Merge entries from ``path``; returns how many were accepted.

        Tolerant of a concurrent or crashed writer: unparseable JSON or a
        non-dict top level loads nothing, and individual values that are
        not dicts are skipped — well-formed entries are salvaged either
        way, and the entries already in memory are never dropped. (The
        save path is atomic, so a torn file means a *foreign* writer; a
        tuning record is a measurement memo, and losing one re-measures —
        crashing the engine build over it would be strictly worse.)
        """
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            return 0
        if not isinstance(data, dict):
            return 0
        good = {k: v for k, v in data.items()
                if isinstance(k, str) and isinstance(v, dict)}
        self.entries.update(good)
        self.path = path
        return len(good)

    def save(self, path: str | None = None) -> str:
        """Atomically persist the ledger (temp file + ``os.replace``): a
        crash mid-save leaves the previous file intact, and a concurrent
        reader sees either the old complete ledger or the new one —
        never a truncated JSON prefix."""
        path = path or self.path
        if path is None:
            raise ValueError("no ledger path given and none remembered")
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump(self.entries, f, indent=1, sort_keys=True)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        self.path = path
        return path


_GLOBAL_LEDGER: TuningLedger | None = None


def global_ledger() -> TuningLedger:
    """The process-wide ledger (auto-loads ``REPRO_TUNING_LEDGER`` if set)."""
    global _GLOBAL_LEDGER
    if _GLOBAL_LEDGER is None:
        path = os.environ.get(_LEDGER_ENV)
        _GLOBAL_LEDGER = TuningLedger(path if path else None)
    return _GLOBAL_LEDGER


def reset_global_ledger() -> None:
    """Drop the cached process ledger (tests / env changes)."""
    global _GLOBAL_LEDGER
    _GLOBAL_LEDGER = None


def resolve_block_rows(kind: str, n: int, d_pad: int, b: int = 1,
                       lanes: int = 1, vecs: int = 1, outs: int = 1,
                       n_rows: int | None = None) -> int:
    """Tile size for an ELL scan: explicit > ledger > VMEM-fit default.

    The untuned default prefers the smallest candidate that covers all
    ``n_rows`` rows in ONE grid step when that fits the budget (grid
    machinery, not arithmetic, dominates small scans on every backend we
    measure), falling back to the largest feasible candidate. Called at
    trace time with static shapes, so the decision is baked into the
    compiled program — tune *before* building long-lived engines (or pass
    ``block_rows=`` explicitly, which bypasses this entirely).
    """
    hit = global_ledger().get(ledger_key(kind, n, d_pad, b, lanes))
    if hit and "block_rows" in hit:
        return int(hit["block_rows"])
    feas = feasible_block_rows(n, d_pad, b, vecs, outs)
    rows = n + 1 if n_rows is None else n_rows
    for r in feas:
        if r >= rows:
            return r
    return feas[-1]


def resolve_block(n: int) -> int:
    """Column tile of the frontier reduction (whole-row when it fits)."""
    return min(DEFAULT_BLOCK, max(128, -(-n // 128) * 128))


# ---------------------------------------------------------------------------
# Measured autotuning
# ---------------------------------------------------------------------------


def measure_launch(kind: str, fn: Callable[[], jax.Array],
                   reps: int = 3) -> float:
    """Time one warmed kernel call and publish every repetition.

    Returns the median wall seconds of ``reps`` blocked executions of
    ``fn`` (first call warms/compiles, untimed). Each repetition is
    observed into the default registry's ``kernel.launch.<kind>``
    histogram — the continuous launch-latency view the obs dashboard
    renders — so both the autotuner's ledger entries *and* ad-hoc
    measurement share one sink.
    """
    jax.block_until_ready(fn())  # compile / warm
    hist = default_registry().histogram(
        f"kernel.launch.{kind}", f"wall seconds per {kind!r} kernel launch"
    )
    walls = []
    for _ in range(reps):
        t0 = obs_timer.now()
        jax.block_until_ready(fn())
        wall = obs_timer.now() - t0
        walls.append(wall)
        hist.observe(wall)
    walls.sort()
    return walls[len(walls) // 2]


def _time_call(fn: Callable[[], jax.Array], reps: int,
               kind: str = "untagged") -> float:
    return measure_launch(kind, fn, reps)


def autotune_block_rows(
    kind: str,
    make_call: Callable[[int], Callable[[], jax.Array]],
    n: int,
    d_pad: int,
    b: int = 1,
    lanes: int = 1,
    *,
    vecs: int = 1,
    outs: int = 1,
    reps: int = 3,
    ledger: TuningLedger | None = None,
) -> int:
    """Measure ``make_call(block_rows)()`` over the feasible candidates and
    record the winner. Returns the chosen ``block_rows``.

    ``make_call`` receives a candidate tile size and returns a nullary
    callable executing one representative kernel call (the autotuner owns
    warm-up and timing). The winner lands in the ledger under
    :func:`ledger_key`, so later :func:`resolve_block_rows` calls for the
    same shape pick it up — persist with ``global_ledger().save(path)``.
    """
    ledger = global_ledger() if ledger is None else ledger
    best: tuple[float, int] | None = None
    measured = {}
    for r in feasible_block_rows(n, d_pad, b, vecs, outs):
        wall = _time_call(make_call(r), reps, kind=kind)
        measured[str(r)] = wall
        if best is None or wall < best[0]:
            best = (wall, r)
    assert best is not None
    ledger.put(
        ledger_key(kind, n, d_pad, b, lanes),
        {"block_rows": best[1], "wall_s": best[0], "measured": measured},
    )
    return best[1]


def autotune_slicing(
    make_call: Callable[[tuple[int, ...] | None], Callable[[], jax.Array]],
    n: int,
    *,
    side: str = "in",
    boundary_sets: tuple[tuple[int, ...] | None, ...] = (None,),
    reps: int = 3,
    ledger: TuningLedger | None = None,
) -> tuple[int, ...] | None:
    """Measure a relax call per candidate bucket-boundary set (``None`` =
    the padded single-bucket layout) and ledger the winner under
    :func:`slicing_ledger_key`, which ``to_ell_in_sliced`` /
    ``to_ell_out_sliced`` consult when built without explicit boundaries —
    tune, ``global_ledger().save(path)``, and every later sliced view of a
    same-sized graph in a ``REPRO_TUNING_LEDGER`` process uses the winner.
    Returns the winning boundary tuple (or None for padded)."""
    ledger = global_ledger() if ledger is None else ledger
    best: tuple[float, tuple[int, ...] | None] | None = None
    measured = {}
    for bset in boundary_sets:
        wall = _time_call(make_call(bset), reps, kind=f"slicing.{side}")
        measured["padded" if bset is None else str(list(bset))] = wall
        if best is None or wall < best[0]:
            best = (wall, bset)
    assert best is not None
    ledger.put(
        slicing_ledger_key(side, n),
        {
            "boundaries": None if best[1] is None else list(best[1]),
            "wall_s": best[0],
            "measured": measured,
        },
    )
    return best[1]


def resolve_slice_boundaries(side: str, n: int) -> tuple[int, ...] | None:
    """The tuned bucket boundaries for a graph's sliced view, or None.

    Returns None both when nothing was tuned and when the tuned winner was
    the padded layout — in either case the builder falls back to its
    degree-distribution default (a caller asking for a sliced view gets
    one).
    """
    hit = global_ledger().get(slicing_ledger_key(side, n))
    if hit and hit.get("boundaries"):
        return tuple(int(x) for x in hit["boundaries"])
    return None


@dataclasses.dataclass(frozen=True)
class KernelExecConfig:
    """A resolved execution configuration (what the autotuner hands back)."""

    interpret: bool
    block_rows: int
    block: int = DEFAULT_BLOCK
    boundaries: tuple[int, ...] | None = None  # None = padded ELL
