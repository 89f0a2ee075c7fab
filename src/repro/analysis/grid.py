"""Grid write-race / coverage detector: concrete BlockSpec index-map analysis.

Why static: the fused kernels lean on *write-disjointness* invariants the
interpret-mode CI legs cannot see — the TPU grid is sequential, so a racing
output BlockSpec (two non-adjacent program instances mapping to the same
output window) silently produces lost updates on real hardware while the
interpreter happens to serialize them. This module evaluates every
``pallas_call``'s BlockSpec index maps over the FULL grid (they are tiny
closed jaxprs of the grid indices — concretely evaluable without running the
kernel) and derives, per operand:

- the sequence of block indices visited in TPU grid order (row-major, last
  axis fastest — the order Mosaic's sequential dimension semantics pin);
- ``distinct`` blocks touched vs ``fetches`` (contiguous runs of one block:
  the double-buffer pipeline only issues a DMA when the index *changes*, so a
  block held across consecutive steps costs one fetch);
- out-of-bounds block coordinates and uncovered output regions.

The verdicts (:func:`repro.analysis.checks.check_grid_write_safety`):

- an output block revisited in two NON-adjacent runs is a **race** (the
  pipeline wrote it back in between — the second visit reads stale VMEM and
  the writes clobber each other): always a violation;
- an output written by more than one consecutive program instance is a
  **multi-writer** and must be explicitly declared (``accumulate`` for
  grad-scratch style ``+=`` chains, ``last_write`` for
  ``pl.when(i == last)``-guarded final stores) via a
  :class:`GridDiscipline` — undeclared multi-writers are violations;
- an input block fetched more often than the double-buffer schedule implies
  (non-adjacent re-fetch) must be declared (``input_refetch``) — e.g. the
  hash-encode coords block re-streamed once per level;
- a declared ``full_coverage_inputs`` operand must touch EVERY block of its
  array — the static form of the PR 8 tiled-sampling invariant that the
  brick sweep visits every owner brick (each corner voxel's owner banks it
  exactly once).

Declarations live next to the kernels (each ``repro.kernels.*.ops`` registers
its :class:`GridDiscipline` at import time); :func:`ensure_declarations`
force-imports them so the check sees every declaration regardless of which
program is being analyzed.

Import-light on purpose (jax only inside functions) — the CLI sets
``XLA_FLAGS`` before anything imports jax.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

from repro.analysis.vmem import kernel_name

#: default allowed actual/ideal HBM-traffic ratio (see analysis.traffic);
#: covers double-buffer ramp effects without hiding a real re-stream
DEFAULT_TRAFFIC_FACTOR = 1.25

#: multi-writer modes a discipline may declare
MULTI_WRITE_MODES = ("accumulate", "last_write")


# --------------------------------------------------------------------------- #
# Per-kernel discipline declarations
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class GridDiscipline:
    """The declared grid-access contract of one kernel.

    Selectors are operand names as the analysis reports them — ``"in[2]"``,
    ``"out[0]"`` — plus the wildcard ``"out[*]"`` / ``"in[*]"``.

    - ``multi_write``: selector -> ``"accumulate"`` | ``"last_write"`` for
      outputs deliberately written across several consecutive grid steps;
    - ``input_refetch``: selectors of inputs deliberately re-fetched beyond
      the double-buffer schedule (each refetch is extra HBM traffic, priced
      by ``analysis.traffic``);
    - ``full_coverage_inputs``: selectors of inputs whose every block must be
      visited (owner-sweep invariants);
    - ``traffic_factor``: max allowed actual/ideal HBM bytes ratio for the
      ``hbm_traffic`` check (``None`` = report-only, e.g. flash attention
      where k/v re-streaming scales with the query-block count by design).
    """

    kernel: str
    multi_write: Mapping[str, str] = field(default_factory=dict)
    input_refetch: Tuple[str, ...] = ()
    full_coverage_inputs: Tuple[str, ...] = ()
    traffic_factor: Optional[float] = DEFAULT_TRAFFIC_FACTOR
    note: str = ""


_DISCIPLINES: Dict[str, GridDiscipline] = {}
_DECLARATIONS_LOADED = False


def register_discipline(kernel: str, *, multi_write: Optional[Mapping] = None,
                        input_refetch: Sequence[str] = (),
                        full_coverage_inputs: Sequence[str] = (),
                        traffic_factor: Optional[float] = DEFAULT_TRAFFIC_FACTOR,
                        note: str = "") -> GridDiscipline:
    """Declare the grid-access contract of ``kernel`` (its traced name — the
    kernel function's ``__name__``). Re-registration replaces (idempotent for
    identical declarations; kernels own their contract)."""
    for sel, mode in dict(multi_write or {}).items():
        if mode not in MULTI_WRITE_MODES:
            raise ValueError(f"multi_write mode {mode!r} for {kernel}:{sel}; "
                             f"expected one of {MULTI_WRITE_MODES}")
    disc = GridDiscipline(kernel=kernel, multi_write=dict(multi_write or {}),
                          input_refetch=tuple(input_refetch),
                          full_coverage_inputs=tuple(full_coverage_inputs),
                          traffic_factor=traffic_factor, note=note)
    _DISCIPLINES[kernel] = disc
    return disc


def get_discipline(kernel: str) -> GridDiscipline:
    """The declared discipline of ``kernel`` (an empty default when none).

    ``vmap`` of a ``pallas_call`` renames the kernel ``<name>_batched`` while
    preserving per-slice semantics (batching just prepends a parallel grid
    dimension), so a batched kernel inherits its base kernel's declaration —
    selector indices are unchanged because batching adds no operands."""
    ensure_declarations()
    base = kernel
    while base not in _DISCIPLINES and base.endswith("_batched"):
        base = base[:-len("_batched")]
    disc = _DISCIPLINES.get(base)
    if disc is None:
        disc = GridDiscipline(kernel=kernel)
    return disc


def declared(disc: GridDiscipline, mapping: str, name: str):
    """Resolve selector ``name`` (e.g. ``"out[3]"``) against one declaration
    mapping (``"multi_write"`` | ``"input_refetch"`` |
    ``"full_coverage_inputs"``); wildcards ``out[*]`` / ``in[*]`` match any
    index of that kind. Returns the declared value (mode string or True), or
    ``None`` when undeclared."""
    wild = name.split("[")[0] + "[*]"
    src = getattr(disc, mapping)
    if isinstance(src, Mapping):
        return src.get(name, src.get(wild))
    if name in src or wild in src:
        return True
    return None


def ensure_declarations() -> None:
    """Import every kernel package's ``ops`` module so their
    ``register_discipline`` calls have run (the analysis may see a traced
    kernel without its wrapper module ever having been imported)."""
    global _DECLARATIONS_LOADED
    if _DECLARATIONS_LOADED:
        return
    import importlib

    for pkg in ("hash_encoding", "fused_mlp", "composite", "flash_attention",
                "fused_train_step"):
        importlib.import_module(f"repro.kernels.{pkg}.ops")
    _DECLARATIONS_LOADED = True


# --------------------------------------------------------------------------- #
# Concrete index-map evaluation
# --------------------------------------------------------------------------- #
@dataclass
class OperandAccess:
    """The concrete grid-order access pattern of one BlockSpec operand."""

    name: str                       # "in[0]" / "out[2]"
    kind: str                       # "in" | "out"
    block_shape: Tuple[int, ...]
    dtype: str
    array_shape: Tuple[int, ...]
    n_blocks_total: int             # prod(ceil(array/block)) per dim
    distinct: int = 0               # distinct block indices visited
    fetches: int = 0                # contiguous runs (= DMA issues)
    n_points: int = 0               # grid points (visits)
    oob: bool = False               # any block coordinate out of range
    evaluable: bool = True
    note: str = ""

    @property
    def block_bytes(self) -> int:
        import jax.numpy as jnp
        n = math.prod(self.block_shape) if self.block_shape else 1
        return n * jnp.dtype(self.dtype).itemsize

    @property
    def refetched(self) -> bool:
        """Fetched beyond the double-buffer schedule (non-adjacent revisit)."""
        return self.fetches > self.distinct

    @property
    def multi_visited(self) -> bool:
        """Some block held across >1 consecutive grid step (runs of len > 1)."""
        return self.n_points > self.fetches

    @property
    def uncovered(self) -> int:
        return max(0, self.n_blocks_total - self.distinct)

    def row(self) -> str:
        flags = []
        if not self.evaluable:
            flags.append("UNEVALUABLE")
        if self.oob:
            flags.append("OOB")
        if self.refetched:
            flags.append("refetched")
        if self.multi_visited:
            flags.append("multi-visit")
        if self.kind == "out" and self.uncovered:
            flags.append(f"uncovered={self.uncovered}")
        tag = f" [{', '.join(flags)}]" if flags else ""
        return (f"{self.name:<8s} blocks={self.distinct}/{self.n_blocks_total}"
                f" fetches={self.fetches} visits={self.n_points}{tag}")


@dataclass
class KernelGridAnalysis:
    """Full-grid access analysis of one ``pallas_call``."""

    kernel: str
    grid: Tuple[int, ...]
    n_points: int
    operands: List[OperandAccess] = field(default_factory=list)
    skipped: str = ""               # reason the kernel could not be analyzed

    def breakdown(self) -> str:
        head = f"pallas_call {self.kernel} grid={self.grid}"
        if self.skipped:
            return f"{head}: SKIPPED ({self.skipped})"
        return "\n".join([head + ":"] + ["  " + a.row() for a in self.operands])


def _grid_points(grid: Tuple[int, ...]):
    """All grid indices in TPU sequential order (row-major, last axis
    fastest), as an (n_points, n_axes) int32 array."""
    import numpy as np

    shape = tuple(int(g) for g in grid)
    if not shape:
        return np.zeros((1, 0), np.int32)
    return np.indices(shape).reshape(len(shape), -1).T.astype(np.int32)


def _eval_index_map(closed_jaxpr, pts, n_grid: int):
    """Evaluate one BlockSpec index-map jaxpr over every grid point.

    The jaxpr's invars are the grid indices followed by the scalar-prefetch
    operands (SMEM refs the in-repo index maps never read — zero-filled
    dummies keep evaluation total). Returns an (n_points, block_rank) int64
    numpy array of block indices."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    jaxpr = closed_jaxpr.jaxpr
    dummies = []
    for v in jaxpr.invars[n_grid:]:
        aval = getattr(v.aval, "inner_aval", v.aval)
        dummies.append(jnp.zeros(aval.shape, aval.dtype))

    def one(pt):
        outs = jax.core.eval_jaxpr(jaxpr, closed_jaxpr.consts,
                                   *[pt[d] for d in range(n_grid)], *dummies)
        if not outs:
            return jnp.zeros((0,), jnp.int32)
        return jnp.stack([jnp.asarray(o).astype(jnp.int32) for o in outs])

    out = jax.vmap(one)(jnp.asarray(pts))
    return np.asarray(out).astype(np.int64)


def _access_stats(acc: OperandAccess, seq, dims) -> None:
    """Fill fetch/coverage stats from the visited block-index sequence."""
    import numpy as np

    acc.n_points = len(seq)
    if seq.ndim != 2 or (dims and seq.shape[1] != len(dims)):
        acc.evaluable = False
        acc.note = (f"index map returned rank {seq.shape[-1] if seq.ndim > 1 else 0}"
                    f" for a {len(dims)}-dim block array")
        return
    if len(seq) == 0:
        return
    changes = (np.any(seq[1:] != seq[:-1], axis=1) if len(seq) > 1
               else np.zeros((0,), bool))
    acc.fetches = int(changes.sum()) + 1
    acc.distinct = len(np.unique(seq, axis=0))
    if dims:
        lim = np.asarray(dims, np.int64)
        acc.oob = bool(np.any(seq < 0)) or bool(np.any(seq >= lim))


def analyze_eqn(eqn) -> KernelGridAnalysis:
    """Concretely evaluate every BlockSpec index map of one traced
    ``pallas_call`` equation over its full grid."""
    gm = eqn.params["grid_mapping"]
    name = kernel_name(eqn)
    grid = tuple(int(g) for g in gm.grid)
    ka = KernelGridAnalysis(kernel=name, grid=grid,
                            n_points=int(math.prod(grid)) if grid else 1)
    if getattr(gm, "num_dynamic_grid_bounds", 0):
        ka.skipped = "dynamic grid bounds (grid not statically known)"
        return ka
    if ka.n_points > 2_000_000:
        ka.skipped = f"grid too large to enumerate ({ka.n_points} points)"
        return ka

    pts = _grid_points(grid)
    n_in = gm.num_inputs
    for i, bm in enumerate(gm.block_mappings):
        aval = getattr(bm.block_aval, "inner_aval", bm.block_aval)
        kind, idx = ("in", i) if i < n_in else ("out", i - n_in)
        arr_shape = tuple(int(d) for d in bm.array_aval.shape)
        blk_shape = tuple(int(d) for d in aval.shape)
        # blocks-per-dim in index-map coordinates: the index map emits one
        # coordinate per array dim, in units of the block shape
        if len(blk_shape) == len(arr_shape):
            dims = tuple(-(-a // b) for a, b in zip(arr_shape, blk_shape))
        else:                       # rank-changing specs: bound unknown
            dims = ()
        acc = OperandAccess(name=f"{kind}[{idx}]", kind=kind,
                            block_shape=blk_shape, dtype=str(aval.dtype),
                            array_shape=arr_shape,
                            n_blocks_total=int(math.prod(dims)) if dims else 0)
        mode = type(getattr(bm, "indexing_mode", None)).__name__
        if mode not in ("Blocked", "NoneType"):
            acc.evaluable = False
            acc.note = f"non-Blocked indexing mode {mode}"
            ka.operands.append(acc)
            continue
        try:
            seq = _eval_index_map(bm.index_map_jaxpr, pts, len(grid))
        except Exception as e:                      # defensive: never crash
            acc.evaluable = False
            acc.note = f"index map not evaluable: {type(e).__name__}: {e}"
            ka.operands.append(acc)
            continue
        _access_stats(acc, seq, dims)
        ka.operands.append(acc)
    return ka


def analyze_jaxpr(jaxpr) -> List[KernelGridAnalysis]:
    """Analyses of every ``pallas_call`` reachable from a (Closed)Jaxpr."""
    from repro.analysis.vmem import iter_pallas_eqns

    inner = getattr(jaxpr, "jaxpr", jaxpr)
    return [analyze_eqn(e) for e in iter_pallas_eqns(inner)]


#: package-level alias (``repro.analysis.analyze_grid_jaxpr``) — the bare
#: ``analyze_jaxpr`` name collides with vmem's at the package root
analyze_grid_jaxpr = analyze_jaxpr
