"""VMEM budget estimator: per-``pallas_call`` block-spec footprint accounting.

Why static: an over-budget kernel (e.g. the 256^3 VMEM-pinned sampling volume)
today only surfaces as a Mosaic "Ran out of memory" at *compile time on real
TPU hardware* — CI's interpret-mode legs sail straight past it. This module
reads the traced ``pallas_call`` equations instead (grid mapping + block
mappings + scratch avals, the exact structures Mosaic allocates from) and sums
the per-buffer VMEM footprints against the backend's budget, so a config that
cannot compile is rejected before burning simulation cycles in situ.

Accounting model (documented, deliberately simple):

- every input/output block is charged ``block bytes x pipeline factor``; the
  factor is 2 for blocks with a non-trivial index window (Mosaic
  double-buffers blocks that move across grid steps — this includes the
  partition-indexed state blocks of the fused train step) and 1 for pinned
  whole-array blocks;
- scratch buffers are charged once (they are allocated, not pipelined);
- scalar-prefetch operands live in SMEM and are excluded;
- the budget is the backend's :attr:`repro.backends.Backend.vmem_limit_bytes`
  (~16 MB for the TPU kernel envelope; ``None`` = unbounded, e.g. jnp
  backends, which emit no ``pallas_call`` at all).

The same :class:`VmemBuffer`/:func:`check_budget` machinery backs the early
guard in ``repro.kernels.fused_train_step.ops`` (closed-form buffer list, no
tracing) and the per-kernel ``vmem_footprint`` hooks on every kernel package
(traced, via :func:`footprint_of`), so all surfaces print one breakdown
format.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

#: default pipeline (double-buffering) factor for grid-varying blocks
PIPELINE_FACTOR = 2


@dataclass(frozen=True)
class VmemBuffer:
    """One VMEM allocation of a kernel: a block, a scratch slab, or an output."""

    name: str                       # e.g. "in[3]:volume", "scratch[0]", "out[2]"
    kind: str                       # "in" | "out" | "scratch"
    block_shape: Tuple[int, ...]
    dtype: str
    pipelined: bool = False         # grid-varying window -> double-buffered

    @property
    def block_bytes(self) -> int:
        import jax.numpy as jnp
        n = math.prod(self.block_shape) if self.block_shape else 1
        return n * jnp.dtype(self.dtype).itemsize

    @property
    def charged_bytes(self) -> int:
        return self.block_bytes * (PIPELINE_FACTOR if self.pipelined else 1)

    def row(self) -> str:
        shape = "x".join(str(d) for d in self.block_shape) or "scalar"
        pipe = f" x{PIPELINE_FACTOR} (double-buffered)" if self.pipelined else ""
        return (f"{self.name:<18s} {self.kind:<7s} {shape:>20s} {self.dtype:<9s}"
                f" {_fmt_bytes(self.block_bytes):>10s}{pipe}")


@dataclass
class KernelFootprint:
    """The full VMEM bill of one ``pallas_call``."""

    kernel: str                             # see kernel_name()
    grid: Tuple[int, ...]
    buffers: List[VmemBuffer] = field(default_factory=list)

    @property
    def total_bytes(self) -> int:
        return sum(b.charged_bytes for b in self.buffers)

    def fits(self, limit_bytes: Optional[int]) -> bool:
        """Does this kernel fit a VMEM budget? (``None`` = unbounded.) The
        assertion form of :func:`over_budget`, for tests and capability
        probes — e.g. the 256^3 brick-tiled sampling footprint vs the 16 MiB
        TPU envelope."""
        return limit_bytes is None or self.total_bytes <= limit_bytes

    def breakdown(self) -> str:
        lines = [f"pallas_call {self.kernel} grid={self.grid}: "
                 f"{_fmt_bytes(self.total_bytes)} VMEM"]
        for b in sorted(self.buffers, key=lambda b: -b.charged_bytes):
            lines.append("  " + b.row())
        return "\n".join(lines)


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024
    return f"{n:.1f} GiB"


# --------------------------------------------------------------------------- #
# Traced-program estimation
# --------------------------------------------------------------------------- #
def iter_pallas_eqns(jaxpr, acc=None):
    """All ``pallas_call`` equations reachable from ``jaxpr`` (recursing
    through scan/cond/jit/custom_vjp sub-jaxprs, NOT into kernel bodies)."""
    acc = [] if acc is None else acc
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            acc.append(eqn)
            continue                     # a kernel cannot nest another kernel
        for v in eqn.params.values():
            for x in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(x, "jaxpr", None)
                if inner is not None:
                    iter_pallas_eqns(inner, acc)
                elif hasattr(x, "eqns"):
                    iter_pallas_eqns(x, acc)
    return acc


def kernel_name(eqn) -> str:
    """The kernel function's name of one traced ``pallas_call`` equation
    (e.g. ``"_encode_kernel"``): an explicit ``name=`` if the call gave one,
    else the kernel jaxpr's source info."""
    name = eqn.params.get("name")
    if not name:
        dbg = getattr(eqn.params["jaxpr"], "debug_info", None)
        name = getattr(dbg, "func_src_info", None) or "pallas_call"
    return str(name).split(" at ")[0]


def footprint_of_eqn(eqn) -> KernelFootprint:
    """Read one traced ``pallas_call`` equation into a :class:`KernelFootprint`.

    Uses the grid mapping's block mappings (block aval = the VMEM block Mosaic
    allocates; ``has_trivial_window`` = whole-array pinned block, charged once)
    plus the kernel jaxpr's trailing scratch refs.
    """
    gm = eqn.params["grid_mapping"]
    fp = KernelFootprint(kernel=kernel_name(eqn), grid=tuple(gm.grid))

    n_in, n_out = gm.num_inputs, gm.num_outputs
    for i, bm in enumerate(gm.block_mappings):
        aval = bm.block_aval.inner_aval if hasattr(bm.block_aval, "inner_aval") \
            else bm.block_aval
        kind, idx = ("in", i) if i < n_in else ("out", i - n_in)
        trivial = bm.has_trivial_window    # property in newer jax, method here
        if callable(trivial):
            trivial = trivial()
        fp.buffers.append(VmemBuffer(
            name=f"{kind}[{idx}]", kind=kind,
            block_shape=tuple(int(d) for d in aval.shape),
            dtype=str(aval.dtype),
            pipelined=not bool(trivial)))

    n_scratch = gm.num_scratch_operands
    if n_scratch:
        kernel_jaxpr = eqn.params["jaxpr"]
        for j, var in enumerate(kernel_jaxpr.invars[-n_scratch:]):
            aval = var.aval
            inner = getattr(aval, "inner_aval", aval)
            # SMEM/semaphore scratch does not count against VMEM
            space = str(getattr(aval, "memory_space", "") or "").lower()
            if "smem" in space or "semaphore" in space:
                continue
            fp.buffers.append(VmemBuffer(
                name=f"scratch[{j}]", kind="scratch",
                block_shape=tuple(int(d) for d in inner.shape),
                dtype=str(inner.dtype), pipelined=False))
    return fp


def estimate_jaxpr(jaxpr) -> List[KernelFootprint]:
    """Footprints of every ``pallas_call`` reachable from a (Closed)Jaxpr."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    return [footprint_of_eqn(e) for e in iter_pallas_eqns(inner)]


def footprint_of(fn, *args, **kwargs) -> List[KernelFootprint]:
    """Trace ``fn`` abstractly (args may be ShapeDtypeStructs) and estimate
    every ``pallas_call`` it contains — the uniform implementation behind the
    per-kernel ``vmem_footprint`` hooks."""
    import jax
    jx = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    return estimate_jaxpr(jx)


# --------------------------------------------------------------------------- #
# Budget comparison (shared by check (2) and the ops.py early guard)
# --------------------------------------------------------------------------- #
def over_budget(fp: KernelFootprint,
                limit_bytes: Optional[int]) -> Optional[str]:
    """``None`` if ``fp`` fits, else the full per-buffer failure message."""
    if limit_bytes is None or fp.total_bytes <= limit_bytes:
        return None
    return (f"estimated VMEM footprint {_fmt_bytes(fp.total_bytes)} exceeds "
            f"the {_fmt_bytes(limit_bytes)} budget\n{fp.breakdown()}")


def check_budget(footprints: Sequence[KernelFootprint],
                 limit_bytes: Optional[int]) -> List[Tuple[KernelFootprint, str]]:
    """All over-budget kernels with their breakdown messages."""
    out = []
    for fp in footprints:
        msg = over_budget(fp, limit_bytes)
        if msg is not None:
            out.append((fp, msg))
    return out
