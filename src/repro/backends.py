"""Backend registry: the single place kernel implementations are named.

Every kernel package (``hash_encoding``, ``fused_mlp``, ``composite``,
``flash_attention``) used to thread an ad-hoc ``impl: str`` flag and string-
compare it locally. This module replaces that with registered ``Backend``
objects carrying capability metadata:

- ``ref``        pure-jnp oracle; runs everywhere (alias: ``xla``, the name the
                 LM stack historically used for the same path)
- ``fused``      jnp path with the fused corner-gather hash encoding (training
                 fast path on CPU/GPU; other ops fall back to ``ref``)
- ``pallas``     Pallas kernels in interpret mode (kernel debugging on CPU)
- ``pallas_tpu`` compiled Pallas kernels (real TPU hardware)

``resolve("auto")`` picks the highest-priority backend available on the
current jax platform: ``ref`` everywhere, TPU included. ``pallas_tpu`` ranks
below it because the v5e compiler still refuses its in-kernel table lookup,
table-gradient scatter and uint32 sampling draws (``tests/test_tpu_compile.py``
rehearses each one); it stays selectable by name.

All dispatch helpers accept either a backend name or a ``Backend`` instance,
so model objects and trainers can be parameterized by resolved backends and
pass them straight through ``jit``/``custom_vjp`` static arguments (``Backend``
is a frozen, hashable dataclass).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple, Union

import jax

from repro.precision import SUPPORTED_DTYPES

# Op names used in capability sets. "fused_train_step" is the whole-step op
# (fwd + bwd + AdamW, see repro.kernels.fused_train_step): jnp/fused backends
# implement it as the ref composition, pallas backends as one kernel.
# "fused_sampling" extends it with the in-op batch sampling stage (counter-
# based coords + trilinear target gather) — in-kernel on pallas backends.
# "tiled_sampling" means the in-op sampling stage can keep the volume in HBM
# and stream bricks through on-chip memory (the sampling_brick knob): on
# pallas backends the brick-tiled kernel, on jnp backends trivially true
# (their gather is HBM-resident already). Without it, fused_sampling is
# limited to volumes that fit vmem_limit_bytes pinned.
OPS = ("hash_encoding", "fused_mlp", "composite", "flash_attention",
       "fused_train_step", "fused_sampling", "tiled_sampling", "brick_cache")


@dataclass(frozen=True)
class Backend:
    """One kernel implementation family plus its capability metadata.

    ``kind`` is the dispatch class the kernel wrappers branch on:
    ``"jnp"`` (pure jax.numpy oracle), ``"fused"`` (jnp with fused gathers),
    or ``"pallas"`` (Pallas kernels, interpreted or compiled).
    """

    name: str
    kind: str                                     # "jnp" | "fused" | "pallas"
    description: str = ""
    # pallas interpret mode: a pallas backend must state it (no default that
    # could run interpreted on a TPU); jnp backends run no kernels -> False
    interpret: Optional[bool] = None
    platforms: Tuple[str, ...] = ("cpu", "gpu", "tpu")
    priority: int = 0                             # rank for `auto` resolution
    capabilities: frozenset = field(default_factory=frozenset)
    # compute dtypes the kernels accept WITHOUT silently upcasting to f32;
    # checked by the dtype-aware op entry points and the trainer
    dtypes: Tuple[str, ...] = SUPPORTED_DTYPES
    # per-kernel on-chip memory budget (bytes) the backend's pallas_call
    # operands + scratch must fit — the static VMEM estimator
    # (repro.analysis.vmem) and the fused-sampling dispatch guard check
    # against it. None = unbounded (jnp backends emit no pallas_call).
    vmem_limit_bytes: Optional[int] = None
    # default device-memory budget (bytes) of the serving brick pool
    # (repro.serving.BrickCache) on this backend — HBM, not VMEM, so far
    # looser than vmem_limit_bytes. Overridable per cache; the closed-form
    # pool_bytes never exceeds it.
    cache_budget_bytes: int = 64 * 2**20

    def __post_init__(self):
        if self.interpret is None:
            if self.kind == "pallas":
                raise ValueError(f"pallas backend {self.name!r} must set "
                                 "interpret=True or False")
            object.__setattr__(self, "interpret", False)

    # ------------------------------------------------------------------ #
    @property
    def is_pallas(self) -> bool:
        return self.kind == "pallas"

    @property
    def is_fused(self) -> bool:
        return self.kind == "fused"

    def supports(self, op: str) -> bool:
        """Does this backend natively implement ``op``? (Ops fall back to the
        jnp oracle when not — capability metadata, not a hard error.)"""
        return op in self.capabilities

    @property
    def fused_train_step(self) -> str:
        """Which fused-train-step implementation this backend runs:
        ``""`` (none — the trainer keeps the unfused step), ``"ref"`` (the
        composition of this backend's own ops + AdamW), ``"pallas-interpret"``
        or ``"pallas"`` (the single-kernel path). The trainer's
        ``DVNRConfig.fuse_train_step="auto"`` enables fusion exactly when this
        is non-empty."""
        if not self.supports("fused_train_step"):
            return ""
        if self.is_pallas:
            return "pallas-interpret" if self.interpret else "pallas"
        return "ref"

    @property
    def fused_sampling(self) -> str:
        """Which in-op batch-sampling implementation this backend runs inside
        its fused train step: ``""`` (none — the trainer samples on the host),
        ``"ref"`` (the counter-based sampler + trilinear gather composed
        outside the kernels), ``"pallas-interpret"`` or ``"pallas"`` (the
        sampling stage inside the single train-step kernel). Only meaningful
        when :attr:`fused_train_step` is non-empty; the trainer's
        ``DVNRConfig.fuse_sampling="auto"`` enables it exactly when both are
        non-empty."""
        if not self.supports("fused_sampling"):
            return ""
        if self.is_pallas:
            return "pallas-interpret" if self.interpret else "pallas"
        return "ref"

    @property
    def tiled_sampling(self) -> str:
        """Which volume-tiled in-op sampling implementation this backend can
        run when the partition exceeds :attr:`vmem_limit_bytes`: ``""``
        (none — only VMEM-pinned volumes work), ``"ref"`` (jnp gathers are
        HBM-resident already), ``"pallas-interpret"`` or ``"pallas"`` (the
        brick-tiled train-step kernel). Only meaningful when
        :attr:`fused_sampling` is non-empty; ``sampling_brick="auto"``
        falls back to the pinned layout when this is empty."""
        if not (self.supports("tiled_sampling")
                and self.supports("fused_sampling")):
            return ""
        if self.is_pallas:
            return "pallas-interpret" if self.interpret else "pallas"
        return "ref"

    def supports_dtype(self, dtype) -> bool:
        """Does this backend's kernel family accept ``dtype`` compute natively
        (no silent f32 upcast)? ``dtype``: jnp/np dtype or name."""
        import jax.numpy as jnp
        return jnp.dtype(dtype).name in self.dtypes

    def require_dtype(self, dtype, role: str = "compute"):
        """Resolve ``dtype`` and raise if this backend cannot run it — the
        shared guard of every dtype-aware op entry point. Returns the jnp
        dtype so callers can cast with it."""
        import jax.numpy as jnp
        dt = jnp.dtype(dtype)
        if not self.supports_dtype(dt):
            raise ValueError(f"backend {self.name!r} does not support "
                             f"{role} dtype {dt.name!r}")
        return dt

    def available(self, platform: str | None = None) -> bool:
        """Can this backend run on ``platform`` (default: current jax one)?"""
        plat = platform or jax.default_backend()
        return plat in self.platforms

    def __repr__(self) -> str:  # keep jit cache keys / logs readable
        return f"Backend({self.name!r})"


BackendLike = Union[str, Backend]

_REGISTRY: Dict[str, Backend] = {}
_ALIASES: Dict[str, str] = {}


def register_backend(backend: Backend, *, aliases: Tuple[str, ...] = ()) -> Backend:
    """Register ``backend`` (and optional alias names). Re-registration under
    the same name replaces the previous entry (tests rely on this)."""
    _REGISTRY[backend.name] = backend
    for a in aliases:
        _ALIASES[a] = backend.name
    return backend


def available_backends(platform: str | None = None) -> Tuple[str, ...]:
    """Names of registered backends runnable on ``platform`` (default current)."""
    return tuple(n for n, b in _REGISTRY.items() if b.available(platform))


def get_backend(name: BackendLike) -> Backend:
    """Look up a backend by name (or pass a ``Backend`` through)."""
    if isinstance(name, Backend):
        return name
    key = _ALIASES.get(name, name)
    if key == "auto":
        return resolve_auto()
    try:
        return _REGISTRY[key]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered: "
            f"{sorted(set(_REGISTRY) | set(_ALIASES))}") from None


_DEFAULT_OVERRIDE: Optional[str] = None


def set_default_backend(name: Optional[str]) -> None:
    """Pin what ``resolve("auto")`` returns (``None`` clears the pin).

    This is how the CI backend matrix routes the whole test suite through one
    kernel family: ``REPRO_BACKEND=pallas`` (consumed by ``tests/conftest.py``)
    pins interpret-mode Pallas as the default backend, so every call site that
    says ``backend="auto"`` exercises the Pallas kernels on every push.
    """
    global _DEFAULT_OVERRIDE
    if name is not None:
        key = _ALIASES.get(name, name)
        if key == "auto":
            raise ValueError("cannot pin the default backend to 'auto'")
        backend = get_backend(key)             # validate eagerly
        if not backend.available():
            raise ValueError(
                f"cannot pin default backend {key!r}: not available on "
                f"platform {jax.default_backend()!r}")
        name = key
    _DEFAULT_OVERRIDE = name


def resolve_auto(platform: str | None = None) -> Backend:
    """Highest-priority backend available on the current (or given) platform;
    a :func:`set_default_backend` pin overrides the priority ranking."""
    if _DEFAULT_OVERRIDE is not None:
        return _REGISTRY[_DEFAULT_OVERRIDE]
    cands = [b for b in _REGISTRY.values() if b.available(platform)]
    if not cands:
        raise RuntimeError("no backend available for platform "
                           f"{platform or jax.default_backend()!r}")
    return max(cands, key=lambda b: b.priority)


def resolve(impl: BackendLike = "auto") -> Backend:
    """The one dispatch entry point: name/alias/"auto"/Backend -> Backend."""
    return get_backend(impl)


# --------------------------------------------------------------------------- #
# Built-in backends
# --------------------------------------------------------------------------- #
_ALL_OPS = frozenset(OPS)

register_backend(Backend(
    name="ref", kind="jnp",
    description="pure-jnp oracle kernels (XLA-compiled); runs everywhere",
    priority=10, capabilities=_ALL_OPS,
), aliases=("xla",))

register_backend(Backend(
    name="fused", kind="fused",
    description="jnp with fused corner-gather hash encoding (training fast "
                "path); ops without a fused variant fall back to ref",
    priority=5, capabilities=frozenset({"hash_encoding", "fused_train_step",
                                        "fused_sampling", "tiled_sampling"}),
))

# the ~16 MiB/core VMEM envelope the kernel docstrings budget against; the
# interpret-mode backend enforces the same limit so CPU CI rejects exactly
# the configs that would OOM Mosaic on hardware
_TPU_VMEM_BYTES = 16 * 2**20

register_backend(Backend(
    name="pallas", kind="pallas", interpret=True,
    description="Pallas kernels in interpret mode (CPU kernel debugging)",
    priority=1, capabilities=_ALL_OPS, vmem_limit_bytes=_TPU_VMEM_BYTES,
))

register_backend(Backend(
    name="pallas_tpu", kind="pallas", interpret=False,
    description="compiled Pallas kernels on TPU hardware",
    platforms=("tpu",), priority=2, capabilities=_ALL_OPS,
    vmem_limit_bytes=_TPU_VMEM_BYTES,
))
