"""jit'd wrapper for hash encoding: backend dispatch + custom VJP.

Forward: Pallas kernel (TPU) or pure-jnp oracle (CPU / default).
Backward: scatter-add of the blended cotangents into the 8 corners per level —
expressed as ``.at[].add`` which XLA:TPU lowers to its native combining scatter
(the CUDA analogue is atomicAdd; see DESIGN.md hardware-adaptation notes).

Dispatch goes through :mod:`repro.backends`; ``impl`` accepts a backend name
(``"ref"``, ``"fused"``, ``"pallas"``, ``"pallas_tpu"``, ``"auto"``) or a
resolved :class:`~repro.backends.Backend`.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from repro import backends, tracing
from repro.kernels.hash_encoding import ref as _ref
from repro.kernels.hash_encoding.kernel import hash_encode_pallas


def hash_encode(coords, tables, resolutions: Sequence[int],
                impl: backends.BackendLike = "ref", *, compute_dtype=None):
    """coords (N,3) in [0,1]; tables (L,T,F) -> (N, L*F). Differentiable in tables.

    Output features carry the table dtype — every path (ref / fused / pallas)
    accepts bf16 tables without upcasting. ``compute_dtype`` (a dtype or name)
    casts the tables before encoding (a differentiable cast, so the cotangent
    arrives in the caller's param dtype); coords stay float32 — grid
    *positions* need the mantissa.
    """
    backend = backends.resolve(impl)
    with jax.named_scope(tracing.ENCODE):
        if compute_dtype is not None:
            tables = tables.astype(backend.require_dtype(compute_dtype))
        return _hash_encode(coords, tables, resolutions, backend)


def vmem_footprint(coords, tables, resolutions: Sequence[int],
                   impl: backends.BackendLike = "pallas"):
    """Static VMEM bill of the forward encode: one
    :class:`repro.analysis.vmem.KernelFootprint` per ``pallas_call`` the op
    would emit for these operand shapes (empty on jnp backends). ``coords`` /
    ``tables`` may be ``jax.ShapeDtypeStruct``s — nothing executes."""
    from repro.analysis.vmem import footprint_of

    backend = backends.resolve(impl)
    return footprint_of(lambda c, t: _fwd_impl(c, t, resolutions, backend),
                        coords, tables)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _hash_encode(coords, tables, resolutions, backend: backends.Backend):
    return _fwd_impl(coords, tables, resolutions, backend)


def _use_fused(backend):
    return backend.is_fused and backend.supports("hash_encoding")


def _fwd_impl(coords, tables, resolutions, backend):
    if backend.is_pallas:
        return hash_encode_pallas(coords, tables,
                                  jnp.asarray(resolutions, jnp.int32),
                                  interpret=backend.interpret)
    if _use_fused(backend):
        return _ref.hash_encode_fused(coords, tables, resolutions)
    return _ref.hash_encode_ref(coords, tables, resolutions)


def _fwd(coords, tables, resolutions, backend):
    if _use_fused(backend):
        # store the (small) corner indices/weights as residuals: the backward
        # scatter reuses them instead of recomputing the whole index chain
        # (EXPERIMENTS.md §Perf DVNR iteration C2)
        idx, ww = _ref.fused_corners(coords, resolutions, tables.shape[1])
        out = _ref._combine_fused(idx, ww, tables)
        return out, (coords, tables.shape, idx, ww)
    return _fwd_impl(coords, tables, resolutions, backend), \
        (coords, tables.shape, None, None)


def _bwd(resolutions, backend, res, g):
    coords, tshape, idx, ww = res
    L, T, F = tshape
    N = coords.shape[0]
    if _use_fused(backend):
        # level-vectorized combining scatter (one batched scatter-add)
        gl = g.reshape(N, L, F).transpose(1, 0, 2)                # (L,N,F)
        upd = ww.astype(g.dtype)[..., None] * gl[:, :, None, :]   # (L,N,8,F)
        dt = jax.vmap(lambda i, u_: jnp.zeros((T, F), g.dtype)
                      .at[i.reshape(-1)].add(u_.reshape(-1, F)))(idx, upd)
        return jnp.zeros_like(coords), dt

    g = g.reshape(N, L, F)
    dt = jnp.zeros(tshape, g.dtype)
    for l in range(L):
        r = int(resolutions[l])
        pos = coords * r
        lo = jnp.clip(jnp.floor(pos), 0, max(r - 1, 0)).astype(jnp.int32)
        w = pos - lo
        for dx in (0, 1):
            for dy in (0, 1):
                for dz in (0, 1):
                    corner = lo + jnp.array([dx, dy, dz], jnp.int32)
                    idx = _ref.corner_indices(corner, r, T)
                    ww = (jnp.where(dx, w[:, 0], 1 - w[:, 0])
                          * jnp.where(dy, w[:, 1], 1 - w[:, 1])
                          * jnp.where(dz, w[:, 2], 1 - w[:, 2]))
                    dt = dt.at[l, idx].add(ww[:, None].astype(g.dtype) * g[:, l, :])
    return jnp.zeros_like(coords), dt


_hash_encode.defvjp(_fwd, _bwd)


# --------------------------------------------------------------------------- #
# Grid-access contract (repro.analysis grid_write_safety / hbm_traffic)
# --------------------------------------------------------------------------- #
from repro.analysis.grid import register_discipline  # noqa: E402

register_discipline(
    "_encode_kernel",
    # the (BLOCK_N, 3) coords block is re-streamed once per hash level (the
    # level axis is the outer grid dim); table and output blocks single-pass.
    # Worst-case actual/ideal traffic is 1 + 12(L-1)/(12 + 4F*L) < 2.5 for
    # any level count at F >= 2 (the output array grows with L too).
    input_refetch=("in[0]",),
    traffic_factor=2.5,
    note="coords re-fetched per level; table/output blocks move once")
