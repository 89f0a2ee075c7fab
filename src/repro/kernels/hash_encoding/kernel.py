"""Pallas TPU kernel: fused multi-resolution hash encoding (gather + trilerp).

TPU-native blocking (vs. the paper's CUDA gather kernel):
  grid = (L levels, N/BLOCK_N coord tiles)
  - the level's table slice (1, T, F) is pinned in VMEM for all coord tiles of
    that level (level-major grid order), so each table is DMA'd from HBM once;
  - a (BLOCK_N, 3) coordinate tile is broadcast across levels;
  - the 8-corner gather + trilinear blend happens entirely in VMEM/VREGs and the
    (BLOCK_N, 1, F) feature tile is written out fused (no (N, 8, F) intermediate).

VMEM budget: T*F*4 bytes per level block; the adaptive-parameter rule of the
paper (III-B) keeps per-partition T at 2^11..2^16, i.e. <= 16 MB VMEM at F=4.
Validated in interpret mode on CPU; resolutions arrive via scalar prefetch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_N = 1024
_P0, _P1, _P2 = 1, 2_654_435_761, 805_459_861


def _encode_kernel(res_ref, coords_ref, table_ref, out_ref):
    l = pl.program_id(0)
    res = res_ref[l]
    table = table_ref[0]                                  # (T, F) in VMEM
    T = table.shape[0]
    n_dense = (res + 1) * (res + 1) * (res + 1)

    coords = coords_ref[...]                              # (BN, 3)
    rf = res.astype(coords.dtype)
    pos = coords * rf
    lo = jnp.clip(jnp.floor(pos), 0, jnp.maximum(rf - 1, 0)).astype(jnp.int32)
    w = pos - lo.astype(coords.dtype)                     # (BN, 3)

    acc = jnp.zeros((coords.shape[0], table.shape[1]), table.dtype)
    rp1 = (res + 1).astype(jnp.uint32)
    for dx in (0, 1):
        for dy in (0, 1):
            for dz in (0, 1):
                cx = (lo[:, 0] + dx).astype(jnp.uint32)
                cy = (lo[:, 1] + dy).astype(jnp.uint32)
                cz = (lo[:, 2] + dz).astype(jnp.uint32)
                dense = cx + rp1 * (cy + rp1 * cz)
                hashed = (cx * jnp.uint32(_P0)) ^ (cy * jnp.uint32(_P1)) \
                    ^ (cz * jnp.uint32(_P2))
                idx = jnp.where(n_dense <= T, dense, hashed) % jnp.uint32(T)
                ww = (jnp.where(dx, w[:, 0], 1 - w[:, 0])
                      * jnp.where(dy, w[:, 1], 1 - w[:, 1])
                      * jnp.where(dz, w[:, 2], 1 - w[:, 2]))
                acc = acc + ww[:, None].astype(table.dtype) * jnp.take(
                    table, idx.astype(jnp.int32), axis=0)
    out_ref[:, 0, :] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def hash_encode_pallas(coords: jnp.ndarray, tables: jnp.ndarray,
                       resolutions: jnp.ndarray, *, interpret: bool):
    """coords (N,3) float32 in [0,1]; tables (L,T,F); resolutions (L,) int32.

    Returns (N, L*F) features. N is padded to BLOCK_N internally.
    """
    N = coords.shape[0]
    L, T, F = tables.shape
    n_pad = (-N) % BLOCK_N
    coords_p = jnp.pad(coords, ((0, n_pad), (0, 0)))
    grid = (L, (N + n_pad) // BLOCK_N)

    out = pl.pallas_call(
        _encode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((BLOCK_N, 3), lambda l, i, res_ref: (i, 0)),
                pl.BlockSpec((1, T, F), lambda l, i, res_ref: (l, 0, 0)),
            ],
            out_specs=pl.BlockSpec((BLOCK_N, 1, F), lambda l, i, res_ref: (i, l, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((N + n_pad, L, F), tables.dtype),
        interpret=interpret,
    )(resolutions.astype(jnp.int32), coords_p, tables)
    return out[:N].reshape(N, L * F)
