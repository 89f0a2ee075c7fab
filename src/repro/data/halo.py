"""Ghost-cell halo exchange for post-hoc volumes (DESIGN.md §2).

In situ, ghost layers come precomputed from the simulation (the paper's
assumption — zero extra communication). For POST-HOC volumes loaded without
ghosts, this module fills them: each partition sends its owned boundary slab
to the face neighbor on the partition grid.

Two implementations with identical semantics:
- ``halo_exchange_ref``: host/gather reference (any P, no mesh);
- ``halo_exchange``: shard_map ``lax.ppermute`` version — one permute per
  face (6 total), each moving an (n^2 * ghost)-cell slab; domain-edge ghosts
  are left untouched (non-periodic).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _neighbor_table(grid: Tuple[int, int, int]) -> np.ndarray:
    """(P, 3, 2) neighbor partition index per (axis, direction); -1 = none."""
    px, py, pz = grid
    P = px * py * pz
    out = np.full((P, 3, 2), -1, np.int64)
    for p in range(P):
        ix, iy, iz = p % px, (p // px) % py, p // (px * py)
        coords = [ix, iy, iz]
        dims = [px, py, pz]
        for ax in range(3):
            for d, step in ((0, -1), (1, +1)):
                c = coords.copy()
                c[ax] += step
                if 0 <= c[ax] < dims[ax]:
                    out[p, ax, d] = c[0] + px * (c[1] + py * c[2])
    return out


def _owned_slab(vol, ax: int, side: int, g: int):
    """The owned boundary slab a partition SENDS toward ``side`` of axis ax."""
    n = vol.shape[ax]
    lo = g if side == 0 else n - 2 * g
    return jax.lax.slice_in_dim(vol, lo, lo + g, axis=ax)


def _set_ghost(vol, slab, ax: int, side: int, g: int):
    n = vol.shape[ax]
    start = [0, 0, 0]
    start[ax] = 0 if side == 0 else n - g
    return jax.lax.dynamic_update_slice(vol, slab, tuple(start))


def halo_exchange_ref(vols: jnp.ndarray, grid: Tuple[int, int, int],
                      ghost: int = 1) -> jnp.ndarray:
    """vols (P, nx+2g, ny+2g, nz+2g) -> same, interior ghosts filled."""
    g = ghost
    nbr = _neighbor_table(grid)
    out = vols
    for ax in range(3):
        for side in (0, 1):
            # ghost slab on ``side`` comes from the neighbor on that side,
            # which sends the slab facing the OPPOSITE direction
            src = nbr[:, ax, side]
            have = src >= 0
            slabs = _owned_slab(out[jnp.asarray(np.where(have, src, 0))],
                                ax + 1, 1 - side, g)
            new = jax.vmap(lambda v, s: _set_ghost(v, s, ax, side, g))(out, slabs)
            out = jnp.where(jnp.asarray(have)[:, None, None, None], new, out)
    return out


def halo_exchange(vols: jnp.ndarray, grid: Tuple[int, int, int], mesh,
                  ghost: int = 1) -> jnp.ndarray:
    """shard_map ppermute halo exchange; vols stacked (P, ...) sharded over
    all mesh axes (one partition per device)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    g = ghost
    axes = tuple(mesh.axis_names)
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))
    assert vols.shape[0] == n_dev, "one partition per device"
    nbr = _neighbor_table(grid)

    def local(v):
        v = v[0]
        for ax in range(3):
            for side in (0, 1):
                # device p sends its slab facing ``side`` to neighbor(p, side);
                # equivalently receiver r gets it as its (1-side) ghost... we
                # build perms receiver-centric: r receives from nbr[r, ax, side].
                pairs = [(int(nbr[r, ax, side]), r) for r in range(n_dev)
                         if nbr[r, ax, side] >= 0]
                send = _owned_slab(v, ax, 1 - side, g)
                got = jax.lax.ppermute(send, axes, pairs)
                me = jax.lax.axis_index(axes)
                has = jnp.asarray(nbr[:, ax, side] >= 0)[me]
                filled = _set_ghost(v, got, ax, side, g)
                v = jnp.where(has, filled, v)
        return v[None]

    spec = P(axes)
    return shard_map(local, mesh=mesh, in_specs=(spec,), out_specs=spec,
                     check_vma=False)(vols)
