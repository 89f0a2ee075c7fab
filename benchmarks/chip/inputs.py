"""Inputs made from ``--seed``: the field each rank trains on, the INR
weights, and the seed's own draws.

The field is the synthetic CloverLeaf-like shock (an expanding spherical
front over a radial interior and a background gradient) on the global domain
[0, 1]^3, cut into a near-cubic grid of boxes, one per rank, each with its
ghost layer, cell-centred. Each rank's values are normalised by its owned
region's minimum and maximum (paper III-A). Everything is computed on the
device in one jitted call; on a mesh each device makes its own ranks.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Draws:
    """What one ``--seed`` fixes besides the weights' key."""

    key: np.ndarray              # (2,) uint32 raw PRNG key of the training
    weights_key: np.ndarray      # (2,) uint32 raw PRNG key of the weights
    rng: np.random.Generator     # the seed's host generator, for the rest


def draws(seed: int) -> Draws:
    rng = np.random.default_rng(int(seed))
    words = rng.integers(0, 2**32, size=4, dtype=np.uint64).astype(np.uint32)
    return Draws(words[:2], words[2:], rng)


def partition_grid(n: int) -> tuple[int, int, int]:
    """Near-cubic factorisation of ``n`` ranks (largest factor on z)."""
    best, best_cost = (1, 1, n), float("inf")
    for px in range(1, n + 1):
        if n % px:
            continue
        for py in range(1, n // px + 1):
            if (n // px) % py:
                continue
            pz = n // px // py
            cost = max(px, py, pz) / min(px, py, pz)
            if cost < best_cost:
                best_cost, best = cost, (px, py, pz)
    return best


def boxes(n: int) -> list[tuple[tuple[float, ...], tuple[float, ...]]]:
    """(origin, extent) of each rank's box, rank-major in x, then y, then z."""
    px, py, pz = partition_grid(n)
    ext = (1.0 / px, 1.0 / py, 1.0 / pz)
    out = []
    for p in range(n):
        ix, iy, iz = p % px, (p // px) % py, p // (px * py)
        out.append(((ix * ext[0], iy * ext[1], iz * ext[2]), ext))
    return out


def cloverleaf(x, y, z, t):
    r = jnp.sqrt((x - 0.5) ** 2 + (y - 0.5) ** 2 + (z - 0.5) ** 2)
    front = 0.15 + 0.5 * t
    shock = jnp.exp(-((r - front) / 0.03) ** 2) * 4.0
    interior = jnp.where(r < front, 2.0 - r / jnp.maximum(front, 1e-3), 0.1)
    return shock + interior + 0.2 * x


FIELDS = {"cloverleaf": cloverleaf}


def _rank_volume(field, local: int, ghost: int, t, origin, extent):
    """One rank's ghost-padded raw volume and its owned (min, max)."""
    i = (jnp.arange(-ghost, local + ghost, dtype=jnp.float32) + 0.5) / local
    cx, cy, cz = (origin[a] + i * extent[a] for a in range(3))
    vol = field(cx[:, None, None], cy[None, :, None], cz[None, None, :], t)
    vol = vol.astype(jnp.float32)
    owned = vol[ghost:ghost + local, ghost:ghost + local, ghost:ghost + local]
    lo, hi = owned.min(), owned.max()
    return (vol - lo) / jnp.maximum(hi - lo, 1e-12), jnp.stack([lo, hi])


def make_volumes(config: dict, t: float, mesh=None):
    """(P, n+2g, n+2g, n+2g) normalised volumes on the device (sharded over
    the rank axis on a mesh) and the (P, 2) raw owned ranges, on the host."""
    P, local, ghost = config["ranks"], config["local"], config["ghost"]
    field = FIELDS[config["field"]]
    geo = np.asarray([o + e for o, e in boxes(P)], np.float32)   # (P, 6)

    def some(geo_local, t):
        return jax.lax.map(
            lambda g: _rank_volume(field, local, ghost, t, g[:3], g[3:]),
            geo_local)

    t = np.float32(t)           # an operand: one program for every seed
    if mesh is None:
        vols, ranges = jax.jit(some)(geo, t)
    else:
        from jax.sharding import NamedSharding, PartitionSpec

        spec = PartitionSpec(tuple(mesh.axis_names))
        fn = jax.shard_map(some, mesh=mesh, in_specs=(spec, PartitionSpec()),
                           out_specs=(spec, spec), check_vma=False)
        vols, ranges = jax.jit(fn)(jax.device_put(
            geo, NamedSharding(mesh, spec)), t)
    return vols, np.asarray(ranges, np.float64)


def make_weights(model: dict, ranks: int, key, table_range: float):
    """Stacked INR weights of ``ranks`` ranks in one jitted call: hash
    tables uniform in [-table_range, table_range], the bias-free MLP
    He-uniform (bound sqrt(6 / fan_in))."""
    L, F = model["n_levels"], model["n_features_per_level"]
    T = 1 << model["log2_hashmap_size"]
    W, H = model["n_neurons"], model["n_hidden_layers"]
    dims = [L * F] + [W] * H + [model.get("out_dim", 1)]

    def one(k):
        ks = jax.random.split(k, len(dims))
        tables = jax.random.uniform(ks[0], (L, T, F), jnp.float32,
                                    -table_range, table_range)
        mlp = [jax.random.uniform(ks[i + 1], (a, b), jnp.float32,
                                  -np.sqrt(6.0 / a), np.sqrt(6.0 / a))
               for i, (a, b) in enumerate(zip(dims[:-1], dims[1:]))]
        return {"tables": tables, "mlp": mlp}

    @jax.jit
    def all_ranks(key):
        return jax.vmap(one)(jax.random.split(key, ranks))

    return all_ranks(jnp.asarray(key, jnp.uint32))
