"""Synthetic simulation volumes + domain decomposition with ghost cells.

Mirrors the paper's evaluation setup: CloverLeaf-like (compressible Euler shock),
NekRS-like (incompressible turbulence), S3D-like (reactive flow / flame sheets),
plus a "magnetic"-like vortex field. All fields are analytic, deterministic, and
time-dependent, so every rank generates its own partition *in situ* with ghost
cells included — exactly the paper's assumption (ghosts come from the simulation,
no extra communication).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------------- #
# Analytic fields on the global domain [0,1]^3
# --------------------------------------------------------------------------- #
def _octaves(kind_seed: int, n: int = 10):
    rng = np.random.default_rng(kind_seed)
    freqs = 2.0 ** rng.uniform(1.0, 5.0, (n, 3))
    phases = rng.uniform(0, 2 * np.pi, (n, 3))
    amps = rng.uniform(0.3, 1.0, n) / np.arange(1, n + 1)
    return freqs, phases, amps


_FIELDS = {}


def _register(name):
    def deco(fn):
        _FIELDS[name] = fn
        return fn
    return deco


@_register("cloverleaf")
def _cloverleaf(x, y, z, t):
    """Expanding shock sphere + background gradient (energy-like field)."""
    r = jnp.sqrt((x - 0.5) ** 2 + (y - 0.5) ** 2 + (z - 0.5) ** 2)
    front = 0.15 + 0.5 * t
    shock = jnp.exp(-((r - front) / 0.03) ** 2) * 4.0
    interior = jnp.where(r < front, 2.0 - r / jnp.maximum(front, 1e-3), 0.1)
    return shock + interior + 0.2 * x


@_register("nekrs")
def _nekrs(x, y, z, t):
    """Turbulence-like velocity magnitude: sum of advected trig octaves."""
    freqs, phases, amps = _octaves(7)
    v = 0.0
    for i in range(len(amps)):
        fx, fy, fz = freqs[i]
        px, py, pz = phases[i]
        v = v + amps[i] * (
            jnp.sin(2 * np.pi * fx * x + px + 2.1 * t)
            * jnp.sin(2 * np.pi * fy * y + py - 1.3 * t)
            * jnp.sin(2 * np.pi * fz * z + pz + 0.7 * t)
        )
    return v


@_register("s3d")
def _s3d(x, y, z, t):
    """Flame-sheet-like heat release: thin wrinkled reaction zone."""
    freqs, phases, amps = _octaves(13, 6)
    wrinkle = 0.0
    for i in range(len(amps)):
        fx, fy, _ = freqs[i]
        px, py, _ = phases[i]
        wrinkle = wrinkle + 0.03 * amps[i] * jnp.sin(2 * np.pi * fx * x + px + t) \
            * jnp.cos(2 * np.pi * fy * y + py - 0.5 * t)
    sheet = jnp.exp(-((z - 0.5 - wrinkle) / 0.02) ** 2)
    hotspots = jnp.exp(-(((x - 0.3 - 0.2 * t) / 0.08) ** 2
                         + ((y - 0.6) / 0.08) ** 2
                         + ((z - 0.5) / 0.05) ** 2))
    return sheet + 1.5 * hotspots


@_register("magnetic")
def _magnetic(x, y, z, t):
    """Reconnection-like current sheet with islands."""
    b = jnp.tanh((y - 0.5) / 0.05)
    island = 0.3 * jnp.cos(4 * np.pi * (x + 0.1 * t)) * jnp.exp(-((y - 0.5) / 0.1) ** 2)
    return b + island + 0.1 * jnp.sin(2 * np.pi * z)


@_register("velocity")
def _velocity(x, y, z, t):
    """3-component solenoidal-ish field for pathline tracing (returns tuple)."""
    u = jnp.sin(2 * np.pi * x + t) * jnp.cos(2 * np.pi * y)
    v = -jnp.cos(2 * np.pi * x + t) * jnp.sin(2 * np.pi * y)
    w = 0.3 * jnp.sin(2 * np.pi * z + 0.5 * t)
    return jnp.stack([u, v, w], axis=-1)


def synthetic_field(kind: str, coords, t: float = 0.0):
    """coords (..., 3) in global [0,1]^3 -> field values (...,) or (..., 3)."""
    fn = _FIELDS[kind]
    return fn(coords[..., 0], coords[..., 1], coords[..., 2], t)


# --------------------------------------------------------------------------- #
# Domain decomposition
# --------------------------------------------------------------------------- #
def partition_grid(n_parts: int) -> Tuple[int, int, int]:
    """Near-cubic 3D factorization of n_parts (largest factors first on z)."""
    best = (1, 1, n_parts)
    best_cost = float("inf")
    for px in range(1, n_parts + 1):
        if n_parts % px:
            continue
        rem = n_parts // px
        for py in range(1, rem + 1):
            if rem % py:
                continue
            pz = rem // py
            cost = max(px, py, pz) / min(px, py, pz)
            if cost < best_cost:
                best_cost, best = cost, (px, py, pz)
    return best


@dataclass
class VolumePartition:
    """One rank's box partition (with ghost layer) of the global volume."""

    data: jnp.ndarray            # (nx+2g, ny+2g, nz+2g) raw values incl. ghosts
    origin: Tuple[float, ...]    # lower corner in global [0,1]^3
    extent: Tuple[float, ...]    # size in global coords
    ghost: int
    vmin: float
    vmax: float

    @property
    def owned_shape(self) -> Tuple[int, int, int]:
        g = self.ghost
        return tuple(s - 2 * g for s in self.data.shape[:3])

    def normalized(self) -> jnp.ndarray:
        """Values scaled to [0,1] using the partition min/max (paper III-A)."""
        scale = max(self.vmax - self.vmin, 1e-12)
        return (self.data - self.vmin) / scale


def make_partition(kind: str, part_idx: int, grid: Tuple[int, int, int],
                   local_shape: Tuple[int, int, int], t: float = 0.0,
                   ghost: int = 1) -> VolumePartition:
    """Generate rank ``part_idx``'s partition (cell-centered, ghost included)."""
    px, py, pz = grid
    ix = part_idx % px
    iy = (part_idx // px) % py
    iz = part_idx // (px * py)
    nx, ny, nz = local_shape
    ext = (1.0 / px, 1.0 / py, 1.0 / pz)
    org = (ix * ext[0], iy * ext[1], iz * ext[2])
    g = ghost

    # cell centers incl. ghost band, in global coordinates
    def centers(n, o, e):
        i = np.arange(-g, n + g) + 0.5
        return o + (i / n) * e

    cx = centers(nx, org[0], ext[0])
    cy = centers(ny, org[1], ext[1])
    cz = centers(nz, org[2], ext[2])
    X, Y, Z = jnp.meshgrid(jnp.asarray(cx), jnp.asarray(cy), jnp.asarray(cz),
                           indexing="ij")
    coords = jnp.stack([X, Y, Z], axis=-1)
    data = synthetic_field(kind, coords, t).astype(jnp.float32)
    owned = data[g:data.shape[0] - g, g:data.shape[1] - g, g:data.shape[2] - g] \
        if g else data
    vmin = float(owned.min())
    vmax = float(owned.max())
    return VolumePartition(data, org, ext, g, vmin, vmax)


def sample_trilinear(data: jnp.ndarray, coords01: jnp.ndarray, ghost: int = 1):
    """Trilinear sampling of a local partition at normalized local coords.

    ``data``: (nx+2g, ny+2g, nz+2g[, C]); ``coords01``: (N,3) in [0,1]^3 over the
    *owned* region. Ghost cells extend valid interpolation across partition
    boundaries (paper Fig. 2A).
    """
    g = ghost
    shape = jnp.asarray(data.shape[:3], jnp.float32)
    owned = shape - 2 * g
    # cell-centered: coord c maps to index c*n - 0.5 (+g offset)
    pos = coords01 * owned - 0.5 + g
    lo = jnp.clip(jnp.floor(pos), 0, shape - 2).astype(jnp.int32)
    w = jnp.clip(pos - lo, 0.0, 1.0)

    # single batched 8-corner gather (one take instead of 8 advanced-index
    # gathers; see EXPERIMENTS.md §Perf DVNR iteration), indexed in 3-D: a
    # flat view of a tiled volume is a copy of it on a TPU
    off = jnp.asarray(np.stack(np.meshgrid([0, 1], [0, 1], [0, 1],
                                           indexing="ij"), -1).reshape(8, 3),
                      jnp.int32)
    corner = lo[:, None, :] + off[None]                       # (N,8,3)
    vals = data[corner[..., 0], corner[..., 1], corner[..., 2]]  # (N,8[,C])
    wsel = jnp.where(off[None].astype(w.dtype) == 1,
                     w[:, None, :], 1.0 - w[:, None, :])      # (N,8,3)
    ww = wsel[..., 0] * wsel[..., 1] * wsel[..., 2]           # (N,8)
    if vals.ndim == 3:
        return jnp.einsum("nc,ncd->nd", ww, vals)
    return jnp.einsum("nc,nc->n", ww, vals)
