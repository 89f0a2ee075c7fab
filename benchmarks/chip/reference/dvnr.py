"""Plain float32 reference of DVNR training.

Written from the method's description (arXiv 2304.10516, section III, and the
instant-ngp hash encoding it builds on), in straightforward ``jax.numpy``:
no kernels, no batching over ranks, no caches. It imports nothing of the
program and takes nothing the program made; its inputs are the benchmark's
own (field, weights, key). Matmuls run at ``highest`` precision.

Training, one rank, one step:

1. the batch: ``N`` rows, the first ``N - round(lambda N)`` uniform in
   [0, 1)^3, the rest on the paper's boundary density (a face picked
   uniformly, the distance from it |N(0, sigma)|). Every random word is
   Threefry-2x32 (20 rounds) of the step's seed words and the counter
   ``(row, word)``; the step's seed words are Threefry-2x32 of the training
   key and ``(step, rank)``. Uniforms are the top 24 bits over 2^24.
2. the target: trilinear interpolation of the rank's ghost-padded,
   cell-centred volume (index ``c * n - 0.5 + ghost``, clamped).
3. the prediction: per level ``l`` at resolution ``r_l = max(2,
   int(R0 * s^l))``, the 8 corners around ``c * r_l`` (lower corner clamped
   to ``r_l - 1``) index the level's table densely (``x + (r+1)(y + (r+1)z)``)
   when ``(r_l + 1)^3 <= T``, else by the spatial hash ``(x * 1) ^ (y *
   2654435761) ^ (z * 805459861) mod T``; their rows are blended trilinearly
   and the levels concatenated into a bias-free ReLU MLP with a linear output.
4. the loss: mean absolute error; its gradient by automatic
   differentiation.
5. AdamW: ``m = b1 m + (1 - b1) g``, ``v = b2 v + (1 - b2) g^2``, bias
   corrected, ``p -= lr (m^ / (sqrt(v^) + eps) + wd p)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_PARITY = 0x1BD11BDA
_ROTATIONS = (13, 15, 26, 6, 17, 29, 16, 24)
_PRIMES = (1, 2_654_435_761, 805_459_861)
_CORNERS = [(dx, dy, dz) for dx in (0, 1) for dy in (0, 1) for dz in (0, 1)]


# --------------------------------------------------------------------------- #
# Counter-based random words
# --------------------------------------------------------------------------- #
def threefry2x32(k0, k1, c0, c1):
    """Threefry-2x32 with 20 rounds (Salmon et al., SC'11) of the counter
    ``(c0, c1)`` under the key ``(k0, k1)``; all uint32, broadcast."""
    u = jnp.uint32
    k0, k1 = jnp.asarray(k0, u), jnp.asarray(k1, u)
    keys = (k0, k1, k0 ^ k1 ^ u(_PARITY))
    x = [jnp.asarray(c0, u) + keys[0], jnp.asarray(c1, u) + keys[1]]
    for group in range(5):
        for r in _ROTATIONS[4 * (group % 2):4 * (group % 2) + 4]:
            x[0] = x[0] + x[1]
            x[1] = (x[1] << u(r)) | (x[1] >> u(32 - r))
            x[1] = x[1] ^ x[0]
        x[0] = x[0] + keys[(group + 1) % 3]
        x[1] = x[1] + keys[(group + 2) % 3] + u(group + 1)
    return x[0], x[1]


def uniform(bits):
    """uint32 words -> float32 in [0, 1): the top 24 bits over 2^24."""
    return (bits >> jnp.uint32(8)).astype(jnp.float32) / np.float32(1 << 24)


def step_seed(key, step: int, rank: int):
    """The two seed words of one rank's batch at one step (0-based)."""
    key = jnp.asarray(key, jnp.uint32)
    return threefry2x32(key[0], key[1], jnp.uint32(step), jnp.uint32(rank))


def batch_coords(seed, n: int, boundary_lambda: float, sigma: float):
    s0, s1 = seed
    rows = jnp.arange(n, dtype=jnp.uint32)[:, None]
    words = jnp.arange(4, dtype=jnp.uint32)[None, :]
    a, b = threefry2x32(s0, s1, rows, words)                    # (n, 4) each
    u3 = uniform(a[:, :3])
    axis = jnp.minimum((uniform(a[:, 3]) * 3.0).astype(jnp.int32), 2)
    far_side = jnp.minimum((uniform(b[:, 0]) * 2.0).astype(jnp.int32), 1) == 1
    radius = sigma * jnp.sqrt(-2.0 * jnp.log(1.0 - uniform(b[:, 1])))
    dist = jnp.clip(jnp.abs(radius * jnp.cos(np.float32(2.0 * np.pi)
                                             * uniform(b[:, 2]))), 0.0, 1.0)
    face = jnp.where(far_side, 1.0 - dist, dist)
    boundary = jnp.where(jnp.arange(3)[None, :] == axis[:, None],
                         face[:, None], u3)
    n_uniform = n - int(round(boundary_lambda * n))
    is_boundary = jnp.arange(n)[:, None] >= n_uniform
    return jnp.where(is_boundary, boundary, u3)


# --------------------------------------------------------------------------- #
# Interpolation, encoding, MLP
# --------------------------------------------------------------------------- #
def trilinear(vol, coords, ghost: int):
    """Cell-centred trilinear value of ``vol`` (ghost-padded) at ``coords``
    in [0, 1]^3 over the owned region."""
    shape = np.asarray(vol.shape[:3])
    pos = coords * (shape - 2 * ghost).astype(np.float32) - 0.5 + ghost
    lo = jnp.clip(jnp.floor(pos), 0, shape - 2).astype(jnp.int32)
    w = jnp.clip(pos - lo, 0.0, 1.0)
    out = jnp.zeros(coords.shape[0], jnp.float32)
    for d in _CORNERS:
        weight = jnp.prod(jnp.where(np.asarray(d) == 1, w, 1.0 - w), axis=1)
        i = lo + np.asarray(d, np.int32)
        out = out + weight * vol[i[:, 0], i[:, 1], i[:, 2]]
    return out


def resolutions(model: dict) -> list[int]:
    r0, s = model["base_resolution"], model["per_level_scale"]
    return [max(2, int(r0 * s ** level)) for level in range(model["n_levels"])]


def corner_index(ijk, res: int, table_size: int):
    u = ijk.astype(jnp.uint32)
    if (res + 1) ** 3 <= table_size:
        idx = u[:, 0] + jnp.uint32(res + 1) * (u[:, 1]
                                               + jnp.uint32(res + 1) * u[:, 2])
    else:
        idx = ((u[:, 0] * jnp.uint32(_PRIMES[0]))
               ^ (u[:, 1] * jnp.uint32(_PRIMES[1]))
               ^ (u[:, 2] * jnp.uint32(_PRIMES[2]))) % jnp.uint32(table_size)
    return idx.astype(jnp.int32)


def encode(tables, coords, res_list):
    T = tables.shape[1]
    feats = []
    for level, res in enumerate(res_list):
        pos = coords * np.float32(res)
        lo = jnp.clip(jnp.floor(pos), 0, res - 1).astype(jnp.int32)
        w = pos - lo
        f = jnp.zeros((coords.shape[0], tables.shape[2]), tables.dtype)
        for d in _CORNERS:
            weight = jnp.prod(jnp.where(np.asarray(d) == 1, w, 1.0 - w), axis=1)
            idx = corner_index(lo + np.asarray(d, np.int32), res, T)
            f = f + weight[:, None].astype(tables.dtype) * tables[level][idx]
        feats.append(f)
    return jnp.concatenate(feats, axis=1)


def mlp(weights, x):
    for w in weights[:-1]:
        x = jnp.maximum(x @ w, 0.0)
    return x @ weights[-1]


# --------------------------------------------------------------------------- #
# Training
# --------------------------------------------------------------------------- #
def _spec(model: dict, ghost: int, batch_fraction: float) -> tuple:
    """The static, hashable part of a training step."""
    n = model["batch_size"]
    return (n, int(n * batch_fraction), float(model["boundary_lambda"]),
            float(model["boundary_sigma"]), tuple(resolutions(model)), ghost,
            float(model["lrate"]), float(model["weight_decay"]),
            float(model["adam_eps"]), float(model["adam_beta1"]),
            float(model["adam_beta2"]))


@functools.partial(jax.jit, static_argnums=0)
def _step(spec, params, m, v, vol, t, seed):
    n, keep, lam, sigma, res_list, ghost, lr, wd, eps, b1, b2 = spec
    coords = batch_coords(seed, n, lam, sigma)[:keep]
    target = trilinear(vol, coords, ghost)

    def loss_fn(p):
        pred = mlp(p["mlp"], encode(p["tables"], coords, res_list))[:, 0]
        return jnp.mean(jnp.abs(pred - target))

    loss, g = jax.value_and_grad(loss_fn)(params)
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, m_, v_: p - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + eps)
                                    + wd * p), params, m, v)
    return params, m, v, loss, g


def train(model: dict, params, vol, key, rank: int, steps: int, ghost: int,
          batch_fraction: float = 1.0):
    """``steps`` steps of one rank from ``params``. Returns the loss of each
    step, the first step's gradient, and the Adam first moment and the
    parameters after the last step. ``batch_fraction < 1`` keeps only the
    batch's leading rows (a planted fault, for the check's own readings)."""
    spec = _spec(model, ghost, batch_fraction)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad1 = [], None
    with jax.default_matmul_precision("highest"):
        for step in range(steps):
            params, m, v, loss, g = _step(spec, params, m, v, vol,
                                          np.float32(step + 1),
                                          step_seed(key, step, rank))
            losses.append(float(loss))
            grad1 = g if grad1 is None else grad1
    return {"losses": losses, "grad1": grad1, "m": m, "params": params}
