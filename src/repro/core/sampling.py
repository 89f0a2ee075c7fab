"""Training-sample generation: stochastic uniform + boundary half-Gaussian (III-C).

The boundary density (paper Eq. 2) is a mixture over the 6 faces: pick an axis
and a side uniformly, draw |N(0, sigma)| as the distance from that face, and
uniform coordinates on the other two axes. The total loss draws
(1-lambda)*N uniform and lambda*N boundary samples so cost is lambda-independent.

The generator is COUNTER-BASED: every random word is a pure function of
``(seed words, sample row, word index)`` through a hand-rolled Threefry-2x32
block cipher written in plain uint32 arithmetic. That one property carries the
whole in-kernel sampling design (:mod:`repro.kernels.fused_train_step`):

- the exact same :func:`counter_coords` runs on the host (unfused trainer
  step, ref composition of the fused op) and INSIDE the Pallas train-step
  kernel — rows are global sample ids, so the kernel's batch tiling does not
  change the draws and all paths are bit-comparable;
- no ``threefry2x32`` jaxpr primitive is emitted anywhere (the cipher is
  adds/xors/rotates), so a scan-fused chunk with in-kernel sampling contains
  no RNG ops outside the fused op — asserted by
  ``tests/test_fused_sampling.py``;
- reproducibility contract: per training step the seed words are
  ``step_seeds(key, step, p) = threefry(key_words(key), (step, p))``, i.e. a
  pure function of the user's PRNGKey, the step counter and the partition
  index — the counter-based analogue of the legacy :func:`step_keys` /
  ``jax.random.fold_in`` chain.

``step_keys`` (jax.random-based) is kept for callers that need real PRNGKeys;
the trainer itself is fully on the counter path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# one Threefry block yields 2 words; 4 blocks = 8 words per sample row:
# block outputs a[:, 0..3] / b[:, 0..3] are assigned in counter_coords
_N_PAIRS = 4
_PARITY = 0x1BD11BDA
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def _rotl(x, d: int):
    return (x << jnp.uint32(d)) | (x >> jnp.uint32(32 - d))


def threefry2x32(k0, k1, c0, c1):
    """Standard 20-round Threefry-2x32: counters (c0, c1) -> two random words.

    Exactly the cipher behind ``jax.random``, but expressed as elementwise
    uint32 adds/xors/rotates so it (a) runs inside Pallas kernels and (b)
    never emits the ``threefry2x32`` jaxpr primitive. All args broadcast;
    returns ``(x0, x1)`` uint32 arrays of the broadcast shape.
    """
    k0 = jnp.asarray(k0, jnp.uint32)
    k1 = jnp.asarray(k1, jnp.uint32)
    x0 = jnp.asarray(c0, jnp.uint32) + k0
    x1 = jnp.asarray(c1, jnp.uint32) + k1
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_PARITY))
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x0 ^ x1
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


def key_words(key):
    """A PRNGKey (raw uint32 pair or typed) -> ``(k0, k1)`` scalar seed words."""
    if hasattr(key, "dtype") and jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    key = jnp.asarray(key, jnp.uint32).reshape(-1)
    return key[0], key[1]


def uniform01(bits):
    """uint32 words -> f32 uniforms in [0, 1) (top 24 bits, exact in f32).

    The top 24 bits are below 2^24, so they pass through int32 unchanged and
    convert to f32 exactly; the TPU kernel compiler has no uint32 -> f32
    cast, and this route gives the same bits on every backend."""
    top = (bits >> jnp.uint32(8)).astype(jnp.int32)
    return top.astype(jnp.float32) * jnp.float32(1 / (1 << 24))


def n_boundary(n_batch: int, boundary_lambda: float) -> int:
    """Static split of the batch (paper III-C): lambda*N boundary samples."""
    return int(round(boundary_lambda * n_batch))


def counter_coords(k0, k1, rows, n_uniform: int, sigma: float):
    """The shared sampling stage: global sample ids -> training coordinates.

    ``rows`` is an (N, 1) int32 column of GLOBAL sample indices (inside the
    Pallas kernel: ``tile * BLOCK_N + iota``); rows ``< n_uniform`` draw
    uniformly in [0,1)^3, rows ``>= n_uniform`` draw the paper's Eq. 2
    boundary mixture (uniform face/side, |N(0, sigma)| offset via Box-Muller).
    Every op here is elementwise / iota, so the function is Pallas-legal and
    bit-comparable between the host and in-kernel paths.
    """
    n = rows.shape[0]
    c0 = jnp.broadcast_to(rows, (n, _N_PAIRS)).astype(jnp.uint32)
    c1 = jax.lax.broadcasted_iota(jnp.uint32, (n, _N_PAIRS), 1)
    a, b = threefry2x32(k0, k1, c0, c1)

    u3 = uniform01(a[:, :3])                                     # (N, 3)
    # floor(u*k) with a defensive min: u < 1 exactly, but stay safe vs rounding
    axis = jnp.minimum((uniform01(a[:, 3]) * 3.0).astype(jnp.int32), 2)
    side = jnp.minimum((uniform01(b[:, 0]) * 2.0).astype(jnp.int32),
                       1).astype(jnp.float32)
    # half-Gaussian |N(0, sigma)| via Box-Muller; 1 - u in [2^-24, 1] so the
    # log never sees 0
    u_r = uniform01(b[:, 1])
    u_t = uniform01(b[:, 2])
    mag = sigma * jnp.sqrt(-2.0 * jnp.log(1.0 - u_r))
    off = jnp.clip(jnp.abs(mag * jnp.cos(jnp.float32(2.0 * np.pi) * u_t)),
                   0.0, 1.0)
    coord = side * (1.0 - off) + (1.0 - side) * off              # near 0 or 1
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (n, 3), 1)
              == axis[:, None]).astype(jnp.float32)
    boundary = u3 * (1.0 - onehot) + coord[:, None] * onehot
    is_b = (rows >= n_uniform).astype(jnp.float32)               # (N, 1)
    return u3 * (1.0 - is_b) + boundary * is_b


def gather_trilinear_bricked(vol, coords, ghost: int, brick):
    """Host-side oracle of the brick-TILED in-kernel gather
    (:func:`repro.kernels.fused_train_step.kernel.fused_train_step_sampling_tiled_pallas`).

    Visits the ghost-padded volume one ``brick`` = (bx, by, bz) block at a
    time (a python loop standing in for the kernel's brick grid axis), banks
    the raw values of the 8 trilinear corners OWNED by each brick
    (``corner_voxel // brick == brick_index`` per axis — owner bricks
    partition the corner voxels, so each (corner, sample) slot is written
    exactly once), then combines the banked values in the canonical
    (dx, dy, dz) corner order with the cell-center weights of
    :func:`repro.data.volume.sample_trilinear`. Bit-exact vs the in-kernel
    pinned/tiled gathers (same expressions, same summation order); equal to
    ``sample_trilinear`` up to floating-point summation order.

    ``vol``: (nx, ny, nz[, C]) ghost-padded partition; ``coords``: (N, 3)
    f32 in [0, 1]^3 over the owned region. Returns (N, C) f32.
    """
    vol = vol if vol.ndim == 4 else vol[..., None]
    nx, ny, nz, C = vol.shape
    bx, by, bz = (min(int(b), int(n)) for b, n in zip(brick, (nx, ny, nz)))
    los, ws = [], []
    for ax, n in enumerate((nx, ny, nz)):
        owned = jnp.float32(n - 2 * ghost)
        pos = coords[:, ax].astype(jnp.float32) * owned - 0.5 \
            + jnp.float32(ghost)
        lo = jnp.clip(jnp.floor(pos), 0.0, jnp.float32(n - 2))
        los.append(lo.astype(jnp.int32))
        ws.append(jnp.clip(pos - lo, 0.0, 1.0))
    n_samples = coords.shape[0]
    corners = [jnp.zeros((n_samples, C), jnp.float32) for _ in range(8)]
    offsets = [(dx, dy, dz) for dx in (0, 1) for dy in (0, 1)
               for dz in (0, 1)]
    for bxi in range(-(-nx // bx)):
        for byi in range(-(-ny // by)):
            for bzi in range(-(-nz // bz)):
                sub = vol[bxi * bx:(bxi + 1) * bx, byi * by:(byi + 1) * by,
                          bzi * bz:(bzi + 1) * bz]
                sx, sy, sz = sub.shape[:3]
                flat = sub.reshape(sx * sy * sz, C).astype(jnp.float32)
                for k, (dx, dy, dz) in enumerate(offsets):
                    cx, cy, cz = los[0] + dx, los[1] + dy, los[2] + dz
                    own = ((cx // bx == bxi) & (cy // by == byi)
                           & (cz // bz == bzi))
                    rx = jnp.clip(cx - bxi * bx, 0, sx - 1)
                    ry = jnp.clip(cy - byi * by, 0, sy - 1)
                    rz = jnp.clip(cz - bzi * bz, 0, sz - 1)
                    vals = jnp.take(flat, (rx * sy + ry) * sz + rz, axis=0)
                    corners[k] = jnp.where(own[:, None], vals, corners[k])
    acc = None
    for k, (dx, dy, dz) in enumerate(offsets):
        ww = (ws[0] if dx else 1.0 - ws[0]) \
            * (ws[1] if dy else 1.0 - ws[1]) \
            * (ws[2] if dz else 1.0 - ws[2])
        term = ww[:, None] * corners[k]
        acc = term if acc is None else acc + term
    return acc


def training_coords_counter(seed, n_batch: int, boundary_lambda: float,
                            sigma: float):
    """Counter-based batch: (2,) uint32 seed words -> (N, 3) coords.

    First ``N - round(lambda*N)`` rows uniform, the rest boundary — the same
    layout the in-kernel sampler produces for the same seed."""
    n_u = n_batch - n_boundary(n_batch, boundary_lambda)
    rows = jax.lax.broadcasted_iota(jnp.int32, (n_batch, 1), 0)
    return counter_coords(seed[0], seed[1], rows, n_u, sigma)


def step_seeds(key, step, n_partitions: int) -> jnp.ndarray:
    """(P, 2) uint32 per-partition seed words for one training step:
    ``threefry(key_words(key), (step, p))``. The single source of per-step
    randomness for every trainer path (unfused, fused, fused-with-in-kernel-
    sampling), so all of them draw identical sample batches for the same
    ``(key, step, p)``. Emits no ``threefry2x32`` primitive (the scan-fused
    chunk body stays free of RNG ops outside the fused op)."""
    k0, k1 = key_words(key)
    p = jnp.arange(n_partitions, dtype=jnp.uint32)
    s0, s1 = threefry2x32(k0, k1,
                          jnp.broadcast_to(jnp.asarray(step, jnp.uint32),
                                           (n_partitions,)), p)
    return jnp.stack([s0, s1], axis=1)


def step_keys(key, step, n_partitions: int) -> jnp.ndarray:
    """Per-partition jax.random keys for one step (fold in step, then
    partition). Legacy helper for callers that need real PRNGKeys; the trainer
    now derives :func:`step_seeds` instead (same contract, counter-based)."""
    base = jax.random.fold_in(key, step)
    return jax.vmap(lambda p: jax.random.fold_in(base, p))(
        jnp.arange(n_partitions))


def training_coords(key, n_batch: int, boundary_lambda: float, sigma: float):
    """(1-lambda)N uniform + lambda N boundary samples (paper III-C).

    Public convenience wrapper over the counter-based generator: the draws
    are ``training_coords_counter(key_words(key), ...)``."""
    return training_coords_counter(jnp.stack(key_words(key)), n_batch,
                                   boundary_lambda, sigma)
