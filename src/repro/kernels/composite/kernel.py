"""Pallas TPU kernel: front-to-back over-operator compositing.

This is the shading/compositing stage of the paper's sample-streaming renderer
(Wu et al. [2]): sample radiances arrive as (rays, samples, rgba) and are
reduced along the sample axis with the non-commutative over operator.

Blocking: grid = (R/BLOCK_R, S/BLOCK_S); the sample axis is the minor
(sequential) grid dimension, so a VMEM scratch accumulator carries
(color, transmittance) across sample blocks for each ray tile — the TPU
analogue of the CUDA persistent-thread compositor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_R = 256
BLOCK_S = 64


def _composite_kernel(rgba_ref, out_ref, acc_ref, trans_ref, *, n_s_blocks):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        trans_ref[...] = jnp.ones_like(trans_ref)

    rgba = rgba_ref[...]                       # (BR, BS*4), lane-dense
    color = acc_ref[...]
    trans = trans_ref[...]
    for s in range(rgba.shape[1] // 4):        # static unroll within the block
        a = rgba[:, 4 * s + 3:4 * s + 4]
        color = color + trans * a * rgba[:, 4 * s:4 * s + 3]
        trans = trans * (1.0 - a)
    acc_ref[...] = color
    trans_ref[...] = trans

    @pl.when(j == n_s_blocks - 1)
    def _write():
        # the f32 scratch accumulation casts back down for bf16 inputs
        out_ref[...] = jnp.concatenate([color, 1.0 - trans],
                                       axis=-1).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def composite_pallas(rgba: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    R, S, _ = rgba.shape
    pr, ps = (-R) % BLOCK_R, (-S) % BLOCK_S
    rgba_p = jnp.pad(rgba, ((0, pr), (0, ps), (0, 0)))  # padded samples: a=0 (no-op)
    Rp, Sp = R + pr, S + ps
    n_s_blocks = Sp // BLOCK_S
    # samples x rgba flattened onto the lane axis: a (BR, BS, 4) block would
    # pad its 4-wide minor dim to 128 lanes (32x the VMEM)
    rgba_p = rgba_p.reshape(Rp, Sp * 4)
    out = pl.pallas_call(
        functools.partial(_composite_kernel, n_s_blocks=n_s_blocks),
        grid=(Rp // BLOCK_R, n_s_blocks),
        in_specs=[pl.BlockSpec((BLOCK_R, BLOCK_S * 4), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((BLOCK_R, 4), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, 4), rgba.dtype),
        scratch_shapes=[pltpu.VMEM((BLOCK_R, 3), jnp.float32),
                        pltpu.VMEM((BLOCK_R, 1), jnp.float32)],
        interpret=interpret,
    )(rgba_p)
    return out[:R]
