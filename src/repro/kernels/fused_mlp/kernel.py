"""Pallas TPU kernel: fused bias-free ReLU MLP (tiny-cuda-nn analogue).

The paper trains with tiny-cuda-nn's fully-fused MLP: all layer weights stay in
shared memory and the batch streams through one kernel. The TPU translation:
weights (D_in x W, (H-1) x W x W, W x D_out — a few hundred KB at W<=128) are
pinned in VMEM for every batch tile; a (BLOCK_N, D_in) tile runs the whole
layer stack on the MXU inside a single pallas_call. No inter-layer HBM traffic.

Backward pass: a second kernel recomputes forward activations in VMEM and
accumulates dW across batch tiles into aliased output blocks (TPU grid is
sequential over the batch dimension, so `+=` accumulation is safe) — this is
the MXU-friendly replacement for CUDA's atomics-based accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_N = 512


def _fwd_kernel(x_ref, w_in_ref, w_hid_ref, w_out_ref, out_ref, *, n_hidden):
    h = jnp.maximum(x_ref[...] @ w_in_ref[...], 0.0)
    for i in range(n_hidden - 1):                 # static unroll: weights in VMEM
        h = jnp.maximum(h @ w_hid_ref[i], 0.0)
    out_ref[...] = h @ w_out_ref[...]


def _bwd_kernel(x_ref, w_in_ref, w_hid_ref, w_out_ref, g_ref,
                dx_ref, dw_in_ref, dw_hid_ref, dw_out_ref, *, n_hidden):
    """Recompute activations, then backprop; accumulate dW across grid steps."""
    first = pl.program_id(0) == 0

    @pl.when(first)
    def _init():
        dw_in_ref[...] = jnp.zeros_like(dw_in_ref)
        dw_hid_ref[...] = jnp.zeros_like(dw_hid_ref)
        dw_out_ref[...] = jnp.zeros_like(dw_out_ref)

    x = x_ref[...]
    acts = [jnp.maximum(x @ w_in_ref[...], 0.0)]
    for i in range(n_hidden - 1):
        acts.append(jnp.maximum(acts[-1] @ w_hid_ref[i], 0.0))

    g = g_ref[...]                                        # (BN, D_out)
    dw_out_ref[...] += acts[-1].T @ g
    d = g @ w_out_ref[...].T
    for i in range(n_hidden - 2, -1, -1):
        d = d * (acts[i + 1] > 0)
        dw_hid_ref[i] += acts[i].T @ d
        d = d @ w_hid_ref[i].T
    d = d * (acts[0] > 0)
    dw_in_ref[...] += x.T @ d
    dx_ref[...] = d @ w_in_ref[...].T


def _pad(x, bn):
    n = x.shape[0]
    return jnp.pad(x, ((0, (-n) % bn), (0, 0))), n


@functools.partial(jax.jit, static_argnames=("interpret", "n_hidden"))
def fused_mlp_fwd_pallas(x, w_in, w_hid, w_out, *, n_hidden: int,
                         interpret: bool):
    """x (N,D_in); w_in (D_in,W); w_hid (>=1,W,W); w_out (W,D_out) -> (N,D_out)."""
    xp, n = _pad(x, BLOCK_N)
    grid = (xp.shape[0] // BLOCK_N,)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, n_hidden=n_hidden),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_N, x.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec(w_in.shape, lambda i: (0, 0)),
            pl.BlockSpec(w_hid.shape, lambda i: (0, 0, 0)),
            pl.BlockSpec(w_out.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_N, w_out.shape[1]), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], w_out.shape[1]), x.dtype),
        interpret=interpret,
    )(xp, w_in, w_hid, w_out)
    return out[:n]


@functools.partial(jax.jit, static_argnames=("interpret", "n_hidden"))
def fused_mlp_bwd_pallas(x, w_in, w_hid, w_out, g, *, n_hidden: int,
                         interpret: bool):
    xp, n = _pad(x, BLOCK_N)
    gp, _ = _pad(g, BLOCK_N)
    grid = (xp.shape[0] // BLOCK_N,)
    dx, dw_in, dw_hid, dw_out = pl.pallas_call(
        functools.partial(_bwd_kernel, n_hidden=n_hidden),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_N, x.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec(w_in.shape, lambda i: (0, 0)),
            pl.BlockSpec(w_hid.shape, lambda i: (0, 0, 0)),
            pl.BlockSpec(w_out.shape, lambda i: (0, 0)),
            pl.BlockSpec((BLOCK_N, g.shape[1]), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_N, x.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec(w_in.shape, lambda i: (0, 0)),
            pl.BlockSpec(w_hid.shape, lambda i: (0, 0, 0)),
            pl.BlockSpec(w_out.shape, lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((xp.shape[0], x.shape[1]), x.dtype),
            jax.ShapeDtypeStruct(w_in.shape, x.dtype),
            jax.ShapeDtypeStruct(w_hid.shape, x.dtype),
            jax.ShapeDtypeStruct(w_out.shape, x.dtype),
        ],
        interpret=interpret,
    )(xp, w_in, w_hid, w_out, gp)
    return dx[:n], dw_in, dw_hid, dw_out
