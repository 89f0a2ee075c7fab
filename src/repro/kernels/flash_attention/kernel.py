"""Pallas TPU flash attention (GQA, causal, sliding window).

TPU adaptation of the FlashAttention online-softmax schedule:

- grid = (B*Hq, Sq/BLOCK_Q, Sk/BLOCK_K); the KV dimension is innermost and
  marked "arbitrary" (sequential), so VMEM scratch carries the running
  max / denominator / accumulator across KV steps for one Q tile.
- Q tile (BLOCK_Q, dh), K/V tiles (BLOCK_K, dh) live in VMEM; the (BQ, BK)
  score tile exists ONLY in VMEM/VREGs — the S x S matrix never touches HBM,
  which is precisely the memory-roofline term the dry-run analysis charges to
  the XLA path (EXPERIMENTS.md §Perf).
- GQA is handled in the index maps: q head h reads kv head h // (Hq/Hkv).
- Causal/window masks are computed from block offsets; fully-masked KV tiles
  still iterate (TPU grids cannot skip) but `pl.when` skips their FLOPs.

Layouts: q (B,Hq,Sq,dh), k/v (B,Hkv,Sk,dh) — ops.py transposes from the
model-layer (B,S,H,dh) layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_Q = 256
BLOCK_K = 256



def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, window, sq: int, sk: int, dh: int, n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = (sk - sq) + qi * BLOCK_Q + jax.lax.broadcasted_iota(
        jnp.int32, (BLOCK_Q, BLOCK_K), 0)
    k_pos = ki * BLOCK_K + jax.lax.broadcasted_iota(
        jnp.int32, (BLOCK_Q, BLOCK_K), 1)

    # tile-level skip: any work in this (q,k) tile?
    lo_q = (sk - sq) + qi * BLOCK_Q                       # first q position
    hi_q = lo_q + BLOCK_Q - 1
    lo_k = ki * BLOCK_K
    live = jnp.bool_(True)
    if causal:
        live &= lo_k <= hi_q
    if window is not None:
        live &= (lo_k + BLOCK_K - 1) > (lo_q - window)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)               # (BQ, dh)
        k = k_ref[0, 0].astype(jnp.float32)               # (BK, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s / jnp.sqrt(jnp.float32(dh))
        mask = k_pos < sk
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                               # (BQ, 1)
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)                            # (BQ, BK)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        v_blk = v_ref[0, 0].astype(jnp.float32)           # (BK, dh)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_cur

    @pl.when(ki == n_k - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, window=None,
                         interpret: bool):
    """q (B,Hq,Sq,dh); k,v (B,Hkv,Sk,dh) -> (B,Hq,Sq,dh)."""
    B, Hq, Sq, dh = q.shape
    _, Hkv, Sk, _ = k.shape
    g = Hq // Hkv
    pad_q = (-Sq) % BLOCK_Q
    pad_k = (-Sk) % BLOCK_K
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Sq_p, Sk_p = Sq + pad_q, Sk + pad_k
    n_q, n_k = Sq_p // BLOCK_Q, Sk_p // BLOCK_K

    kernel = functools.partial(_flash_kernel, causal=causal, window=window,
                               sq=Sq, sk=Sk, dh=dh, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, BLOCK_Q, dh),
                         lambda bh, qi, ki: (bh // Hq, bh % Hq, qi, 0)),
            pl.BlockSpec((1, 1, BLOCK_K, dh),
                         lambda bh, qi, ki: (bh // Hq, (bh % Hq) // g, ki, 0)),
            pl.BlockSpec((1, 1, BLOCK_K, dh),
                         lambda bh, qi, ki: (bh // Hq, (bh % Hq) // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, BLOCK_Q, dh),
                               lambda bh, qi, ki: (bh // Hq, bh % Hq, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq_p, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((BLOCK_Q, 1), jnp.float32),
            pltpu.VMEM((BLOCK_Q, 1), jnp.float32),
            pltpu.VMEM((BLOCK_Q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq]
