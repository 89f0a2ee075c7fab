"""Reference composition of the fused train step.

This is byte-for-byte the math of ``DVNRTrainer``'s unfused step body —
(optionally) the counter-based batch sampler + trilinear target gather,
forward through the backend's own hash-encode + fused-MLP ops, gradients via
``jax.value_and_grad``, update via :meth:`repro.optim.adamw.AdamW.step` —
vmapped over the stacked partition axis. Backends of kind ``jnp``/``fused``
run this as *their* fused-train-step implementation (the fusion they benefit
from is the surrounding ``lax.scan``), and it is the parity oracle the Pallas
kernel is tested against.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro import tracing
from repro.core.sampling import training_coords_counter
from repro.data.volume import sample_trilinear
from repro.kernels.fused_mlp.ops import fused_mlp
from repro.kernels.hash_encoding.ops import hash_encode
from repro.optim.adamw import AdamW


def train_step_ref(params, opt, coords, target, gate,
                   resolutions: Sequence[int], adam: AdamW, backend,
                   compute_dtype=None):
    """One L1 train step for every partition (stacked inputs, no Python loop).

    params/opt: (P, ...)-stacked pytrees; coords (P, N, 3) f32;
    target (P, N, out_dim) f32; gate (P,) f32 convergence mask.
    Returns ``(params, opt, loss)`` with loss (P,) f32.
    """

    def one(params_p, opt_p, coords_p, target_p, gate_p):
        def loss_fn(p):
            feats = hash_encode(coords_p, p["tables"], resolutions, backend,
                                compute_dtype=compute_dtype)
            pred = fused_mlp(feats, p["mlp"], backend,
                             compute_dtype=compute_dtype)
            return jnp.mean(jnp.abs(pred.astype(jnp.float32) - target_p))

        loss, grads = jax.value_and_grad(loss_fn)(params_p)
        params_p, opt_p = adam.step(grads, opt_p, params_p, gate_p)
        return params_p, opt_p, loss

    return jax.vmap(one)(params, opt, coords, target, gate)


def train_step_sampling_ref(params, opt, volumes, seeds, gate,
                            resolutions: Sequence[int], adam: AdamW, backend,
                            *, n_batch: int, boundary_lambda: float,
                            sigma: float, ghost: int, compute_dtype=None):
    """The sampling-included fused step as its ref composition: draw the
    counter-based batch (:func:`repro.core.sampling.training_coords_counter`
    — bit-identical to the in-kernel draws for the same (P, 2) uint32
    ``seeds``), gather trilinear targets from the ghost-padded ``volumes``
    (P, nx+2g, ny+2g, nz+2g[, C]), then run :func:`train_step_ref`. This is
    exactly the unfused trainer step's sampling + loss/grad/Adam body, so
    jnp/fused backends replay the unfused trajectory bit-for-bit. The
    ``sampling_brick`` knob never reaches this path: the draws and the
    gather here are global (HBM-resident), which is precisely why this
    composition anchors the parity tests for BOTH pallas volume layouts
    (pinned and brick-tiled).
    """

    @jax.named_scope(tracing.SAMPLE)
    def sample(vol_p, seed_p):
        coords = training_coords_counter(seed_p, n_batch, boundary_lambda,
                                         sigma)
        target = sample_trilinear(vol_p, coords, ghost)
        if target.ndim == 1:
            target = target[:, None]
        return coords, target

    coords, target = jax.vmap(sample)(volumes, seeds)
    return train_step_ref(params, opt, coords, target, gate, resolutions,
                          adam, backend, compute_dtype)
