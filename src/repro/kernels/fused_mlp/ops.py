"""jit'd wrapper for the fused MLP with custom VJP (fwd + bwd kernels).

Dispatch goes through :mod:`repro.backends`: Pallas backends run the fused
kernels (interpret or compiled); everything else uses the jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import backends, tracing
from repro.kernels.fused_mlp import ref as _ref
from repro.kernels.fused_mlp.kernel import fused_mlp_bwd_pallas, fused_mlp_fwd_pallas


def _stack(weights):
    """[w_in, h1..h_{H-1}, w_out] -> (w_in, (max(H-1,1),W,W), w_out, n_hidden).

    An all-zero dummy hidden slab keeps BlockSpecs non-empty when H == 1; the
    kernel's static layer unroll (n_hidden) never touches it.
    """
    w_in, *hid, w_out = weights
    n_hidden = len(hid) + 1
    w_hid = jnp.stack(hid) if hid else jnp.zeros((1, w_in.shape[1], w_in.shape[1]),
                                                 w_in.dtype)
    return w_in, w_hid, w_out, n_hidden


def fused_mlp(x, weights, impl: backends.BackendLike = "ref", *,
              compute_dtype=None):
    """x (N, D_in); weights [w_in, hidden..., w_out] -> (N, D_out).

    The output carries the input/weight dtype — both the jnp oracle and the
    Pallas kernels run bf16 inputs without upcasting. ``compute_dtype`` casts
    activations and weights before the matmul stack (differentiable casts)."""
    backend = backends.resolve(impl)
    with jax.named_scope(tracing.MLP):
        if compute_dtype is not None:
            dt = backend.require_dtype(compute_dtype)
            x = x.astype(dt)
            weights = [w.astype(dt) for w in weights]
        return _fused_mlp(x, weights, backend)


def vmem_footprint(x, weights, impl: backends.BackendLike = "pallas"):
    """Static VMEM bill of the forward MLP: one
    :class:`repro.analysis.vmem.KernelFootprint` per ``pallas_call`` the op
    would emit for these operand shapes (empty on jnp backends). ``x`` /
    ``weights`` may be ``jax.ShapeDtypeStruct``s — nothing executes."""
    from repro.analysis.vmem import footprint_of

    backend = backends.resolve(impl)
    return footprint_of(lambda xx, *ww: _fwd_impl(xx, list(ww), backend),
                        x, *weights)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _fused_mlp(x, weights, backend: backends.Backend):
    return _fwd_impl(x, weights, backend)


def _fwd_impl(x, weights, backend):
    if backend.is_pallas:
        w_in, w_hid, w_out, n_hidden = _stack(weights)
        return fused_mlp_fwd_pallas(x, w_in, w_hid, w_out, n_hidden=n_hidden,
                                    interpret=backend.interpret)
    return _ref.fused_mlp_ref(x, weights)


def _fwd(x, weights, backend):
    return _fwd_impl(x, weights, backend), (x, weights)


def _bwd(backend, res, g):
    x, weights = res
    if backend.is_pallas:
        w_in, w_hid, w_out, n_hidden = _stack(weights)
        dx, dw_in, dw_hid, dw_out = fused_mlp_bwd_pallas(
            x, w_in, w_hid, w_out, g, n_hidden=n_hidden,
            interpret=backend.interpret)
        dws = [dw_in] + [dw_hid[i] for i in range(n_hidden - 1)] + [dw_out]
        return dx, dws
    _, vjp = jax.vjp(lambda xx, ww: _ref.fused_mlp_ref(xx, ww), x, weights)
    return vjp(g)


_fused_mlp.defvjp(_fwd, _bwd)


# --------------------------------------------------------------------------- #
# Grid-access contract (repro.analysis grid_write_safety / hbm_traffic)
# --------------------------------------------------------------------------- #
from repro.analysis.grid import register_discipline  # noqa: E402

register_discipline(
    "_fwd_kernel",
    note="weights VMEM-pinned (trivial window); x/out stream single-pass")
register_discipline(
    "_bwd_kernel",
    # dW outputs are whole-array pinned blocks accumulated (`+=`) across the
    # batch-tile grid, zero-initialized at pl.when(first) — the sequential
    # TPU grid makes the accumulation safe (the MXU-friendly atomicAdd)
    multi_write={"out[1]": "accumulate", "out[2]": "accumulate",
                 "out[3]": "accumulate"},
    note="dW pinned accumulators across batch tiles; dx streams per tile")
