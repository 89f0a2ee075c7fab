"""AdamW with exponential / cosine / constant LR schedules and global-norm clipping.

The paper uses Adam with exponential learning-rate decay for DVNR training
(beta1=0.9, beta2=0.999, eps=1e-8, weight decay 1e-9); the LM trainer shares the
implementation. Moment dtypes are configurable: bf16 moments keep the 480B-param
arctic cell within single-pod HBM (see EXPERIMENTS.md §Dry-run).

Mixed precision: when ``OptConfig.master_dtype`` is set and the params are
narrower (bf16 training), ``init`` stores a full-precision master copy in the
optimizer state (``"mw"``); :meth:`AdamW.step` applies every update to the
master and re-derives the working params by casting, so the optimizer
trajectory never accumulates bf16 rounding (standard mixed-precision practice,
cf. Instant-NGP-style INR trainers).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro import tracing


@dataclass(frozen=True)
class OptConfig:
    lr: float = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 1e-9
    schedule: str = "constant"          # constant | exp | cosine
    decay_rate: float = 0.33            # exp: lr *= decay_rate every decay_steps
    decay_steps: int = 1000
    warmup_steps: int = 0
    total_steps: int = 10_000           # cosine horizon
    clip_norm: float = 1.0              # 0 = off
    moments_dtype: str = "float32"      # bf16 halves optimizer HBM (arctic/grok)
    master_dtype: str = ""              # "" = params are their own master;
                                        # "float32" keeps f32 master params
                                        # when the working params are narrower


def make_schedule(cfg: OptConfig):
    def lr(step):
        step = step.astype(jnp.float32)
        base = jnp.asarray(cfg.lr, jnp.float32)
        if cfg.schedule == "exp" and cfg.decay_steps > 0:
            base = base * cfg.decay_rate ** (step / cfg.decay_steps)
        elif cfg.schedule == "cosine":
            frac = jnp.clip(step / max(cfg.total_steps, 1), 0.0, 1.0)
            base = base * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
        if cfg.warmup_steps > 0:
            base = base * jnp.clip((step + 1.0) / cfg.warmup_steps, 0.0, 1.0)
        return base

    return lr


def global_norm(tree) -> jnp.ndarray:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def clip_by_global_norm(tree, max_norm: float):
    if max_norm <= 0:
        return tree, global_norm(tree)
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda x: (x.astype(jnp.float32) * scale).astype(x.dtype), tree), norm


class AdamW:
    """Functional AdamW: ``init(params) -> state``, ``update(grads, state, params)``."""

    def __init__(self, cfg: OptConfig):
        self.cfg = cfg
        self.schedule = make_schedule(cfg)

    def _wants_master(self, params) -> bool:
        if not self.cfg.master_dtype:
            return False
        wdt = jnp.dtype(self.cfg.master_dtype)
        return any(x.dtype != wdt for x in jax.tree.leaves(params))

    def init(self, params):
        mdt = jnp.dtype(self.cfg.moments_dtype)
        zeros = lambda p: jnp.zeros(p.shape, mdt)
        state = {
            "step": jnp.zeros((), jnp.int32),
            "m": jax.tree.map(zeros, params),
            "v": jax.tree.map(zeros, params),
        }
        if self._wants_master(params):
            wdt = jnp.dtype(self.cfg.master_dtype)
            state["mw"] = jax.tree.map(lambda p: p.astype(wdt), params)
        return state

    def update(self, grads, state, params):
        cfg = self.cfg
        step = state["step"] + 1
        lr = self.schedule(step)
        b1, b2 = cfg.beta1, cfg.beta2
        bc1 = 1.0 - b1 ** step.astype(jnp.float32)
        bc2 = 1.0 - b2 ** step.astype(jnp.float32)
        mdt = jnp.dtype(cfg.moments_dtype)

        def upd(g, m, v, p):
            g32 = g.astype(jnp.float32)
            m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g32
            v32 = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(g32)
            mhat = m32 / bc1
            vhat = v32 / bc2
            delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
            if cfg.weight_decay:
                delta = delta + cfg.weight_decay * p.astype(jnp.float32)
            return (-lr * delta).astype(p.dtype), m32.astype(mdt), v32.astype(mdt)

        out = jax.tree.map(upd, grads, state["m"], state["v"], params)
        updates = jax.tree.map(lambda t: t[0], out, is_leaf=lambda t: isinstance(t, tuple))
        m = jax.tree.map(lambda t: t[1], out, is_leaf=lambda t: isinstance(t, tuple))
        v = jax.tree.map(lambda t: t[2], out, is_leaf=lambda t: isinstance(t, tuple))
        return updates, {**state, "step": step, "m": m, "v": v}

    def step(self, grads, state, params, gate=None):
        """One full optimizer step -> (new_params, new_state).

        The master-weight path: moments and the delta are computed in f32
        against the master copy in ``state["mw"]`` (when present), the
        (optionally ``gate``-masked, for convergence freezing) update is
        applied to the master, and the working params are re-derived by
        casting — bf16 rounding never feeds back into the trajectory. Without
        a master this is exactly ``params + gate * update``.
        """
        with jax.named_scope(tracing.ADAM):
            master = state.get("mw", params)
            updates, state = self.update(grads, state, master)
            if gate is None:
                apply = lambda p, u: p + u
            else:
                apply = lambda p, u: p + (gate * u).astype(p.dtype)
            master = jax.tree.map(apply, master, updates)
            if "mw" in state:
                state = {**state, "mw": master}
                params = jax.tree.map(lambda w, p: w.astype(p.dtype), master,
                                      params)
            else:
                params = master
            return params, state

    @staticmethod
    def apply_updates(params, updates):
        return jax.tree.map(lambda p, u: p + u.astype(p.dtype), params, updates)
