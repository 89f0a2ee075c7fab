"""DVNR training system (paper §III): per-partition INRs, zero-communication
model parallelism, adaptive parameters, boundary loss, convergence masking.

- ``adaptive_config`` / ``train_iterations``: §III-B scaling rules.
- ``DVNRTrainer``: trains P partition models as one stacked pytree. On a mesh,
  the stacked axis is sharded over ALL mesh axes via shard_map — the per-device
  program contains NO collectives (asserted by tests/test_dvnr_zero_comm.py and
  the DVNR dry-run cell).
- per-partition early stopping is realized as convergence *masking* (SPMD ranks
  stay in lockstep; converged partitions freeze their weights).
- the hot path is device-resident: :meth:`DVNRTrainer.train_chunk` rolls many
  SPMD steps into one ``jax.lax.scan`` under a single ``jax.jit`` (donated
  params/opt carry, per-step keys derived on device, loss trace accumulated on
  device). Convergence is only *checked* on the host at chunk boundaries
  (``check_every``), so a run may overshoot convergence by at most one chunk —
  converged partitions stay frozen inside the chunk, so results are unchanged.
- mixed precision (``DVNRConfig.precision``, see :mod:`repro.precision`):
  under the ``"bf16"`` policy the scan carry holds bf16 params/activations
  while AdamW keeps f32 master params and moments and the L1 loss is reduced
  in f32; coordinates and the loss trace stay f32.
- fused train step (``DVNRConfig.fuse_train_step``, see
  :mod:`repro.kernels.fused_train_step`): when the backend advertises the
  ``fused_train_step`` capability (default ``"auto"`` = all built-ins), the
  loss/grad/AdamW section of the SPMD step runs as ONE op — the ref
  composition on jnp/fused backends, a single Pallas kernel (fwd +
  hand-derived bwd + gated AdamW, partition axis as a grid dimension) on
  pallas backends. ``"off"`` keeps the unfused value_and_grad step, which
  remains the parity baseline (tests/test_fused_train_step.py).
- in-op batch sampling (``DVNRConfig.fuse_sampling``): with the fused step
  enabled, the coordinate draws + trilinear target gather move inside the
  fused op too (in-kernel on pallas backends) — the whole scan body is one
  op and no coords/targets/RNG keys materialize in HBM. Sampling is
  COUNTER-BASED on every path (:mod:`repro.core.sampling`): per-step seeds
  are ``step_seeds(key, step, p)`` and the draws are a pure function of
  ``(seed, sample row)``, so unfused, fused and fused-with-sampling trainers
  see bit-identical batches for the same ``(key, step, partition)``
  (tests/test_fused_sampling.py). ``DVNRConfig.sampling_brick`` picks the
  kernel's volume layout on pallas backends: VMEM-pinned when the partition
  fits the budget, HBM-resident with bricks streamed through a
  double-buffered VMEM block otherwise (production 256^3 partitions) — the
  trainer rejects at build time only configs neither layout can fit.
"""
from __future__ import annotations

import math
from collections import OrderedDict
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import backends, tracing
from repro.configs.dvnr import DVNRConfig
from repro.core.inr import _decode_grid, _inr_apply, init_inr
from repro.core.metrics import psnr_from_mses
from repro.core.sampling import step_seeds, training_coords_counter
from repro.data.volume import sample_trilinear
from repro.kernels.fused_train_step.ops import (fused_train_step,
                                                fused_train_step_sampling)
from repro.optim.adamw import AdamW, OptConfig
from repro.precision import Precision, resolve_precision


# --------------------------------------------------------------------------- #
# III-B: adaptive parameters
# --------------------------------------------------------------------------- #
def train_iterations(cfg: DVNRConfig, nvox: int) -> int:
    """N_train^max = max(N_train^min, ceil(Nvox/Nbatch) * Nepoch)."""
    return max(cfg.n_train_min, math.ceil(nvox / cfg.batch_size) * cfg.epochs)


def adaptive_config(cfg: DVNRConfig, nvox_local: int, nvox_global: int) -> DVNRConfig:
    """T = max(Tmin, Tref * ceil(Nvox/Nvox_global)); R0 = floor(Rref * cbrt(T/Tref)).

    Under strong scaling this keeps total model size (and compression ratio)
    roughly constant as the partition count grows.
    """
    t_ref = cfg.table_size
    frac = nvox_local / max(nvox_global, 1)
    t = max(1 << cfg.t_min_log2, int(2 ** round(math.log2(max(t_ref * frac, 1)))))
    r_ref = cfg.resolved_base_resolution
    r0 = max(2, int(r_ref * (t / t_ref) ** (1.0 / 3.0)))
    return cfg.replace(log2_hashmap_size=int(round(math.log2(t))), base_resolution=r0)


def _opt_config(cfg: DVNRConfig, prec: Precision) -> OptConfig:
    return OptConfig(
        lr=cfg.lrate,
        beta1=cfg.adam_beta1, beta2=cfg.adam_beta2, eps=cfg.adam_eps,
        weight_decay=cfg.weight_decay,
        schedule="exp" if cfg.lrate_decay > 0 else "constant",
        decay_rate=0.33, decay_steps=max(cfg.lrate_decay, 1),
        clip_norm=0.0,
        master_dtype=prec.master_dtype if prec.needs_master else "",
    )


# --------------------------------------------------------------------------- #
# Trainer
# --------------------------------------------------------------------------- #
@dataclass
class DVNRState:
    params: dict          # stacked (P, ...) INR params
    opt: dict             # stacked Adam state
    loss_ma: jnp.ndarray  # (P,) moving-average loss
    active: jnp.ndarray   # (P,) convergence mask
    step: int = 0
    # (P,) bool non-finite detector output of the last chunk (None before any
    # chunk ran, or with cfg.guard_nonfinite=False). False means the partition
    # saw a NaN/Inf loss while active, or holds NaN/Inf params — the signal
    # RecoveryPolicy (repro.resilience) acts on.
    finite: Optional[jnp.ndarray] = None


class DVNRTrainer:
    def __init__(self, cfg: DVNRConfig, n_partitions: int, *, mesh=None,
                 impl: backends.BackendLike = "ref", ghost: int = 1,
                 volume_shape=None):
        """``volume_shape`` (optional): the ghost-padded per-partition volume
        shape (nx+2g, ny+2g, nz+2g[, C]) this trainer will be fed. Declaring
        it up front lets build time reject configs that could not run: the
        VMEM budget of the volume-pinned sampling kernel is checked
        immediately (always — a 256^3 partition with in-op sampling on a
        pallas backend fails HERE with the per-buffer breakdown, not at
        Mosaic compile time on the TPU), and ``cfg.static_checks`` =
        "warn"/"error" additionally traces the chunk program and runs the
        jaxpr-level checks of :mod:`repro.analysis` over it."""
        self.cfg = cfg
        self.P = n_partitions
        self.mesh = mesh
        self.backend = backends.resolve(impl)
        self.ghost = ghost
        self.volume_shape = (tuple(int(d) for d in volume_shape)
                             if volume_shape is not None else None)
        if cfg.static_checks not in ("off", "warn", "error"):
            raise ValueError(f"static_checks must be 'off', 'warn' or "
                             f"'error', got {cfg.static_checks!r}")
        self.precision = resolve_precision(cfg.precision)
        self.backend.require_dtype(self.precision.param_dtype, "param")
        self.backend.require_dtype(self.precision.compute_dtype, "compute")
        # None = full-f32 policy: skip the (noop) casts entirely so the traced
        # program is unchanged from the pre-precision stack
        self._compute_dtype = (None if self.precision == resolve_precision("f32")
                               else self.precision.compute_dtype)
        self.adam = AdamW(_opt_config(cfg, self.precision))
        self.fuse_train_step = self._resolve_fuse(cfg.fuse_train_step)
        self.fuse_sampling = self._resolve_fuse_sampling(cfg.fuse_sampling)
        if not isinstance(cfg.sampling_brick, (int, str)) \
                or (isinstance(cfg.sampling_brick, str)
                    and cfg.sampling_brick not in ("auto", "pinned")) \
                or (isinstance(cfg.sampling_brick, int)
                    and cfg.sampling_brick < 0):
            raise ValueError("sampling_brick must be 'auto', 'pinned' or an "
                             f"int brick edge, got {cfg.sampling_brick!r}")
        if (self.fuse_sampling and self.backend.is_pallas
                and self.volume_shape is not None):
            # resolves pinned-vs-brick-tiled and rejects configs whose
            # resolved sampling layout cannot fit the VMEM budget
            from repro.kernels.fused_train_step.ops import ensure_sampling_fits
            ensure_sampling_fits(self.volume_shape, self.backend, cfg=cfg,
                                 param_dtype=self.precision.param_dtype,
                                 has_master=self.precision.needs_master,
                                 P=self.P)
        self._spmd_step = self._build_spmd_step()
        self._step_fn = jax.jit(self._spmd_step, donate_argnums=(0, 1))
        # n_steps -> jitted scan-fused chunk; LRU-bounded so a long-lived
        # trainer fed varying step counts can't hoard compiled executables
        self._chunk_fns: "OrderedDict[int, object]" = OrderedDict()
        self._chunk_fns_max = 8
        if cfg.static_checks != "off":
            self.run_static_checks(strict=cfg.static_checks == "error")

    @property
    def impl(self) -> str:
        """Backward-compat name of the resolved backend."""
        return self.backend.name

    def _resolve_fuse(self, mode: str) -> bool:
        """``cfg.fuse_train_step`` ("auto"/"on"/"off") -> use the fused step?"""
        if mode not in ("auto", "on", "off"):
            raise ValueError(f"fuse_train_step must be 'auto', 'on' or 'off', "
                             f"got {mode!r}")
        advertised = bool(self.backend.fused_train_step)
        if mode == "on" and not advertised:
            raise ValueError(f"fuse_train_step='on' but backend "
                             f"{self.backend.name!r} does not implement it")
        return mode != "off" and advertised

    def _resolve_fuse_sampling(self, mode: str) -> bool:
        """``cfg.fuse_sampling`` ("auto"/"on"/"off") -> sample inside the
        fused op? Requires the fused step itself (auto degrades, "on"
        errors)."""
        if mode not in ("auto", "on", "off"):
            raise ValueError(f"fuse_sampling must be 'auto', 'on' or 'off', "
                             f"got {mode!r}")
        advertised = self.backend.supports("fused_sampling")
        if mode == "on":
            if not advertised:
                raise ValueError(f"fuse_sampling='on' but backend "
                                 f"{self.backend.name!r} does not implement "
                                 "it")
            if not self.fuse_train_step:
                raise ValueError("fuse_sampling='on' requires the fused train "
                                 "step (fuse_train_step resolved off)")
            return True
        return mode == "auto" and advertised and self.fuse_train_step

    @staticmethod
    def master_params(state: "DVNRState"):
        """Highest-precision view of the trained params: the f32 AdamW master
        when the policy keeps one, else the working params. This is what
        warm-start caches (§III-E weight caching) should store — re-seeding
        from the bf16 working copy would round the trajectory once per tick."""
        if isinstance(state.opt, dict) and "mw" in state.opt:
            return state.opt["mw"]
        return state.params

    # -------------------------- init ---------------------------------- #
    def init(self, key, cached_params: Optional[dict] = None) -> DVNRState:
        """Random init, or warm-start from cached weights (§III-E weight caching).

        Params are carried in the policy's ``param_dtype`` (bf16 under the
        mixed policy); AdamW's ``init`` adds the f32 master copy to the
        optimizer state when the params are narrower."""
        pdt = self.precision.param_jnp
        if cached_params is not None:
            # defensive copy (cast to the policy dtype on the way): the step fn
            # donates its params buffers, which must not invalidate the
            # caller's cached copy (temporal windows)
            params = jax.tree.map(lambda x: jnp.array(x, pdt, copy=True),
                                  cached_params)
        else:
            keys = jax.random.split(key, self.P)
            params = jax.vmap(lambda k: init_inr(self.cfg, k))(keys)
            if pdt != jnp.float32:
                params = jax.tree.map(lambda t: t.astype(pdt), params)
        opt = jax.vmap(self.adam.init)(params)
        if cached_params is not None and "mw" in opt:
            # seed the f32 master straight from the cache, NOT from the
            # bf16-rounded working copy adam.init derived — a warm start from
            # a full-precision cache (see :meth:`master_params`) must not
            # re-introduce one tick of bf16 rounding into the trajectory
            wdt = jnp.dtype(self.adam.cfg.master_dtype)
            opt["mw"] = jax.tree.map(lambda x: jnp.array(x, wdt, copy=True),
                                     cached_params)
        return DVNRState(params, opt,
                         jnp.full((self.P,), jnp.inf, jnp.float32),
                         jnp.ones((self.P,), bool), 0)

    # -------------------------- one SPMD step -------------------------- #
    def _build_spmd_step(self, adam: Optional[AdamW] = None):
        """The per-step SPMD body: ``(params, opt, vols, seeds, active,
        loss_ma) -> (params, opt, loss, loss_ma, active)``. ``seeds`` is the
        (P, 2) uint32 counter-seed table from
        :func:`repro.core.sampling.step_seeds` — every path (unfused, fused,
        fused-with-in-op-sampling) draws the same batch from it. ``adam``
        overrides the trainer's optimizer (lr-backoff retries from
        :mod:`repro.resilience` rebuild the step with a scaled lr)."""
        cfg, ghost, backend = self.cfg, self.ghost, self.backend
        adam = self.adam if adam is None else adam
        compute_dtype = self._compute_dtype

        @jax.named_scope(tracing.SAMPLE)
        def sample_batch(vol, seed):
            coords = training_coords_counter(seed, cfg.batch_size,
                                             cfg.boundary_lambda,
                                             cfg.boundary_sigma)
            target = sample_trilinear(vol, coords, ghost)
            if cfg.out_dim == 1 and target.ndim == 1:
                target = target[:, None]
            return coords, target

        def mask_convergence(loss, loss_ma, active):
            loss_ma = jnp.where(jnp.isinf(loss_ma), loss,
                                0.95 * loss_ma + 0.05 * loss)
            if cfg.target_loss > 0:
                active = active & (loss_ma > cfg.target_loss)
            return loss_ma, active

        if self.fuse_train_step and self.fuse_sampling:
            # fully fused op: sampling + fwd + bwd + AdamW inside
            # fused_train_step_sampling — the volume is an op operand and the
            # scan body is ONE op (in-kernel sampling on pallas backends)
            resolutions = cfg.level_resolutions()
            opt_cfg = adam.cfg

            def base_step(params, opt, vols, seeds, active, loss_ma):
                # scalar volumes gain an explicit channel axis so the op's
                # target layout matches out_dim (local reshape, shard-safe)
                vols_c = vols if vols.ndim == 5 else vols[..., None]
                params, opt, loss = fused_train_step_sampling(
                    params, opt, vols_c, seeds,
                    active.astype(jnp.float32),
                    n_batch=cfg.batch_size,
                    boundary_lambda=cfg.boundary_lambda,
                    sigma=cfg.boundary_sigma, ghost=ghost,
                    resolutions=resolutions, opt_cfg=opt_cfg, impl=backend,
                    compute_dtype=compute_dtype,
                    sampling_brick=cfg.sampling_brick)
                loss_ma, active = mask_convergence(loss, loss_ma, active)
                return params, opt, loss, loss_ma, active
        elif self.fuse_train_step:
            # fused whole-step op (repro.kernels.fused_train_step): sampling is
            # vmapped on the host side, then the stacked state goes through ONE
            # op — the ref composition on jnp/fused backends, a single Pallas
            # kernel (with the partition axis as a grid dimension) on pallas
            # backends
            resolutions = cfg.level_resolutions()
            opt_cfg = adam.cfg

            def base_step(params, opt, vols, seeds, active, loss_ma):
                coords, target = jax.vmap(sample_batch)(vols, seeds)
                params, opt, loss = fused_train_step(
                    params, opt, coords, target,
                    active.astype(jnp.float32), resolutions=resolutions,
                    opt_cfg=opt_cfg, impl=backend,
                    compute_dtype=compute_dtype)
                loss_ma, active = mask_convergence(loss, loss_ma, active)
                return params, opt, loss, loss_ma, active
        else:
            # unfused fallback (and the fused path's parity baseline):
            # value_and_grad of the per-partition loss + AdamW, vmapped
            def one_partition(params, opt, vol, seed, active, loss_ma):
                coords, target = sample_batch(vol, seed)

                def loss_fn(p):
                    # forward in the policy's compute dtype; the L1 reduction
                    # is always f32 (bf16 params promote vs the f32 target)
                    pred = _inr_apply(cfg, p, coords, backend,
                                      compute_dtype=compute_dtype)
                    return jnp.mean(jnp.abs(pred.astype(jnp.float32) - target))

                loss, grads = jax.value_and_grad(loss_fn)(params)
                # master-weight AdamW step (f32 moments + master when params
                # are bf16); converged partitions are frozen via the gate
                gate = active.astype(jnp.float32)
                params, opt = adam.step(grads, opt, params, gate)
                loss_ma, active = mask_convergence(loss, loss_ma, active)
                return params, opt, loss, loss_ma, active

            base_step = jax.vmap(one_partition)

        spmd_step = base_step

        if self.mesh is not None:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            axes = tuple(self.mesh.axis_names)
            part = P(axes)
            specs_stacked = P(axes)

            def spec_like(tree):
                return jax.tree.map(lambda _: specs_stacked, tree,
                                    is_leaf=lambda x: hasattr(x, "ndim"))

            def sharded(params, opt, vols, seeds, active, loss_ma):
                return shard_map(
                    base_step, mesh=self.mesh,
                    in_specs=(spec_like(params), spec_like(opt), part, part,
                              part, part),
                    out_specs=(spec_like(params), spec_like(opt), part, part, part),
                    check_vma=False,
                )(params, opt, vols, seeds, active, loss_ma)

            spmd_step = sharded

        return spmd_step

    # -------------------------- scan-fused chunk ------------------------ #
    def _chunk_body(self, n_steps: int, lr_scale: float = 1.0):
        """The unjitted ``n_steps``-long scan of the SPMD step. Exposed
        separately from :meth:`_chunk_fn` so tests can inspect the traced
        program (``jax.make_jaxpr``) — e.g. that with in-op sampling no RNG /
        gather primitives remain outside the fused op.

        With ``cfg.guard_nonfinite`` the chunk also carries a (P,) ``finite``
        flag through the scan (``isfinite(loss) | ~active`` per step — a
        frozen partition's stale NaN loss is not a new failure) and ANDs in a
        per-leaf params isfinite reduction at the chunk boundary. Both
        reductions run over the NON-sharded per-partition axes only, so the
        per-device program stays collective-free (zero_collectives holds).

        ``lr_scale != 1`` rebuilds the SPMD step around an AdamW with
        ``lr * lr_scale`` — the lr-backoff rung of
        :class:`repro.resilience.RecoveryPolicy`."""
        if lr_scale == 1.0:
            spmd_step = self._spmd_step
        else:
            import dataclasses
            adam = AdamW(dataclasses.replace(
                self.adam.cfg, lr=self.adam.cfg.lr * float(lr_scale)))
            spmd_step = self._build_spmd_step(adam)
        P, guard = self.P, self.cfg.guard_nonfinite

        def dvnr_train_chunk(params, opt, vols, key, step0, active, loss_ma):
            def body(carry, i):
                params, opt, active, loss_ma, finite = carry
                seeds = step_seeds(key, step0 + i, P)
                active_in = active
                params, opt, loss, loss_ma, active = spmd_step(
                    params, opt, vols, seeds, active, loss_ma)
                if guard:
                    finite = finite & (jnp.isfinite(loss) | ~active_in)
                return (params, opt, active, loss_ma, finite), loss

            finite0 = jnp.ones((P,), bool)
            (params, opt, active, loss_ma, finite), losses = jax.lax.scan(
                body, (params, opt, active, loss_ma, finite0),
                jnp.arange(n_steps))
            if guard:
                leaf_ok = [jnp.all(jnp.isfinite(x.astype(jnp.float32)),
                                   axis=tuple(range(1, x.ndim)))
                           for x in jax.tree.leaves(params)]
                finite = finite & jnp.stack(leaf_ok).all(axis=0)
            return params, opt, active, loss_ma, finite, losses

        return dvnr_train_chunk

    def _chunk_fn(self, n_steps: int, lr_scale: float = 1.0):
        """Jitted ``n_steps``-long scan of the SPMD step (cached per
        (length, lr_scale))."""
        cache_key = (n_steps, float(lr_scale))
        fn = self._chunk_fns.get(cache_key)
        if fn is not None:
            self._chunk_fns.move_to_end(cache_key)
            return fn
        fn = jax.jit(self._chunk_body(n_steps, lr_scale),
                     donate_argnums=(0, 1))
        self._chunk_fns[cache_key] = fn
        while len(self._chunk_fns) > self._chunk_fns_max:
            self._chunk_fns.popitem(last=False)
        return fn

    # -------------------------- static analysis ------------------------- #
    def abstract_chunk_args(self, n_steps: int = 2):
        """ShapeDtypeStruct pytree of :meth:`_chunk_body` arguments — what the
        static verifier traces instead of real buffers. The volume uses the
        declared ``volume_shape`` when given, else a nominal 8^3 placeholder
        (fine for the precision/RNG checks; pass ``volume_shape`` for real
        VMEM estimates)."""
        g = self.ghost
        vshape = self.volume_shape or (8 + 2 * g,) * 3

        def build():
            st = self.init(jax.random.PRNGKey(0))
            return st.params, st.opt, st.active, st.loss_ma

        params, opt, active, loss_ma = jax.eval_shape(build)
        vols = jax.ShapeDtypeStruct((self.P,) + tuple(vshape), jnp.float32)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        step0 = jax.ShapeDtypeStruct((), jnp.int32)
        return (params, opt, vols, key, step0, active, loss_ma)

    def run_static_checks(self, *, strict: bool = True, n_steps: int = 2):
        """Trace the scan-fused chunk and run the jaxpr-level checks of
        :mod:`repro.analysis` over it (VMEM budget, precision flow, RNG/gather
        placement — no XLA compile). ``strict`` raises
        :class:`repro.analysis.StaticCheckError` on violations; otherwise
        they are issued as a warning. Returns the report."""
        import warnings

        from repro.analysis import (CheckContext, StaticCheckError, capture,
                                    run_checks)

        program = capture(
            self._chunk_body(n_steps), *self.abstract_chunk_args(n_steps),
            name=f"train_chunk[{self.backend.name}]", donate_argnums=(0, 1))
        ctx = CheckContext(
            backend=self.backend, precision=self.precision,
            fuse_sampling=self.fuse_sampling,
            expect_pallas=self.backend.is_pallas and self.fuse_train_step,
            donate_argnums=(0, 1))
        report = run_checks(program, ctx, max_level="jaxpr")
        if not report.passed:
            if strict:
                raise StaticCheckError(report)
            warnings.warn("static checks failed (static_checks='warn'):\n"
                          + report.render(), stacklevel=2)
        return report

    def train_chunk(self, state: DVNRState, volumes, n_steps: int, *,
                    key, lr_scale: float = 1.0) -> tuple[DVNRState, jnp.ndarray]:
        """Run ``n_steps`` training steps as ONE device program (no host round
        trips): a ``jax.lax.scan`` over the SPMD step under a single ``jit``
        with donated params/opt, per-step/per-partition keys derived inside the
        scan, and the (n_steps, P) loss trace accumulated on device.

        Returns the advanced state and the on-device loss trace; nothing is
        transferred to the host until the caller inspects either. The
        ``state.finite`` field carries the non-finite detector output (all
        True when ``cfg.guard_nonfinite`` is off).
        """
        n_steps = int(n_steps)
        params, opt, active, loss_ma, finite, losses = \
            self._chunk_fn(n_steps, lr_scale)(
                *self._chunk_args(state, volumes, key))
        return DVNRState(params, opt, loss_ma, active,
                         state.step + n_steps, finite), losses

    def chunk_program(self, state: DVNRState, volumes, n_steps: int, *, key):
        """The compiled chunk that :meth:`train_chunk` runs on these
        arguments (served from JAX's caches when that call compiled it). Its
        ``as_text()`` names each instruction of a profiler trace and holds
        the instruction's stage (:mod:`repro.tracing`) in its ``op_name``."""
        return self._chunk_fn(int(n_steps)).lower(
            *self._chunk_args(state, volumes, key)).compile()

    @staticmethod
    def _chunk_args(state: DVNRState, volumes, key):
        return (state.params, state.opt, volumes, key, jnp.int32(state.step),
                state.active, state.loss_ma)

    # -------------------------- drivers -------------------------------- #
    def train(self, state: DVNRState, volumes, *, steps: int, key,
              log_every: int = 0, check_every: int = 0,
              recovery=None) -> tuple[DVNRState, dict]:
        """Chunked training driver. volumes: (P, nx+2g, ny+2g, nz+2g)
        pre-normalized partitions.

        ``check_every`` is the chunk size — the granularity of host-side
        convergence checks (and the only device→host syncs in the loop).
        0 picks a default: the whole run as one chunk when early stopping is
        off, else 64-step chunks (at most 63 extra masked steps vs per-step
        checking; masked partitions are frozen, so quality is unaffected).

        ``recovery`` (a :class:`repro.resilience.RecoveryPolicy`) routes the
        run through the non-finite recovery driver: each chunk is snapshotted
        before it runs, partitions whose detector flag trips are retried on a
        reseed → moment-reset → lr-backoff ladder and frozen at their
        last-good params once attempts are exhausted; healthy partitions keep
        their first-attempt results bit-for-bit (zero-comm independence).
        """
        if recovery is not None:
            from repro.resilience.recovery import train_with_recovery
            return train_with_recovery(self, state, volumes, steps=steps,
                                       key=key, log_every=log_every,
                                       check_every=check_every,
                                       policy=recovery)
        if steps <= 0:
            return state, {"loss": [], "final_step": state.step}
        if check_every <= 0:
            check_every = steps if self.cfg.target_loss <= 0 else min(steps, 64)
        losses, done = [], 0
        while done < steps:
            n = min(check_every, steps - done)
            start = state.step
            state, trace = self.train_chunk(state, volumes, n, key=key)
            if log_every:
                mean = np.asarray(trace.mean(axis=1))   # one transfer per chunk
                losses += [(start + i + 1, float(mean[i])) for i in range(n)
                           if (done + i + 1) % log_every == 0]
            done += n
            if self.cfg.target_loss > 0 and not bool(state.active.any()):
                break
        return state, {"loss": losses, "final_step": state.step}

    def train_looped(self, state: DVNRState, volumes, *, steps: int, key,
                     log_every: int = 0) -> tuple[DVNRState, dict]:
        """The pre-chunk per-step driver: one jitted dispatch (plus host key
        derivation and a convergence sync) per step. Kept as the parity
        reference for :meth:`train_chunk` and as the dispatch-overhead
        baseline in ``benchmarks/bench_train_loop.py``.
        """
        losses = []
        for i in range(steps):
            seeds = step_seeds(key, state.step, self.P)
            params, opt, loss, loss_ma, active = self._step_fn(
                state.params, state.opt, volumes, seeds, state.active, state.loss_ma)
            state = DVNRState(params, opt, loss_ma, active, state.step + 1)
            if log_every and (i + 1) % log_every == 0:
                losses.append((state.step, float(loss.mean())))
            if self.cfg.target_loss > 0 and not bool(active.any()):
                break
        return state, {"loss": losses, "final_step": state.step}

    # -------------------------- evaluation ----------------------------- #
    def evaluate(self, state: DVNRState, volumes, owned_shape, *,
                 out_dtype=None) -> dict:
        """Decode every partition (one vmapped program, no per-partition
        Python loop) and compute PSNR vs the normalized reference; the MSE
        reduction stays on device — a single host transfer at the end.

        The decode runs in the trainer's compute dtype (bf16 under the mixed
        policy — evaluation then measures the quality of the reduced-precision
        inference path, which is what ships); ``out_dtype`` overrides the
        decoded-grid dtype (default: the policy's ``output_dtype``). The MSE
        reduction itself is always f32.

        Peak memory is O(P * prod(owned_shape)) for the decoded grids — the
        same order as the stacked ``volumes`` input that is already resident,
        so batching trades a constant factor of memory for P-way batching of
        the decode matmuls."""
        g = self.ghost
        cfg, backend = self.cfg, self.backend
        odt = self.precision.output_dtype if out_dtype is None else out_dtype
        decs = jax.vmap(
            lambda p: _decode_grid(cfg, p, owned_shape, backend,
                                   compute_dtype=self._compute_dtype,
                                   out_dtype=odt))(state.params)
        if decs.ndim == 5:                       # (P, nx, ny, nz, out_dim)
            decs = decs[..., 0]
        refs = jnp.asarray(volumes)[:, g:g + owned_shape[0],
                                    g:g + owned_shape[1], g:g + owned_shape[2]]
        mses = np.asarray(jnp.mean(jnp.square(decs - refs), axis=(1, 2, 3)),
                          np.float64)
        return {"psnr": float(psnr_from_mses(mses)),
                "mse_per_partition": [float(m) for m in mses]}
