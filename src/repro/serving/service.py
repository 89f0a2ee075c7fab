"""Batched render service: many concurrent clients, one jitted batch per tick.

The paper's reactive story (§IV) ends with many viewers exploring the same
compressed simulation state. This module serves that workload:

- clients :meth:`RenderService.submit` :class:`repro.api.RenderRequest`\\ s
  (camera, transfer function, LOD, timestep) and get a ticket back;
- each :meth:`RenderService.tick` coalesces every pending request into
  batches grouped by shape-static fields (width/height/fov/samples/LOD/
  timestep/compute dtypes), renders each batch as ONE jitted program mapped
  over the per-client camera + transfer-function arrays, and streams
  :class:`RenderResponse`\\ s back;
- value samples come from the :class:`~repro.serving.cache.BrickCache` (warm
  bricks are reused across frames and clients), and requests for historical
  ``timestep``\\ s decode weights out of a
  :class:`~repro.core.temporal.TemporalModelCache` with a small warm-model
  LRU in front.
"""
from __future__ import annotations

import functools
import time
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import backends
from repro.core.render import (_render_distributed, _render_distributed_sampled,
                               rays_from_arrays)
from repro.serving.cache import BrickCache


def batched_frame_program(cfg, *, fov: float, width: int, height: int,
                          n_samples: int, density: float,
                          compute_dtype=None, out_dtype=None,
                          backend=None, cached: bool = True,
                          view_geom=None):
    """The one-tick frame program: one frame per client, mapped over the
    per-client camera (eye/center/up) and transfer-function arrays, sharing
    the pool/slot-map/meta/param operands. Frames run one after another
    inside the program (``jax.lax.map``): each frame is already chunked into
    passes large enough to fill the chip, so the tick's memory stays one
    frame's whatever its batch (a vmap over clients multiplied it, and the
    v5e compile of a vmapped cached frame took minutes).

    ``cached=True`` samples the :class:`BrickCache` pool (``view_geom`` =
    ``(grid_shape, brick_edge)`` of the cache view; ``params`` unused);
    ``cached=False`` renders through direct INR inference (``pool``/``slots``
    unused). Module-level (not a service method) so ``repro.analysis`` can
    capture the exact serving-tick program the service jits
    (:func:`repro.analysis.programs.serving_tick_program`)."""
    def one_frame(eye, center, up, tf_table, pool, slots, metas, grange,
                  params):
        rays = rays_from_arrays(eye, center, up, fov, width, height)
        if cached:
            grid_shape, brick_edge = view_geom
            return _render_distributed_sampled(
                pool, slots, grid_shape, brick_edge, metas,
                None, width, height, grange, n_samples=n_samples,
                impl=backend, tf_table=tf_table, density=density,
                compute_dtype=compute_dtype, out_dtype=out_dtype, rays=rays)
        return _render_distributed(
            cfg, params, None, None, width, height, grange,
            n_samples=n_samples, impl=backend, tf_table=tf_table,
            density=density, compute_dtype=compute_dtype,
            out_dtype=out_dtype, metas=metas, rays=rays)

    def frames(eyes, centers, ups, tf_tables, pool, slots, metas, grange,
               params):
        return jax.lax.map(
            lambda c: one_frame(*c, pool, slots, metas, grange, params),
            (eyes, centers, ups, tf_tables))

    return frames


@functools.lru_cache(maxsize=64)
def frame_program(cfg, *, fov: float, width: int, height: int,
                  n_samples: int, density: float, compute_dtype=None,
                  out_dtype=None, backend=None, view_geom=None):
    """The jitted :func:`batched_frame_program` of one static configuration,
    memoized so every tick of a shape group, and every ``api.render`` call
    with the same shape, reuses one compiled program. ``view_geom`` given ->
    the cached (brick-pool) program, ``None`` -> direct INR inference."""
    return jax.jit(batched_frame_program(
        cfg, fov=fov, width=width, height=height, n_samples=n_samples,
        density=density, compute_dtype=compute_dtype, out_dtype=out_dtype,
        backend=backend, cached=view_geom is not None, view_geom=view_geom))


def frame_operands(view, model):
    """The (pool, slots, params) operands of a :func:`frame_program` call:
    the cache view's arrays on the cached program, the model's stacked
    params on the direct one (the unused side gets placeholders)."""
    if view is not None:
        return view.pool, view.slots, None
    return (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32),
            model.stacked_params())


@dataclass(frozen=True, eq=False)
class RenderResponse:
    """One served frame plus enough context to route it back to its client."""

    ticket: int
    request: Any                    # the RenderRequest as submitted
    frame: np.ndarray               # (H, W, 4) f32 (or request.out_dtype)
    timestep: Optional[int]
    tick: int
    batch_size: int                 # how many requests shared this program
    render_ms: float                # wall time of the whole batch


class RenderService:
    """Coalesces concurrent :class:`repro.api.RenderRequest`\\ s into one
    jitted render program per tick, in front of a shared brick cache.

    Construct with either a live ``model`` (a :class:`repro.api.DVNRModel`
    with ``parts_meta``) or a ``temporal`` :class:`TemporalModelCache` plus
    the ``cfg``/``parts_meta`` needed to rebuild models from cached weights;
    both may be given (requests with ``timestep=None`` hit the live model).
    ``use_cache=False`` renders through direct INR inference — the paired
    baseline of the cache speedup benchmark.
    """

    def __init__(self, model=None, *, temporal=None, cfg=None, parts_meta=None,
                 grange=None, cache: Optional[BrickCache] = None,
                 use_cache: bool = True, backend: backends.BackendLike = "auto",
                 cache_kw: Optional[dict] = None, max_warm_models: int = 4):
        from repro import api

        if model is None and temporal is None:
            raise ValueError("RenderService needs a model and/or a temporal "
                             "TemporalModelCache")
        if model is not None and model.parts_meta is None:
            raise ValueError("RenderService model needs parts_meta (train via "
                             "repro.api.train or attach PartitionMeta)")
        self.model = model
        self.temporal = temporal
        self.cfg = model.cfg if model is not None else cfg
        if self.cfg is None:
            raise ValueError("temporal-only RenderService needs cfg=")
        self._parts_meta = (model.parts_meta if model is not None
                            else api._meta_tuple(parts_meta))
        if self._parts_meta is None:
            raise ValueError("temporal-only RenderService needs parts_meta=")
        if grange is None:
            grange = model.grange if model is not None else \
                api._grange_of(self._parts_meta)
        self._grange = grange
        self.backend = backends.resolve(backend)
        self.use_cache = use_cache
        self.cache = cache if cache is not None else \
            BrickCache(self.cfg, backend=self.backend, **(cache_kw or {}))
        self._warm: OrderedDict[int, Any] = OrderedDict()  # ts -> DVNRModel
        self.max_warm_models = max_warm_models
        self._pending: List[tuple] = []                    # (ticket, request)
        self._next_ticket = 0
        self._tick = 0
        self.ticks: List[dict] = []

    # ------------------------------ models ------------------------------ #
    def model_for(self, timestep: Optional[int]):
        """The DVNRModel serving ``timestep`` (None -> the live model).
        Historical timesteps decode out of the temporal cache once and stay
        warm in a small LRU — repeated requests hit warm weights."""
        from repro import api

        if timestep is None:
            if self.model is None:
                raise ValueError("request has timestep=None but the service "
                                 "has no live model")
            return self.model
        ts = int(timestep)
        if ts in self._warm:
            self._warm.move_to_end(ts)
            return self._warm[ts]
        if self.temporal is None:
            if self.model is not None:
                return self.model   # single-model service ignores timestep
            raise KeyError(f"timestep {ts}: no temporal cache attached")
        params = self.temporal.stacked_params(ts)
        m = api.DVNRModel(self.cfg, params, self._parts_meta, self._grange)
        self._warm[ts] = m
        while len(self._warm) > self.max_warm_models:
            self._warm.popitem(last=False)
        return m

    @property
    def warm_timesteps(self) -> list:
        return list(self._warm)

    # ------------------------------ requests ---------------------------- #
    def submit(self, request) -> int:
        """Queue a request; returns the ticket its response will carry."""
        t = self._next_ticket
        self._next_ticket += 1
        self._pending.append((t, request))
        return t

    @property
    def pending(self) -> int:
        return len(self._pending)

    def render(self, request) -> np.ndarray:
        """Convenience single-request path: submit + tick, return the frame."""
        ticket = self.submit(request)
        for resp in self.tick():
            if resp.ticket == ticket:
                return resp.frame
        raise RuntimeError("unreachable: submitted request not in tick")

    # ------------------------------ batching ---------------------------- #
    @staticmethod
    def _group_key(req) -> tuple:
        # everything that fixes array shapes / static jit args; cameras and
        # TF tables vary within a group (mapped over)
        tfk = req.tf.table_shape
        return (req.width, req.height, req.n_samples, req.camera.fov_deg,
                req.lod, req.timestep, tfk, req.tf.density,
                req.compute_dtype, req.out_dtype)

    def _batch_fn(self, key, view):
        """The jitted frame program of one group."""
        (W, H, S, fov, _lod, _ts, _tfk, density, cdt, odt) = key
        return frame_program(
            self.cfg, fov=fov, width=W, height=H, n_samples=S,
            density=density, compute_dtype=cdt, out_dtype=odt,
            backend=self.backend,
            view_geom=(None if view is None
                       else (view.grid_shape, view.brick_edge)))

    def tick(self) -> List[RenderResponse]:
        """Render every pending request (one jitted program per
        group) and return the responses, submission-ordered."""
        from repro.core.render import default_tf

        pending, self._pending = self._pending, []
        self._tick += 1
        groups: "OrderedDict[tuple, list]" = OrderedDict()
        for ticket, req in pending:
            groups.setdefault(self._group_key(req), []).append((ticket, req))
        responses: List[RenderResponse] = []
        for key, members in groups.items():
            (_W, _H, _S, _fov, lod, ts, _tfk, _d, _cdt, _odt) = key
            model = self.model_for(ts)
            metas = model.meta_arrays()
            grange = jnp.asarray(model.grange, jnp.float32)
            view = None
            if self.use_cache:
                view = self.cache.ensure(model, level=lod, timestep=ts)
            eyes = jnp.asarray([m[1].camera.eye for m in members], jnp.float32)
            ctrs = jnp.asarray([m[1].camera.center for m in members],
                               jnp.float32)
            ups = jnp.asarray([m[1].camera.up for m in members], jnp.float32)
            tfs = jnp.stack([(default_tf() if m[1].tf.table is None
                              else jnp.asarray(m[1].tf.table, jnp.float32))
                             for m in members])
            fn = self._batch_fn(key, view)
            t0 = time.monotonic()
            pool, slots, params = frame_operands(view, model)
            frames = fn(eyes, ctrs, ups, tfs, pool, slots, metas, grange,
                        params)
            frames = jax.block_until_ready(frames)
            ms = (time.monotonic() - t0) * 1e3
            arr = np.asarray(frames)
            for i, (ticket, req) in enumerate(members):
                responses.append(RenderResponse(
                    ticket=ticket, request=req, frame=arr[i], timestep=ts,
                    tick=self._tick, batch_size=len(members), render_ms=ms))
        self.ticks.append({
            "tick": self._tick, "requests": len(pending),
            "groups": len(groups), "cache": self.cache.stats(),
        })
        responses.sort(key=lambda r: r.ticket)
        return responses

    def stats(self) -> dict:
        return {"ticks": self._tick, "served": self._next_ticket,
                "pending": len(self._pending),
                "warm_models": len(self._warm), "cache": self.cache.stats()}
