"""repro.api — the single entry point for the DVNR lifecycle.

The paper's pipeline (per-partition INR training -> error-bounded weight
compression -> decode/render for reactive triggers) used to be spread across
free functions that each re-threaded an ``impl: str`` flag and raw
``{"tables": ..., "mlp": [...]}`` dicts. This module bundles it:

- :class:`DVNRModel` — a pytree-registered dataclass carrying the
  :class:`~repro.configs.dvnr.DVNRConfig`, the (possibly partition-stacked)
  params, per-partition metadata and the global value range, with
  ``apply`` / ``decode_grid`` / ``compress`` / ``save`` / ``load`` methods;
- lifecycle verbs — :func:`train`, :func:`render`, :func:`isosurface`,
  :func:`trace_pathlines`, :func:`compress` / :func:`decompress`;
- re-exports of the backend registry (:func:`get_backend`,
  :func:`available_backends`) and codec registry (:func:`get_codec`,
  :func:`available_codecs`), so callers never import kernel packages directly.

Quickstart (CPU)::

    from repro import api
    from repro.configs.dvnr import SMOKE
    from repro.data.volume import make_partition

    parts = [make_partition("cloverleaf", p, (1, 1, 2), (16, 16, 16), t=0.3)
             for p in range(2)]
    model, info = api.train(parts, SMOKE, key=jax.random.PRNGKey(0))
    image = api.render(model, api.RenderRequest(width=64, height=64))
    blobs, cinfo = api.compress(model)
    model.save("dvnr.msgpack")

The render surface is request-based: :class:`Camera`, :class:`TransferFunction`
and :class:`RenderRequest` are frozen dataclasses, :func:`render` is the one
public verb (``repro.core.render.render_partition`` / ``render_distributed``
are internal), and the old kwarg form ``api.render(model, eye=..., width=...)``
still works behind a ``DeprecationWarning`` shim. Pass ``cache=`` (a
:class:`repro.serving.BrickCache`) to sample decoded bricks instead of running
INR inference per frame; :class:`repro.serving.RenderService` batches many
concurrent requests.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import msgpack
import numpy as np

from repro import backends
from repro.backends import (Backend, BackendLike, available_backends,
                            get_backend, register_backend)
from repro.compress.model_compress import (compress_stacked,
                                           decompress_model)
from repro.compress.registry import available_codecs, get_codec, register_codec
from repro.configs.dvnr import DVNRConfig
from repro.core.inr import (_decode_grid, _inr_apply, init_inr,
                            param_bytes_f16, param_count)
from repro.core.render import Camera
from repro.core.trainer import DVNRState, DVNRTrainer, train_iterations
from repro.precision import Precision, resolve_precision

__all__ = [
    "DVNRModel", "PartitionMeta",
    "Camera", "TransferFunction", "RenderRequest",
    "train", "render", "isosurface", "trace_pathlines",
    "compress", "decompress", "save", "load",
    "Backend", "get_backend", "register_backend", "available_backends",
    "get_codec", "register_codec", "available_codecs",
    "DVNRConfig", "DVNRTrainer",
    "Precision", "resolve_precision",
]

_SAVE_KIND = "dvnr_model_v1"


# --------------------------------------------------------------------------- #
# Partition metadata
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class PartitionMeta:
    """Host-side metadata of one partition: box placement + value range."""

    origin: Tuple[float, float, float]
    extent: Tuple[float, float, float]
    vmin: float
    vmax: float

    def __getitem__(self, key: str):
        # legacy call sites index partition metadata like a dict
        return getattr(self, key)

    def to_dict(self) -> dict:
        return {"origin": list(self.origin), "extent": list(self.extent),
                "vmin": self.vmin, "vmax": self.vmax}

    @classmethod
    def of(cls, obj) -> "PartitionMeta":
        """Coerce a dict / VolumePartition / PartitionMeta."""
        if isinstance(obj, PartitionMeta):
            return obj
        if isinstance(obj, dict):
            return cls(tuple(obj["origin"]), tuple(obj["extent"]),
                       float(obj["vmin"]), float(obj["vmax"]))
        return cls(tuple(obj.origin), tuple(obj.extent),
                   float(obj.vmin), float(obj.vmax))


def _meta_tuple(parts_meta) -> Optional[Tuple[PartitionMeta, ...]]:
    if parts_meta is None:
        return None
    return tuple(PartitionMeta.of(m) for m in parts_meta)


def _grange_of(metas: Sequence[PartitionMeta]) -> Tuple[float, float]:
    return (min(m.vmin for m in metas), max(m.vmax for m in metas))


# --------------------------------------------------------------------------- #
# Render request objects
# --------------------------------------------------------------------------- #
@dataclass(frozen=True, eq=False)
class TransferFunction:
    """An RGBA transfer function over the GLOBAL normalized value range.

    ``table`` is a (K, 4) piecewise-linear RGBA lookup (``None`` -> the
    built-in cool-to-warm :func:`repro.core.render.default_tf`); ``density``
    scales opacity integration. Frozen (``eq=False``: the array field makes
    value equality meaningless) so requests can share one instance."""

    table: Any = None
    density: float = 50.0

    @property
    def table_shape(self) -> Optional[Tuple[int, ...]]:
        """Shape of ``table`` (``None`` for the default) — part of the render
        service's batch grouping key (it fixes traced array shapes)."""
        return None if self.table is None else tuple(np.shape(self.table))

    def resolved_table(self):
        from repro.core.render import default_tf
        return default_tf() if self.table is None else \
            jnp.asarray(self.table, jnp.float32)


@dataclass(frozen=True, eq=False)
class RenderRequest:
    """One render ask: everything a frame depends on, as a value.

    The one argument of :func:`render` (and the unit
    :class:`repro.serving.RenderService` coalesces into batched ticks):

    - ``camera`` / ``tf``   immutable :class:`Camera` / :class:`TransferFunction`
    - ``width``/``height``/``n_samples``   image + ray-march resolution
    - ``iso``               isosurface value in global normalized units
                            (used by :func:`isosurface`; ignored by volume
                            rendering)
    - ``timestep``          historical timestep served out of a
                            :class:`~repro.core.temporal.TemporalModelCache`
                            (``None`` -> the live model)
    - ``lod``               brick-cache level of detail (level ``l`` decodes
                            at ``ceil(shape / 2**l)``; cache path only)
    - ``compute_dtype``     reduced inference/compositing dtype (e.g.
                            ``"bfloat16"``); ``out_dtype`` casts the frame
    """

    camera: Camera = Camera()
    tf: TransferFunction = TransferFunction()
    width: int = 128
    height: int = 128
    n_samples: int = 64
    iso: Optional[float] = None
    timestep: Optional[int] = None
    lod: int = 0
    compute_dtype: Optional[str] = None
    out_dtype: Optional[str] = None


# --------------------------------------------------------------------------- #
# DVNRModel
# --------------------------------------------------------------------------- #
@jax.tree_util.register_pytree_node_class
@dataclass
class DVNRModel:
    """One DVNR: config + INR params (+ distributed partition metadata).

    ``params`` is either a single model pytree (``tables (L,T,F)``) or the
    partition-stacked form (``tables (P,L,T,F)``) the trainer produces. The
    params are pytree children (differentiable / jittable); everything else is
    static aux data, so a ``DVNRModel`` can flow through ``jax.jit`` and
    ``jax.grad`` like any array pytree.
    """

    cfg: DVNRConfig
    params: Any
    parts_meta: Optional[Tuple[PartitionMeta, ...]] = None
    grange: Optional[Tuple[float, float]] = None

    def __post_init__(self):
        if self.parts_meta is not None:
            self.parts_meta = _meta_tuple(self.parts_meta)
            if self.grange is None:
                self.grange = _grange_of(self.parts_meta)

    # ------------------------------ pytree ----------------------------- #
    def tree_flatten(self):
        return (self.params,), (self.cfg, self.parts_meta, self.grange)

    @classmethod
    def tree_unflatten(cls, aux, children):
        cfg, parts_meta, grange = aux
        obj = cls.__new__(cls)
        obj.cfg, obj.params, obj.parts_meta, obj.grange = \
            cfg, children[0], parts_meta, grange
        return obj

    # ----------------------------- construction ------------------------ #
    @classmethod
    def init(cls, cfg: DVNRConfig, key, n_partitions: Optional[int] = None,
             parts_meta=None) -> "DVNRModel":
        """Random-init a single model, or a stacked one for P partitions."""
        if n_partitions is None:
            return cls(cfg, init_inr(cfg, key), _meta_tuple(parts_meta))
        keys = jax.random.split(key, n_partitions)
        params = jax.vmap(lambda k: init_inr(cfg, k))(keys)
        return cls(cfg, params, _meta_tuple(parts_meta))

    @classmethod
    def from_state(cls, cfg: DVNRConfig, state: DVNRState,
                   parts_meta=None) -> "DVNRModel":
        """Wrap a trainer state's stacked params."""
        return cls(cfg, state.params, _meta_tuple(parts_meta))

    @classmethod
    def from_compressed(cls, cfg: DVNRConfig, blobs, parts_meta=None,
                        grange=None) -> "DVNRModel":
        """Rebuild a model from :meth:`compress` output (list of blobs, one
        per partition; a single ``bytes`` blob is accepted too)."""
        if isinstance(blobs, (bytes, bytearray)):
            blobs = [bytes(blobs)]
        parts = [decompress_model(cfg, b) for b in blobs]
        if len(parts) == 1:
            params = parts[0]
        else:
            params = jax.tree.map(lambda *xs: jnp.stack(xs), *parts)
        return cls(cfg, params, _meta_tuple(parts_meta), grange)

    # ------------------------------ structure --------------------------- #
    @property
    def stacked(self) -> bool:
        return self.params["tables"].ndim == 4

    @property
    def n_partitions(self) -> int:
        return int(self.params["tables"].shape[0]) if self.stacked else 1

    def partition(self, p: int) -> "DVNRModel":
        """Extract partition ``p`` as a single (unstacked) model."""
        if not self.stacked:
            if p != 0:
                raise IndexError("model is not partition-stacked")
            return self
        params_p = jax.tree.map(lambda t: t[p], self.params)
        meta = (self.parts_meta[p],) if self.parts_meta is not None else None
        return DVNRModel(self.cfg, params_p, meta, self.grange)

    def stacked_params(self) -> Any:
        """Params with a leading partition axis (added if single)."""
        if self.stacked:
            return self.params
        return jax.tree.map(lambda t: t[None], self.params)

    def _derive_meta_arrays(self):
        from repro.core.render import meta_arrays
        return meta_arrays(self.parts_meta)

    def meta_arrays(self):
        """Partition metadata batched to ``(los, exts, vrs)`` device arrays,
        derived ONCE per model instance — repeated renders reuse the memoized
        arrays instead of re-reducing over partitions every call. (Memo lives
        outside the pytree: unflattened copies lazily re-derive.)"""
        cached = self.__dict__.get("_meta_arrays_cache")
        if cached is None:
            if self.parts_meta is None:
                raise ValueError("meta_arrays() needs model.parts_meta")
            cached = self._derive_meta_arrays()
            self.__dict__["_meta_arrays_cache"] = cached
        return cached

    @property
    def param_count(self) -> int:
        return self.n_partitions * param_count(self.cfg)

    @property
    def nbytes(self) -> int:
        return sum(np.asarray(t).nbytes for t in jax.tree.leaves(self.params))

    # ------------------------------ inference --------------------------- #
    def apply(self, coords, backend: BackendLike = "auto", *,
              compute_dtype=None):
        """coords (N,3) in [0,1]^3 -> (N, out_dim). Single-partition models
        only — use :meth:`partition` first on stacked models.
        ``compute_dtype`` runs the encode+MLP stack reduced (e.g. bf16)."""
        if self.stacked:
            raise ValueError("apply() on a stacked model: select a partition "
                             "first (model.partition(p).apply(coords))")
        return _inr_apply(self.cfg, self.params, coords,
                          backends.resolve(backend),
                          compute_dtype=compute_dtype)

    def decode_grid(self, shape: Sequence[int], backend: BackendLike = "auto",
                    chunk: int = 1 << 17, *, compute_dtype=None,
                    out_dtype=None):
        """Decode back to a cell-centered grid (compatibility path).
        ``compute_dtype``/``out_dtype``: reduced-precision decode and/or
        output cast (fully-bf16 inference: both set to ``"bfloat16"``)."""
        if self.stacked:
            raise ValueError("decode_grid() on a stacked model: select a "
                             "partition first (model.partition(p))")
        return _decode_grid(self.cfg, self.params, shape,
                            backends.resolve(backend), chunk,
                            compute_dtype=compute_dtype, out_dtype=out_dtype)

    # ------------------------------ compression ------------------------- #
    def compress(self, r_enc: Optional[float] = None,
                 r_mlp: Optional[float] = None, **codec_kw) -> list:
        """Error-bounded weight compression (paper III-D) of every partition.
        Returns one blob per partition. Codec selection by name via
        ``dense_codec=`` / ``hash_codec=`` / ``mlp_codec=``."""
        blobs, _ = compress(self, r_enc=r_enc, r_mlp=r_mlp, **codec_kw)
        return blobs

    # ------------------------------ persistence ------------------------- #
    def save(self, path) -> None:
        """Serialize config + params + metadata to ``path`` (msgpack)."""
        from repro.compress.codec_util import dtype_token

        def arr(t):
            a = np.asarray(t)
            return {"dtype": dtype_token(a.dtype), "shape": list(a.shape),
                    "data": a.tobytes()}

        payload = {
            "kind": _SAVE_KIND,
            "cfg": dataclasses.asdict(self.cfg),
            "tables": arr(self.params["tables"]),
            "mlp": [arr(w) for w in self.params["mlp"]],
            "parts_meta": ([m.to_dict() for m in self.parts_meta]
                           if self.parts_meta is not None else None),
            "grange": list(self.grange) if self.grange is not None else None,
        }
        with open(path, "wb") as f:
            f.write(msgpack.packb(payload, use_bin_type=True))

    @classmethod
    def load(cls, path) -> "DVNRModel":
        with open(path, "rb") as f:
            try:
                payload = msgpack.unpackb(f.read(), raw=False)
            except Exception as e:
                raise ValueError(f"{path}: not a saved DVNRModel ({e})") from e
        if not isinstance(payload, dict) or payload.get("kind") != _SAVE_KIND:
            raise ValueError(f"{path}: not a saved DVNRModel")

        def arr(d):
            return jnp.asarray(np.frombuffer(d["data"], np.dtype(d["dtype"]))
                               .reshape(d["shape"]))

        cfg = DVNRConfig(**payload["cfg"])
        params = {"tables": arr(payload["tables"]),
                  "mlp": [arr(w) for w in payload["mlp"]]}
        meta = (_meta_tuple(payload["parts_meta"])
                if payload["parts_meta"] is not None else None)
        grange = tuple(payload["grange"]) if payload["grange"] else None
        return cls(cfg, params, meta, grange)


# --------------------------------------------------------------------------- #
# Lifecycle verbs
# --------------------------------------------------------------------------- #
def train(partitions, cfg: DVNRConfig, *, backend: BackendLike = "auto",
          mesh=None, steps: Optional[int] = None, key=None,
          cached_params=None, trainer: Optional[DVNRTrainer] = None,
          ghost: Optional[int] = None, volumes=None,
          log_every: int = 0, check_every: int = 0,
          precision=None,
          fuse_train_step: Optional[str] = None,
          fuse_sampling: Optional[str] = None,
          sampling_brick=None,
          recovery=None, train_mask=None) -> Tuple[DVNRModel, dict]:
    """Train one INR per partition (zero-communication) and return the model.

    ``partitions``: sequence of :class:`~repro.data.volume.VolumePartition`
    (anything with ``normalized()``, ``owned_shape``, ``origin``, ``extent``,
    ``vmin``, ``vmax``, ``ghost``). ``steps`` defaults to the paper's III-B
    adaptive iteration count. Pass a pre-built ``trainer`` to reuse its
    compiled step across repeated calls (in situ ticks); pass ``volumes``
    (a stacked (P, ...) normalized array) to train on data other than the
    partitions' own; ``log_every`` > 0 records a loss curve in the info dict.

    Training runs device-resident: ``check_every`` steps are fused into one
    scanned device program between host-side convergence checks (0 = auto;
    see :meth:`DVNRTrainer.train`).

    ``precision`` overrides ``cfg.precision`` (a policy name like ``"bf16"``,
    a ``"param/compute/output"`` triple, or a
    :class:`repro.precision.Precision`): the mixed ``"bf16"`` policy trains
    with bf16 params/activations and f32 AdamW master state.

    ``fuse_train_step`` overrides ``cfg.fuse_train_step`` (``"auto"`` /
    ``"on"`` / ``"off"``): whether each step runs as the fused
    fwd+bwd+AdamW op (:mod:`repro.kernels.fused_train_step` — one Pallas
    kernel on pallas backends) instead of the unfused value_and_grad step.
    ``fuse_sampling`` likewise overrides ``cfg.fuse_sampling``: whether the
    batch sampling (counter-based coordinate draws + trilinear target
    gather) happens inside that fused op too (in-kernel on pallas backends)
    instead of on the host — every mode draws bit-identical batches.
    ``sampling_brick`` overrides ``cfg.sampling_brick`` (``"auto"`` /
    ``"pinned"`` / an int cube edge): whether the in-kernel gather pins the
    whole partition in VMEM or streams HBM-resident bricks through a
    double-buffered VMEM block — both layouts are bit-identical; ``"auto"``
    tiles exactly when the partition cannot fit pinned.

    ``recovery`` (a :class:`repro.resilience.RecoveryPolicy`) routes training
    through the non-finite recovery driver — partitions tripping the
    on-device detector are retried (reseed → rollback → lr-backoff) and
    frozen at their last-good params when attempts run out; the info dict
    then carries a ``"recovery"`` entry. ``train_mask`` ((P,) bool) excludes
    partitions from training from step 0 (their INRs keep the warm-start /
    cached params — the degraded-rank restore path of the in situ session).
    """
    key = jax.random.PRNGKey(0) if key is None else key
    k_init, k_train = jax.random.split(key)
    P = len(partitions)
    g = partitions[0].ghost if ghost is None else ghost
    if fuse_train_step is not None:
        cfg = cfg.replace(fuse_train_step=fuse_train_step)
        # compare resolved behavior, not flag strings: "auto" and "on" are the
        # same program on a backend that advertises the op
        if trainer is not None and \
                trainer.fuse_train_step != trainer._resolve_fuse(fuse_train_step):
            raise ValueError(
                f"fuse_train_step={fuse_train_step!r} conflicts with the "
                f"pre-built trainer's {trainer.cfg.fuse_train_step!r}; build "
                f"the trainer with the desired cfg.fuse_train_step instead")
    if fuse_sampling is not None:
        cfg = cfg.replace(fuse_sampling=fuse_sampling)
        if trainer is not None and \
                trainer.fuse_sampling != trainer._resolve_fuse_sampling(fuse_sampling):
            raise ValueError(
                f"fuse_sampling={fuse_sampling!r} conflicts with the "
                f"pre-built trainer's {trainer.cfg.fuse_sampling!r}; build "
                f"the trainer with the desired cfg.fuse_sampling instead")
    if sampling_brick is not None:
        cfg = cfg.replace(sampling_brick=sampling_brick)
        # the brick feeds the trainer's traced step directly — a pre-built
        # trainer has already committed to its cfg's layout
        if trainer is not None and \
                trainer.cfg.sampling_brick != sampling_brick:
            raise ValueError(
                f"sampling_brick={sampling_brick!r} conflicts with the "
                f"pre-built trainer's {trainer.cfg.sampling_brick!r}; build "
                f"the trainer with the desired cfg.sampling_brick instead")
    if precision is not None:
        cfg = cfg.replace(precision=resolve_precision(precision).name)
        if trainer is not None and trainer.precision != resolve_precision(precision):
            # a pre-built trainer carries its own compiled policy; silently
            # training under it while the returned model claims `precision`
            # would lie to every downstream consumer of model.cfg
            raise ValueError(
                f"precision={precision!r} conflicts with the pre-built "
                f"trainer's policy {trainer.cfg.precision!r}; build the "
                f"trainer with the desired cfg.precision instead")
    vols = jnp.stack([p.normalized() for p in partitions]) \
        if volumes is None else volumes
    if trainer is None:
        # declaring the volume shape lets build time reject configs that
        # could not run (VMEM budget of the volume-pinned sampling kernel,
        # cfg.static_checks) before any compilation happens
        trainer = DVNRTrainer(cfg, P, mesh=mesh, impl=backend, ghost=g,
                              volume_shape=tuple(vols.shape[1:]))
    state = trainer.init(k_init, cached_params=cached_params)
    if train_mask is not None:
        mask = jnp.asarray(np.asarray(train_mask, bool))
        state = dataclasses.replace(state, active=state.active & mask)
    nvox = int(np.prod(partitions[0].owned_shape))
    n_steps = train_iterations(cfg, nvox) if steps is None else steps
    t0 = time.time()
    state, hist = trainer.train(state, vols, steps=n_steps, key=k_train,
                                log_every=log_every, check_every=check_every,
                                recovery=recovery)
    jax.block_until_ready(state.params)
    train_time_s = time.time() - t0
    metas = _meta_tuple(partitions)
    model = DVNRModel(cfg, state.params, metas)
    info = {"train_time_s": train_time_s, "steps": int(state.step),
            "loss_history": hist.get("loss", []), "state": state,
            "trainer": trainer}
    if "recovery" in hist:
        info["recovery"] = hist["recovery"]
    return model, info


_LEGACY_RENDER_KW = ("camera", "eye", "center", "up", "fov_deg", "width",
                     "height", "n_samples", "tf_table", "density",
                     "compute_dtype", "out_dtype")


def _request_from_legacy(kw: dict) -> RenderRequest:
    """The pre-RenderRequest kwarg surface, shimmed (PR 1 ``inr_apply``
    migration pattern): warn once per call site, build the equivalent request."""
    import warnings

    bad = set(kw) - set(_LEGACY_RENDER_KW)
    if bad:
        raise TypeError(f"render() got unexpected keyword arguments "
                        f"{sorted(bad)}")
    warnings.warn(
        "api.render(eye=..., width=..., ...) kwargs are deprecated; pass a "
        "request: api.render(model, RenderRequest(camera=Camera(eye=...), "
        "width=...))", DeprecationWarning, stacklevel=3)
    cam = kw.pop("camera", None)
    if cam is None:
        d = Camera()
        cam = Camera(eye=tuple(kw.pop("eye", d.eye)),
                     center=tuple(kw.pop("center", d.center)),
                     up=tuple(kw.pop("up", d.up)),
                     fov_deg=float(kw.pop("fov_deg", d.fov_deg)))
    else:
        for k in ("eye", "center", "up", "fov_deg"):
            kw.pop(k, None)
    tf = TransferFunction(table=kw.pop("tf_table", None),
                          density=float(kw.pop("density", 50.0)))
    return RenderRequest(camera=cam, tf=tf, **kw)


def render(model: DVNRModel, request: Optional[RenderRequest] = None, *,
           backend: BackendLike = "auto", mesh=None, cache=None, **legacy):
    """Sort-last direct volume rendering of the DVNR (never decodes a grid).

    ``request`` is a :class:`RenderRequest` (default: the default request —
    128x128, default camera/TF). ``cache`` (a
    :class:`repro.serving.BrickCache`) swaps per-frame INR inference for
    trilinear sampling of its decoded brick pool (``request.lod`` /
    ``request.timestep`` select the cached level); without it every frame
    runs INR inference. ``request.compute_dtype`` runs inference reduced
    (bf16 decode for interactivity); ``request.out_dtype`` casts the final
    (H,W,4) image.

    The old kwarg form ``render(model, eye=..., width=...)`` still renders
    identically but emits ``DeprecationWarning``."""
    from repro.serving.service import frame_operands, frame_program

    if model.parts_meta is None:
        raise ValueError("render() needs model.parts_meta (train via "
                         "repro.api.train or attach PartitionMeta)")
    if legacy:
        if request is not None:
            raise TypeError("render() takes a RenderRequest OR legacy "
                            "kwargs, not both")
        request = _request_from_legacy(dict(legacy))
    elif request is None:
        request = RenderRequest()
    r = request
    view = None
    if cache is not None:
        view = cache.ensure(model, level=r.lod, timestep=r.timestep)
    # one frame through the render service's jitted frame program (a batch
    # of one): the same compiled program a service tick of this shape runs
    fn = frame_program(
        model.cfg, fov=r.camera.fov_deg, width=r.width, height=r.height,
        n_samples=r.n_samples, density=r.tf.density,
        compute_dtype=r.compute_dtype, out_dtype=r.out_dtype,
        backend=backends.resolve(backend),
        view_geom=None if view is None else (view.grid_shape,
                                             view.brick_edge))
    cam = r.camera
    pool, slots, params = frame_operands(view, model)
    frames = fn(jnp.asarray([cam.eye], jnp.float32),
                jnp.asarray([cam.center], jnp.float32),
                jnp.asarray([cam.up], jnp.float32),
                r.tf.resolved_table()[None], pool, slots,
                model.meta_arrays(), jnp.asarray(model.grange, jnp.float32),
                params)
    return frames[0]


def isosurface(model: DVNRModel, iso01=0.5, *, resolution: int = 32,
               backend: BackendLike = "auto") -> np.ndarray:
    """Per-partition marching tets on the INR; returns world-space points.
    ``iso01`` is in GLOBAL normalized units — either a float or a
    :class:`RenderRequest` whose ``iso`` field carries the value (the same
    request object :func:`render` takes)."""
    from repro.core.isosurface import isosurface_from_inr, surface_points

    if isinstance(iso01, RenderRequest):
        if iso01.iso is None:
            raise ValueError("isosurface() from a RenderRequest needs "
                             "request.iso set")
        iso01 = float(iso01.iso)
    if model.parts_meta is None:
        raise ValueError("isosurface() needs model.parts_meta")
    b = backends.resolve(backend)
    gmin, gmax = model.grange
    clouds = []
    for p in range(model.n_partitions):
        meta = model.parts_meta[p]
        iso_raw = gmin + iso01 * (gmax - gmin)
        denom = max(meta.vmax - meta.vmin, 1e-12)
        iso_local = (iso_raw - meta.vmin) / denom
        if not (0.0 <= iso_local <= 1.0):
            continue                   # isosurface does not cross this partition
        part = model.partition(p)
        tris, valid = isosurface_from_inr(
            model.cfg, part.params, float(iso_local),
            shape=(resolution,) * 3, origin=meta.origin,
            extent=meta.extent, impl=b)
        pts = surface_points(tris, valid)
        if len(pts):
            clouds.append(pts)
    if not clouds:
        return np.zeros((0, 3), np.float32)
    return np.concatenate(clouds, axis=0)


def trace_pathlines(models: Sequence[DVNRModel], seeds, dt: float, *,
                    substeps: int = 4, backend: BackendLike = "auto"):
    """Backward pathline tracing over a temporal window of velocity DVNRs
    (newest -> oldest). Returns trajectory (T*substeps+1, N, 3)."""
    from repro.core.pathlines import trace_backward

    if not models:
        raise ValueError("empty model window")
    if any(m.parts_meta is None for m in models):
        raise ValueError("trace_pathlines() needs parts_meta on every model "
                         "in the window (train via repro.api.train or attach "
                         "PartitionMeta)")
    cfg = models[0].cfg
    window = [m.stacked_params() for m in models]
    metas = [list(m.parts_meta) for m in models]
    return trace_backward(cfg, window, metas, seeds, dt, substeps=substeps,
                          impl=backends.resolve(backend))


def compress(model: DVNRModel, *, r_enc: Optional[float] = None,
             r_mlp: Optional[float] = None, **codec_kw) -> Tuple[list, dict]:
    """Compress every partition; returns (blobs, info) where info aggregates
    byte counts and the model compression ratio vs fp16 storage."""
    pairs = compress_stacked(model.cfg, model.stacked_params(),
                             r_enc=r_enc, r_mlp=r_mlp, **codec_kw)
    blobs = [b for b, _ in pairs]
    total = sum(len(b) for b in blobs)
    f16 = model.n_partitions * param_bytes_f16(model.cfg)
    info = {"bytes": total, "f16_bytes": f16,
            "model_cr": f16 / max(total, 1),
            "per_partition": [i for _, i in pairs]}
    return blobs, info


def decompress(cfg: DVNRConfig, blobs, *, parts_meta=None,
               grange=None) -> DVNRModel:
    """Inverse of :func:`compress`."""
    return DVNRModel.from_compressed(cfg, blobs, parts_meta, grange)


def save(model: DVNRModel, path) -> None:
    model.save(path)


def load(path) -> DVNRModel:
    return DVNRModel.load(path)
