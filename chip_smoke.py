#!/usr/bin/env python3
"""Chip smoke test: the DVNR in situ path end to end on a TPU.

    python3 chip_smoke.py [--seed N] [--out FILE.json]     # one chip
    python3 chip_smoke.py --chips 4                        # four-chip phase

One process, no child processes. Everything runs through ``repro.api`` and
``repro.serving`` on the default (``auto``) backend, at the paper's 512^3
strong-scaled run: 8 ranks of the ``PRODUCTION256`` preset (256^3 owned
voxels plus 1 ghost layer each, T = 2^13, batch 65,536) on one chip. The
field is the synthetic CloverLeaf-like shock (``repro.data.volume``) at a
time drawn from ``--seed``; the INR weights are initialized from ``--seed``.

One-chip phases, each timed cold (compile included) and warm:

- ``train``     ``api.train``: two 64-step scan-fused chunks;
- ``parity``    the first steps of the same program on the host CPU device;
- ``compress``  ``api.compress`` -> ``api.decompress``, the model appended to
                a ``TemporalModelCache``, decoded PSNR vs the volume;
- ``render``    ``api.render`` of a 512^2 frame, 64 samples per ray;
- ``serve``     a ``RenderService`` with a ``BrickCache`` (128^3 per rank,
                LOD 1 of the 256^3 ranks) answers orbiting requests, live
                and from the temporal cache, over two ticks.

Checks (any failure exits non-zero and prints no result): the loss falls and
every partition stays finite; the first per-step losses match the CPU run
within ``PARITY_RTOL``; the decoded PSNR clears ``PSNR_FLOOR_DB``; every frame
is finite; the cached frame agrees with the direct render within
``CACHE_MEAN_ABS``; no op runs as an interpreted Pallas kernel.

``--chips 4`` runs only the four-chip phase: the same 8 ranks trained for
``mesh_steps`` steps under a 2x2 device mesh against the same steps on one
chip (per-rank params within ``MESH_PARAM_RTOL``, and a train program with no
collective), and binary-swap compositing of a ``swap_frame``^2 frame over the
mesh against the single-device depth-sort composite.

The last line of stdout is ``{"ok": true, "device": {...}}``. Without a TPU,
or outside a checkout of this repository, the script exits non-zero. The
compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache``
in the checkout.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time
import traceback
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: relative tolerance of the TPU-vs-CPU per-step losses over the first
#: ``parity_steps`` steps. TPU f32 matmuls run bf16 passes by default: on a
#: v5e the sound run reads 7.9e-5, and bf16 compute (6.7e-5) or ``highest``
#: precision (1.2e-5) read no more, so this check cannot see precision; a
#: learning rate 2 % too large reads 1.3e-3 (see PERF.md)
PARITY_RTOL = 5e-4
#: decoded (compressed -> decompressed) PSNR floor over all ranks, dB
PSNR_FLOOR_DB = 30.0
#: the loss must fall: mean of the last steps <= this x mean of the first
LOSS_FALL = 0.5
#: mean |cached frame - direct frame| (the brick pool resamples the INR)
CACHE_MEAN_ABS = 0.02
#: per-rank relative L2 distance of mesh-trained vs one-chip-trained params:
#: the same program reads 5.7e-9 on a v5e, while any change of matmul
#: precision reads 0.11 after 16 steps (see PERF.md)
MESH_PARAM_RTOL = 1e-6
#: binary swap vs single-device depth-sort composite, absolute
SWAP_ATOL = 1e-5


class SmokeFailure(AssertionError):
    """A phase's check failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


@dataclass(frozen=True)
class Sizes:
    """One smoke configuration: the preset plus how much of it to run."""

    cfg_name: str = "PRODUCTION256"
    ranks: int = 8
    local: int = 256                 # owned voxels per axis per rank
    chunk: int = 64                  # steps per scan-fused chunk
    chunks: int = 2
    parity_steps: int = 4
    frame: int = 512                 # frame edge, pixels
    samples: int = 64                # samples per ray
    cache_grid: int = 128            # brick-cache grid per rank
    brick_edge: int = 16
    requests: int = 2                # live requests per tick
    ticks: int = 2
    decode_chunk: int = 1 << 18      # coords per PSNR decode pass
    mesh_steps: int = 16             # --chips 4: steps of each train run
    swap_frame: int = 256            # --chips 4: binary-swapped frame edge
    # checks that depend on the size (tiny CPU rehearsals train far less)
    psnr_floor_db: float = PSNR_FLOOR_DB
    loss_fall: float = LOSS_FALL
    cache_mean_abs: float = CACHE_MEAN_ABS

    @property
    def cfg(self):
        from repro.configs import dvnr
        return getattr(dvnr, self.cfg_name)

    @property
    def steps(self) -> int:
        return self.chunk * self.chunks


FULL = Sizes()


# --------------------------------------------------------------------------- #
# Measurement plumbing
# --------------------------------------------------------------------------- #
def compile_mark() -> int:
    """A wall-clock mark for :func:`compiled_since`. Importing
    ``repro.tracing`` registers the program's compile listeners."""
    import repro.tracing  # noqa: F401
    return time.time_ns()


def compiled_since(mark: int) -> dict:
    """Backend compiles (or persistent-cache reads) that ended since
    ``mark``: their seconds, their count, and the cache reads among them,
    from ``repro.tracing``."""
    from repro import tracing
    log = tracing.compile_log(since_ns=mark)
    return {"compile_s": sum(log), "compiles": len(log),
            "cache_hits": tracing.cache_hits(mark)}


def timed(fn):
    """(result, wall seconds) of ``fn()`` with its device work finished."""
    import jax
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def report(phase: str, rec: dict, results: dict) -> None:
    results[phase] = rec
    print(f"phase {phase}: " + " ".join(f"{k}={v}" for k, v in rec.items()),
          flush=True)


def op_impls(backend) -> dict:
    """How each main-path op runs on ``backend``: ``xla`` (jnp composition
    compiled by XLA), ``pallas`` or ``pallas-interpret``."""
    kernel = "xla"
    if backend.is_pallas:
        kernel = "pallas-interpret" if backend.interpret else "pallas"
    ops = ("hash_encoding", "fused_mlp", "composite", "fused_train_step",
           "fused_sampling", "brick_cache")
    return {op: kernel if backend.supports(op) else "xla" for op in ops}


# --------------------------------------------------------------------------- #
# Phases
# --------------------------------------------------------------------------- #
def make_partitions(sizes: Sizes, seed: int):
    """The ranks' ghost-padded partitions of one timestep, generated on the
    default device; the simulation time comes from ``seed``."""
    import numpy as np
    from repro.data.volume import make_partition, partition_grid

    t = float(np.random.default_rng(seed).uniform(0.2, 0.5))
    grid = partition_grid(sizes.ranks)
    return [make_partition("cloverleaf", p, grid, (sizes.local,) * 3, t=t)
            for p in range(sizes.ranks)]


def phase_train(parts, sizes: Sizes, key, backend, results):
    """``api.train`` cold (compile + all chunks) and warm (one chunk, the
    built trainer reused); checks that the loss falls and every partition
    stays finite."""
    import numpy as np
    from repro import api

    def run(steps, trainer=None):
        return api.train(parts, sizes.cfg, backend=backend, steps=steps,
                         check_every=sizes.chunk, key=key, log_every=1,
                         trainer=trainer)

    mark = compile_mark()
    (model, info), cold = timed(lambda: run(sizes.steps))
    comp = compiled_since(mark)
    (_, info_w), warm = timed(lambda: run(sizes.chunk, info["trainer"]))
    losses = np.asarray([l for _, l in info["loss_history"]], np.float64)
    warm_losses = np.asarray([l for _, l in info_w["loss_history"]])
    finite = np.asarray(info["state"].finite)
    k = min(8, len(losses) // 2)
    first, last = float(losses[:k].mean()), float(losses[-k:].mean())
    report("train", {
        "ranks": sizes.ranks, "local": sizes.local, "steps": info["steps"],
        "batch": sizes.cfg.batch_size, "cold_s": cold, **comp, "warm_s": warm,
        "warm_steps": sizes.chunk, "warm_steps_per_s": sizes.chunk / warm,
        "loss_first": first, "loss_last": last,
        "warm_repeat_max_abs_diff":
            float(np.abs(warm_losses - losses[:sizes.chunk]).max()),
        "finite": bool(finite.all())}, results)
    check(bool(finite.all()), f"non-finite partitions: {finite.tolist()}")
    check(np.isfinite(losses).all(), "non-finite loss")
    check(last <= sizes.loss_fall * first,
          f"loss did not fall: first {first} -> last {last}")
    return model, losses


def phase_parity(parts, sizes: Sizes, key, backend, ref_losses, results):
    """The first ``parity_steps`` steps of the same training program on the
    host's CPU device; per-step mean losses must match the device run."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import api

    cpu = jax.devices("cpu")[0]
    n = sizes.parity_steps
    vols = jax.device_put(jnp.stack([p.normalized() for p in parts]), cpu)
    mark = compile_mark()
    with jax.default_device(cpu):
        (_, info), wall = timed(lambda: api.train(
            parts, sizes.cfg, backend=backend, steps=n, check_every=n,
            key=jax.device_put(key, cpu), volumes=vols, log_every=1))
    cpu_losses = np.asarray([l for _, l in info["loss_history"]], np.float64)
    rel = np.abs(cpu_losses - ref_losses[:n]) / np.abs(cpu_losses)
    report("parity", {"steps": n, "cpu_s": wall, **compiled_since(mark),
                      "device_losses": ref_losses[:n].tolist(),
                      "cpu_losses": cpu_losses.tolist(),
                      "max_rel_diff": float(rel.max()),
                      "rtol": PARITY_RTOL}, results)
    check(len(cpu_losses) == n, "CPU run logged the wrong number of steps")
    check(float(rel.max()) <= PARITY_RTOL,
          f"device vs CPU losses differ by {float(rel.max())} (relative)")


def decoded_psnr(model, parts, sizes: Sizes) -> float:
    """PSNR (paper V-B: mean MSE over ranks) of ``model`` decoded on every
    owned voxel center against the normalized volume, via
    ``DVNRModel.apply`` in fixed-size passes."""
    import jax
    import jax.numpy as jnp
    from repro.core.metrics import psnr_from_mses

    n, g = sizes.local, parts[0].ghost
    chunk = min(sizes.decode_chunk, n ** 3)

    @jax.jit
    def mse(part, vol):
        xs = (jnp.arange(n) + 0.5) / n
        X, Y, Z = jnp.meshgrid(xs, xs, xs, indexing="ij")
        coords = jnp.stack([X, Y, Z], -1).reshape(-1, chunk, 3)
        dec = jax.lax.map(lambda c: part.apply(c)[:, 0], coords)
        ref = vol[g:g + n, g:g + n, g:g + n].reshape(dec.shape)
        return jnp.mean(jnp.square(dec - ref))

    mses = [mse(model.partition(p), parts[p].normalized())
            for p in range(model.n_partitions)]
    return float(psnr_from_mses(jnp.stack(mses)))


def phase_compress(model, parts, sizes: Sizes, results):
    """``api.compress`` -> ``api.decompress``; the trained model appended to
    a ``TemporalModelCache``; decoded PSNR must clear the floor."""
    from repro import api
    from repro.core.temporal import TemporalModelCache

    mark = compile_mark()
    (blobs, cinfo), t_comp = timed(lambda: api.compress(model))
    dec, t_dec = timed(lambda: api.decompress(sizes.cfg, blobs,
                                              parts_meta=model.parts_meta))
    cache = TemporalModelCache(sizes.cfg, window=2)
    _, t_app = timed(lambda: cache.append(0, model.stacked_params()))
    psnr_dec, t_psnr = timed(lambda: decoded_psnr(dec, parts, sizes))
    report("compress", {
        "compress_s": t_comp, "decompress_s": t_dec, "cache_append_s": t_app,
        "psnr_cold_s": t_psnr, **compiled_since(mark),
        "bytes": cinfo["bytes"],
        "model_cr": cinfo["model_cr"], "cache_bytes": cache.total_bytes,
        "psnr_decoded_db": psnr_dec,
        "psnr_floor_db": sizes.psnr_floor_db}, results)
    check(len(blobs) == model.n_partitions, "one blob per rank expected")
    check(psnr_dec >= sizes.psnr_floor_db,
          f"decoded PSNR {psnr_dec} dB below the {sizes.psnr_floor_db} dB "
          "floor")
    return dec, cache


def phase_render(model, sizes: Sizes, backend, results):
    """``api.render`` of one frame, cold and warm; the frame is finite and
    not empty."""
    import numpy as np
    from repro import api

    req = api.RenderRequest(width=sizes.frame, height=sizes.frame,
                            n_samples=sizes.samples)
    mark = compile_mark()
    _, cold = timed(lambda: api.render(model, req, backend=backend))
    comp = compiled_since(mark)
    frame, warm = timed(lambda: api.render(model, req, backend=backend))
    frame = np.asarray(frame)
    report("render", {"frame": f"{sizes.frame}x{sizes.frame}",
                      "samples": sizes.samples, "cold_s": cold, **comp,
                      "warm_s": warm,
                      "mean_alpha": float(frame[..., 3].mean())}, results)
    check(frame.shape == (sizes.frame, sizes.frame, 4), "wrong frame shape")
    check(np.isfinite(frame).all(), "non-finite direct frame")
    check(float(frame[..., 3].mean()) > 0, "empty direct frame")
    return frame


def phase_serve(model, temporal, direct_frame, sizes: Sizes, backend,
                results):
    """A ``RenderService`` over a ``BrickCache`` answers orbiting live
    requests plus one temporal-cache request per tick, over ``ticks``
    ticks."""
    import numpy as np
    from repro import api
    from repro.serving import BrickCache, RenderService

    # room for the working sets of the live model and one past timestep
    bricks = -(-sizes.cache_grid // sizes.brick_edge) ** 3
    budget = 2 * model.n_partitions * bricks * (sizes.brick_edge + 1) ** 3 * 4
    cache = BrickCache(sizes.cfg, grid_shape=(sizes.cache_grid,) * 3,
                       brick_edge=sizes.brick_edge, budget_bytes=budget,
                       backend=backend)
    svc = RenderService(model, temporal=temporal, cache=cache,
                        backend=backend)
    cam = api.Camera()

    def req(**kw):
        return api.RenderRequest(width=sizes.frame, height=sizes.frame,
                                 n_samples=sizes.samples, **kw)

    ticks, frames = [], []
    for tick in range(sizes.ticks):
        angles = [2 * math.pi * (i / sizes.requests + tick / 12)
                  for i in range(sizes.requests)]
        # tick 0 opens with the default camera: the direct render's view
        cams = [cam] + [cam.orbit(a) for a in angles[1:]] if tick == 0 \
            else [cam.orbit(a) for a in angles]
        for c in cams:
            svc.submit(req(camera=c))
        svc.submit(req(camera=cam.orbit(angles[0]), timestep=0))
        mark = compile_mark()
        out, wall = timed(lambda: svc.tick())
        frames.append([r.frame for r in out])
        ticks.append({"tick_s": wall, **compiled_since(mark),
                      "responses": len(out)})
    stats = cache.stats()
    first_live = np.asarray(frames[0][0])
    mean_abs = float(np.abs(first_live - direct_frame).mean())
    report("serve", {
        "cache_grid": sizes.cache_grid, "brick_edge": sizes.brick_edge,
        "pool_bytes": stats["pool_bytes"], "ticks": len(ticks),
        **{f"tick{i}_{k}": v for i, t in enumerate(ticks)
           for k, v in t.items()},
        "hits": stats["hits"], "misses": stats["misses"],
        "evictions": stats["evictions"],
        "cached_vs_direct_mean_abs": mean_abs,
        "cached_vs_direct_max_abs":
            float(np.abs(first_live - direct_frame).max())}, results)
    n_req = sizes.requests + 1
    check(all(t["responses"] == n_req for t in ticks),
          "a tick lost responses")
    check(all(np.isfinite(f).all() for fs in frames for f in fs),
          "non-finite served frame")
    check(stats["hits"] > 0, "the brick cache never hit")
    check(mean_abs <= sizes.cache_mean_abs,
          f"cached frame differs from the direct render by {mean_abs}")


def run_one_chip(sizes: Sizes, seed: int, backend) -> dict:
    import jax

    results = {}
    key = jax.random.PRNGKey(seed)
    parts, t_data = timed(lambda: make_partitions(sizes, seed))
    print(f"data: {sizes.ranks} ranks x {sizes.local}^3 (+1 ghost), "
          f"generated in {t_data} s", flush=True)
    model, losses = phase_train(parts, sizes, key, backend, results)
    phase_parity(parts, sizes, key, backend, losses, results)
    _, temporal = phase_compress(model, parts, sizes, results)
    frame = phase_render(model, sizes, backend, results)
    phase_serve(model, temporal, frame, sizes, backend, results)
    return results


# --------------------------------------------------------------------------- #
# Four chips
# --------------------------------------------------------------------------- #
def render_partials(model, sizes: Sizes, backend):
    """Every rank's partial image and depth buffer of the default view,
    (P, R, 4) and (P, R), on one device (ranks and ray chunks in turn)."""
    import jax
    import jax.numpy as jnp
    from repro.core.render import (Camera, _render_partition, default_tf,
                                   make_rays)

    origins, dirs = make_rays(Camera(), sizes.swap_frame, sizes.swap_frame)
    R = origins.shape[0]
    per = max(1, min(R, (1 << 20) // sizes.samples))
    while R % per:
        per -= 1
    grange = jnp.asarray(model.grange, jnp.float32)

    @jax.jit
    def partials(params, los, exts, vrs, origins, dirs):
        o = origins.reshape(-1, per, 3)
        d = dirs.reshape(-1, per, 3)

        def one_rank(x):
            p, lo, ext, vr = x

            def chunk(od):
                return _render_partition(
                    model.cfg, p, lo, ext, (vr[0], vr[1]),
                    (grange[0], grange[1]), od[0], od[1], default_tf(),
                    n_samples=sizes.samples, impl=backend)

            img, dep = jax.lax.map(chunk, (o, d))
            return img.reshape(R, 4), dep.reshape(R)

        return jax.lax.map(one_rank, (params, los, exts, vrs))

    return partials(model.stacked_params(), *model.meta_arrays(), origins,
                    dirs)


def run_four_chips(sizes: Sizes, seed: int, backend) -> dict:
    """8 ranks trained on one chip and under a 2x2 mesh; binary swap over
    the mesh against the one-device composite."""
    import jax
    import numpy as np
    from repro.launch.mesh import build_mesh

    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, found "
                             f"{len(devices)}")
    check(sizes.ranks % 4 == 0, "ranks must split evenly over 4 chips")
    axes = ("data", "model")
    mesh = build_mesh(np.asarray(devices[:4]).reshape(2, 2), axes)
    results = {}
    _four_chip_phases(sizes, seed, backend, mesh, axes, results)
    return results


def _four_chip_phases(sizes, seed, backend, mesh, axes, results):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec
    from repro import api
    from repro.analysis import CheckContext, capture, run_checks
    from repro.core.render import binary_swap, composite_depth_sort

    key = jax.random.PRNGKey(seed)
    parts = make_partitions(sizes, seed)

    n = sizes.mesh_steps

    def train(mesh_or_none, trainer=None):
        return api.train(parts, sizes.cfg, backend=backend, mesh=mesh_or_none,
                         steps=n, check_every=n, key=key, log_every=1,
                         trainer=trainer)

    mark = compile_mark()
    (m1, i1), t1 = timed(lambda: train(None))
    c1 = compiled_since(mark)
    mark = compile_mark()
    (m4, i4), t4 = timed(lambda: train(mesh))
    c4 = compiled_since(mark)
    _, t4w = timed(lambda: train(mesh, i4["trainer"]))

    def flat(params, p):
        return np.concatenate([np.asarray(x[p], np.float64).ravel()
                               for x in jax.tree.leaves(params)])

    rel = [float(np.linalg.norm(flat(m4.params, p) - flat(m1.params, p))
                 / np.linalg.norm(flat(m1.params, p)))
           for p in range(sizes.ranks)]

    # the compiled per-device train program under the mesh: no collective
    tr = i4["trainer"]
    st = tr.init(key)
    vols = jnp.stack([p.normalized() for p in parts])
    prog = capture(tr._chunk_fn(n), st.params, st.opt, vols, key,
                   jnp.int32(0), st.active, st.loss_ma,
                   name="train_chunk[2x2 mesh]")
    zc = run_checks(prog, CheckContext(backend=tr.backend),
                    checks=["zero_collectives"]).result("zero_collectives")
    n_ops = int(zc.details.get("n_hlo_ops", 0))
    report("mesh_train", {
        "ranks": sizes.ranks, "devices": 4, "steps": n,
        "one_chip_s": t1, "one_chip_compile_s": c1["compile_s"],
        "mesh_cold_s": t4, "mesh_compile_s": c4["compile_s"],
        "mesh_warm_s": t4w, "max_param_rel_diff": max(rel),
        "rtol": MESH_PARAM_RTOL, "collective_free": zc.passed,
        "hlo_ops_walked": n_ops,
        "finite": bool(np.asarray(i4["state"].finite).all())}, results)
    check(zc.passed and n_ops > 0,
          f"mesh train program has collectives: {zc.violations}")
    check(max(rel) <= MESH_PARAM_RTOL,
          f"mesh vs one-chip params differ by {max(rel)} (relative)")
    check(bool(np.asarray(i4["state"].finite).all()), "non-finite ranks")

    # binary swap: each chip holds two x-adjacent ranks (one box), composites
    # them locally, then the 4 chips binary-swap the frame
    images, depths = render_partials(m1, sizes, backend)
    ref = composite_depth_sort(images, depths)
    R = images.shape[1]
    pair_img = jax.vmap(composite_depth_sort)(images.reshape(4, -1, R, 4),
                                              depths.reshape(4, -1, R))
    pair_dep = depths.reshape(4, -1, R).min(axis=1)
    spec = NamedSharding(mesh, PartitionSpec(axes))
    pair_img, pair_dep = jax.device_put((pair_img, pair_dep), spec)
    swap = jax.jit(functools.partial(binary_swap, mesh, axes))
    mark = compile_mark()
    swapped, t_swap = timed(lambda: swap(pair_img, pair_dep))
    c_swap = compiled_since(mark)
    _, t_swap_w = timed(lambda: swap(pair_img, pair_dep))
    err = float(max(np.abs(np.asarray(swapped[d]) - np.asarray(ref)).max()
                    for d in range(4)))
    report("binary_swap", {
        "frame": f"{sizes.swap_frame}x{sizes.swap_frame}", "devices": 4,
        "cold_s": t_swap, "compile_s": c_swap["compile_s"],
        "warm_s": t_swap_w, "max_abs_diff": err, "atol": SWAP_ATOL,
        "mean_alpha": float(np.asarray(ref)[:, 3].mean())}, results)
    check(np.isfinite(np.asarray(swapped)).all(), "non-finite swapped frame")
    check(err <= SWAP_ATOL, f"binary swap differs from depth sort by {err}")


# --------------------------------------------------------------------------- #
# Entry point
# --------------------------------------------------------------------------- #
def _fail(msg: str, code: int = 2) -> int:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", help="also write the phase records as JSON")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro" / "__init__.py").is_file():
        return _fail(f"no repro package under {ROOT / 'src'}: run from a "
                     "checkout of this repository")
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return _fail(f"needs a TPU; JAX found {dev.platform!r} "
                     f"({dev.device_kind})")
    from repro import backends

    backend = backends.resolve("auto")
    impls = op_impls(backend)
    print(f"device_kind={dev.device_kind} platform={dev.platform} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    print(f"backend=auto -> {backend.name} (kind={backend.kind}, "
          f"interpret={backend.interpret})", flush=True)
    for op, impl in impls.items():
        print(f"op {op}: {impl}", flush=True)
    print(f"compile_cache={jax.config.jax_compilation_cache_dir}",
          flush=True)
    if "pallas-interpret" in impls.values():
        return _fail("an op would run as an interpreted Pallas kernel", 1)

    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            results = run_four_chips(FULL, args.seed, backend)
        else:
            results = run_one_chip(FULL, args.seed, backend)
    except Exception:                     # noqa: BLE001 - reported, exit != 0
        traceback.print_exc()
        return _fail("a phase failed", 1)
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"total_s={time.perf_counter() - t0}", flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(
            {"device_kind": dev.device_kind, "count": len(devices),
             "backend": backend.name, "ops": impls,
             "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
             "phases": results}, indent=1, default=str))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
