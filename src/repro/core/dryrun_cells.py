"""DVNR production dry-run cells (the paper's own technique on the target mesh).

Two cells per mesh:
  - ``train``:  one DVNR training step; P = mesh.size partitions (256^3 voxels
    + 1 ghost layer each), one INR per device via shard_map. The compiled HLO
    must contain ZERO collectives — this is the paper's central claim
    (communication-free model parallelism) and is asserted here.
  - ``render``: the sort-last production renderer — per-device INR ray-march
    (sample streaming) + binary-swap compositing. log2(P) ppermute rounds +
    one tiled all-gather are the ONLY collectives.

Roofline terms come from the same post-SPMD HLO analysis as the LM cells.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.dvnr import PRODUCTION, DVNRConfig
from repro.core.inr import init_inr, param_count
from repro.core.render import default_tf, make_distributed_render_step, make_rays, Camera
from repro.core.sampling import step_seeds
from repro.core.trainer import DVNRTrainer
from repro.launch.mesh import make_production_mesh
from repro.utils import hw
from repro.utils.hlo import analyze_hlo

# Production partition: 256^3 owned voxels + 1 ghost layer (paper's CloverLeaf
# strong-scaling per-rank size class).
PART_N = 256
GHOST = 1
FRAME_W = FRAME_H = 512          # 262144 rays; divisible by 512 devices
N_SAMPLES = 64


def _mlp_params(cfg: DVNRConfig) -> int:
    return param_count(cfg) - cfg.n_levels * cfg.table_size * cfg.n_features_per_level


def _enc_flops_fwd(cfg: DVNRConfig) -> float:
    """Per-sample hash-encoding forward FLOPs: per level, 8-corner trilerp of F
    features (7 lerps x 2 flops x F) + corner-weight/hash arithmetic (~36)."""
    return cfg.n_levels * (14.0 * cfg.n_features_per_level + 36.0)


def model_flops_train(cfg: DVNRConfig, n_partitions: int) -> float:
    """Analytic useful FLOPs of one global DVNR training step.

    Per sample: MLP fwd = 2*mlp_params, train = 3x fwd (fwd + 2x bwd);
    encoding fwd+bwd ~ 3x; plus trilinear target sampling (~28 flops) and the
    Adam update (~10 flops/param)."""
    per_sample = 6.0 * _mlp_params(cfg) + 3.0 * _enc_flops_fwd(cfg) + 28.0
    per_part = cfg.batch_size * per_sample + 10.0 * param_count(cfg)
    return n_partitions * per_part


def model_flops_render(cfg: DVNRConfig, n_partitions: int, n_rays: int,
                       n_samples: int) -> float:
    """Analytic useful FLOPs of one distributed render: every device infers
    R*S samples (2*mlp_params + enc fwd) + TF/over compositing (~40/sample)."""
    per_sample = 2.0 * _mlp_params(cfg) + _enc_flops_fwd(cfg) + 40.0
    return n_partitions * n_rays * n_samples * per_sample


def _sds_stacked(tree, mesh):
    """ShapeDtypeStructs with the leading (P,...) dim sharded over ALL axes."""
    shard = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=shard), tree)


def _sds_rep(tree, mesh):
    shard = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=shard), tree)


def _roofline_record(compiled, mesh, model_flops_global: float, meta: dict) -> dict:
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    an = analyze_hlo(compiled.as_text(), mesh.size)
    # the dry run models the v5e it is sized for, whatever device it runs on
    chip = hw.peaks(hw.V5E)
    terms = {
        "compute_s": an.flops / chip.bf16_flops,
        "memory_s": an.hbm_bytes / chip.hbm_bytes_per_s,
        "collective_s": an.collective_wire_bytes / chip.ici_link_bytes_per_s,
    }
    dominant = max(terms, key=terms.get)
    mf_dev = model_flops_global / mesh.size
    rec = dict(
        status="ok",
        devices=mesh.size,
        memory_analysis={
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
            "alias_bytes": mem.alias_size_in_bytes,
        } if mem is not None else None,
        cost_analysis={"flops": cost.get("flops"),
                       "bytes_accessed": cost.get("bytes accessed")} if cost else None,
        hlo_flops_per_device=an.flops,
        hlo_bytes_per_device=an.hbm_bytes,
        collective_wire_bytes_per_device=an.collective_wire_bytes,
        collective_breakdown=an.collective_summary(),
        roofline=dict(terms, dominant=dominant,
                      step_time_s=max(terms.values()),
                      roofline_fraction=(
                          mf_dev / chip.bf16_flops / max(max(terms.values()), 1e-30))),
        model_flops_global=model_flops_global,
        model_flops_per_device=mf_dev,
        useful_flops_ratio=mf_dev / max(an.flops, 1.0),
    )
    rec.update(meta)
    return rec


def build_train_cell(mesh, cfg: DVNRConfig = PRODUCTION, *, impl: str = "fused"):
    """Lowerable DVNR train step + abstract args for the production mesh."""
    n = mesh.size
    trainer = DVNRTrainer(cfg, n, mesh=mesh, impl=impl, ghost=GHOST)

    params_sds = jax.eval_shape(
        lambda: jax.vmap(lambda k: init_inr(cfg, k))(
            jax.random.split(jax.random.PRNGKey(0), n)))
    opt_sds = jax.eval_shape(lambda p: jax.vmap(trainer.adam.init)(p), params_sds)
    keys_sds = jax.eval_shape(
        lambda: step_seeds(jax.random.PRNGKey(0), 0, n))
    side = PART_N + 2 * GHOST
    vols_sds = jax.ShapeDtypeStruct((n, side, side, side), jnp.float32)
    active_sds = jax.ShapeDtypeStruct((n,), jnp.bool_)
    lossma_sds = jax.ShapeDtypeStruct((n,), jnp.float32)

    args = (_sds_stacked(params_sds, mesh), _sds_stacked(opt_sds, mesh),
            _sds_stacked(vols_sds, mesh), _sds_stacked(keys_sds, mesh),
            _sds_stacked(active_sds, mesh), _sds_stacked(lossma_sds, mesh))
    return trainer._step_fn, args, {
        "arch": "dvnr", "shape": f"train_p{PART_N}",
        "partition_voxels": PART_N ** 3,
        "inr_params_per_partition": param_count(cfg),
        "params": mesh.size * param_count(cfg),
        "active_params": mesh.size * param_count(cfg),
        "batch_per_partition": cfg.batch_size,
    }


def build_render_cell(mesh, cfg: DVNRConfig = PRODUCTION, *, impl: str = "ref"):
    n = mesh.size
    step = make_distributed_render_step(cfg, mesh, n_samples=N_SAMPLES, impl=impl)
    params_sds = jax.eval_shape(
        lambda: jax.vmap(lambda k: init_inr(cfg, k))(
            jax.random.split(jax.random.PRNGKey(0), n)))
    R = FRAME_W * FRAME_H
    args = (
        _sds_stacked(params_sds, mesh),
        _sds_stacked(jax.ShapeDtypeStruct((n, 3), jnp.float32), mesh),   # parts_lo
        _sds_stacked(jax.ShapeDtypeStruct((n, 3), jnp.float32), mesh),   # parts_ext
        _sds_stacked(jax.ShapeDtypeStruct((n, 2), jnp.float32), mesh),   # vranges
        _sds_rep(jax.ShapeDtypeStruct((R, 3), jnp.float32), mesh),       # origins
        _sds_rep(jax.ShapeDtypeStruct((R, 3), jnp.float32), mesh),       # dirs
        _sds_rep(jax.ShapeDtypeStruct((64, 4), jnp.float32), mesh),      # tf
        _sds_rep(jax.ShapeDtypeStruct((2,), jnp.float32), mesh),         # grange
    )
    return step, args, {
        "arch": "dvnr", "shape": f"render_{FRAME_W}x{FRAME_H}",
        "rays": R, "samples_per_ray": N_SAMPLES,
        "inr_params_per_partition": param_count(cfg),
        "params": mesh.size * param_count(cfg),
        "active_params": mesh.size * param_count(cfg),
    }


def run_dvnr_cell(kind: str, mesh_name: str, results_root: Path,
                  cfg: DVNRConfig = PRODUCTION) -> dict:
    """Lower + compile the DVNR cell on the production mesh; save the record."""
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    t0 = time.time()
    if kind == "train":
        fn, args, meta = build_train_cell(mesh, cfg)
        mf = model_flops_train(cfg, mesh.size)
        jitted = fn                      # trainer._step_fn is already jitted
    else:
        fn, args, meta = build_render_cell(mesh, cfg)
        mf = model_flops_render(cfg, mesh.size, meta["rays"], N_SAMPLES)
        jitted = jax.jit(fn)

    with mesh:
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    rec = _roofline_record(compiled, mesh, mf, meta)
    rec.update(mesh=mesh_name, lower_s=round(t_lower, 2),
               compile_s=round(t_compile, 2))

    an_comms = rec["collective_wire_bytes_per_device"]
    if kind == "train":
        # The paper's claim: the distributed training step is communication-free.
        rec["zero_communication"] = bool(an_comms == 0)
        assert an_comms == 0, (
            f"DVNR train step must be collective-free, found {an_comms} wire "
            f"bytes: {rec['collective_breakdown']}")

    d = Path(results_root) / mesh_name
    d.mkdir(parents=True, exist_ok=True)
    (d / f"dvnr__{kind}.json").write_text(json.dumps(rec, indent=1))
    return rec
