"""End-to-end DVNR training: multi-partition INR compression of a synthetic
volume converges to reasonable PSNR with zero inter-partition communication."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.configs import dvnr as dvnr_cfg
from repro.core.trainer import DVNRTrainer, adaptive_config, train_iterations
from repro.data.volume import make_partition, partition_grid


def _partition_volumes(kind="cloverleaf", grid=(2, 2, 2), local=(16, 16, 16), t=0.3):
    P = int(np.prod(grid))
    parts = [make_partition(kind, p, grid, local, t) for p in range(P)]
    vols = jnp.stack([p.normalized() for p in parts])
    return parts, vols


def test_train_iterations_formula():
    cfg = dvnr_cfg.SMOKE.replace(batch_size=512, epochs=4, n_train_min=10)
    assert train_iterations(cfg, 16**3) == max(10, -(-16**3 // 512) * 4)
    assert train_iterations(cfg, 1) == 10


def test_adaptive_config_strong_scaling():
    cfg = dvnr_cfg.PRODUCTION
    full = adaptive_config(cfg, 1 << 24, 1 << 24)
    quarter = adaptive_config(cfg, 1 << 22, 1 << 24)
    assert full.table_size == cfg.table_size
    assert quarter.table_size == cfg.table_size // 4
    assert quarter.resolved_base_resolution <= full.resolved_base_resolution
    tiny = adaptive_config(cfg, 1, 1 << 30)
    assert tiny.table_size == 1 << cfg.t_min_log2   # T_min floor


def test_dvnr_training_converges():
    cfg = dvnr_cfg.SMOKE.replace(batch_size=2048, n_levels=3, log2_hashmap_size=10,
                                 n_neurons=16, n_hidden_layers=2, lrate=1e-2)
    parts, vols = _partition_volumes()
    trainer = DVNRTrainer(cfg, n_partitions=vols.shape[0])
    state = trainer.init(jax.random.PRNGKey(0))
    e0 = trainer.evaluate(state, vols, (16, 16, 16))
    state, hist = trainer.train(state, vols, steps=150, key=jax.random.PRNGKey(1))
    e1 = trainer.evaluate(state, vols, (16, 16, 16))
    assert np.isfinite(e1["psnr"])
    assert e1["psnr"] > e0["psnr"] + 5.0, (e0, e1)
    assert e1["psnr"] > 25.0, e1


def test_boundary_loss_improves_boundary_accuracy():
    """Paper Fig. 14: lambda > 0 improves cross-partition boundary agreement.

    One seed's gap moves by more than the effect at 200 steps, so four
    independent replicas of the 2-partition run (one trainer, 8 partitions:
    each gets its own init and sample stream) train 600 steps, and the
    mean gaps must differ by a clear margin."""
    parts, vols = _partition_volumes(grid=(2, 1, 1), local=(16, 16, 16))
    n_rep = 4

    def run(lam):
        cfg = dvnr_cfg.SMOKE.replace(batch_size=2048, n_levels=3,
                                     log2_hashmap_size=10, n_neurons=16,
                                     n_hidden_layers=2, lrate=1e-2,
                                     boundary_lambda=lam)
        tr = DVNRTrainer(cfg, n_partitions=2 * n_rep)
        st = tr.init(jax.random.PRNGKey(0))
        st, _ = tr.train(st, jnp.concatenate([vols] * n_rep), steps=600,
                         key=jax.random.PRNGKey(1))
        # evaluate on the shared boundary face (x=1 of part0 vs x=0 of part1)
        yz = jnp.stack(jnp.meshgrid(jnp.linspace(0.01, 0.99, 24),
                                    jnp.linspace(0.01, 0.99, 24),
                                    indexing="ij"), -1).reshape(-1, 2)
        c0 = jnp.concatenate([jnp.full((yz.shape[0], 1), 1.0), yz], axis=1)
        c1 = jnp.concatenate([jnp.full((yz.shape[0], 1), 0.0), yz], axis=1)
        gaps = []
        for r in range(n_rep):
            p0 = jax.tree.map(lambda t: t[2 * r], st.params)
            p1 = jax.tree.map(lambda t: t[2 * r + 1], st.params)
            v0 = api.DVNRModel(cfg, p0).apply(c0)
            v1 = api.DVNRModel(cfg, p1).apply(c1)
            # de-normalize to raw field values before comparing partitions
            r0 = v0 * (parts[0].vmax - parts[0].vmin) + parts[0].vmin
            r1 = v1 * (parts[1].vmax - parts[1].vmin) + parts[1].vmin
            gaps.append(float(jnp.mean(jnp.square(r0 - r1))))
        return float(np.mean(gaps))

    gap_nolam = run(0.0)
    gap_lam = run(0.15)
    assert gap_lam < 0.8 * gap_nolam, (gap_lam, gap_nolam)


def test_weight_caching_warm_start_speeds_convergence():
    """Paper III-E: warm start from t-1 weights reaches target loss faster."""
    cfg = dvnr_cfg.SMOKE.replace(batch_size=2048, n_levels=3, log2_hashmap_size=10,
                                 n_neurons=16, n_hidden_layers=2, lrate=5e-3)
    _, vols_t0 = _partition_volumes(t=0.30)
    _, vols_t1 = _partition_volumes(t=0.32)     # adjacent timestep
    tr = DVNRTrainer(cfg, n_partitions=vols_t0.shape[0])

    st = tr.init(jax.random.PRNGKey(0))
    st, _ = tr.train(st, vols_t0, steps=200, key=jax.random.PRNGKey(1))

    warm = tr.init(jax.random.PRNGKey(2), cached_params=st.params)
    cold = tr.init(jax.random.PRNGKey(2))
    warm, _ = tr.train(warm, vols_t1, steps=30, key=jax.random.PRNGKey(3))
    cold, _ = tr.train(cold, vols_t1, steps=30, key=jax.random.PRNGKey(3))
    p_warm = tr.evaluate(warm, vols_t1, (16, 16, 16))["psnr"]
    p_cold = tr.evaluate(cold, vols_t1, (16, 16, 16))["psnr"]
    assert p_warm > p_cold + 3.0, (p_warm, p_cold)
