"""repro.analysis: known-bad fixtures for every registered check (each check
must FAIL on a program built to violate exactly its invariant), the closed-form
vs traced VMEM parity, the trainer build-time rejection of over-budget in-op
sampling, the ``static_checks`` config hook, the per-kernel ``vmem_footprint``
hooks, and the ``python -m repro.analysis`` CLI."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import backends
from repro.analysis import (CheckContext, StaticCheckError, assert_clean,
                            available_checks, capture, estimate_jaxpr,
                            run_checks)
from repro.configs import dvnr as dvnr_cfg

SDS = jax.ShapeDtypeStruct


# --------------------------------------------------------------------------- #
# registry / report plumbing
# --------------------------------------------------------------------------- #

def test_registry_has_the_seven_checks():
    assert list(available_checks()) == [
        "zero_collectives", "vmem_budget", "precision_flow",
        "rng_gather_placement", "donation", "grid_write_safety",
        "hbm_traffic"]


def test_static_check_error_is_an_assertion_error():
    assert issubclass(StaticCheckError, AssertionError)


def test_max_level_caps_skip_expensive_checks():
    prog = capture(lambda x: x + 1.0, SDS((4,), jnp.float32))
    rep = run_checks(prog, CheckContext(), max_level="jaxpr")
    assert rep.passed
    assert rep.result("zero_collectives").skipped    # needs hlo
    assert rep.result("donation").skipped            # needs lowered
    assert "PASS" in rep.render() or "SKIP" in rep.render()


# --------------------------------------------------------------------------- #
# (1) zero_collectives — known-bad: a psum under shard_map
# --------------------------------------------------------------------------- #

def test_zero_collectives_flags_psum():
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("x",))
    dirty = jax.jit(shard_map(lambda v: jax.lax.psum(v, "x"), mesh=mesh,
                              in_specs=P("x"), out_specs=P()))
    with pytest.raises(StaticCheckError, match="psum|all-reduce"):
        assert_clean(dirty, jnp.ones((4,)), checks=["zero_collectives"])


def test_zero_collectives_clean_and_not_vacuous():
    rep = assert_clean(lambda x: jnp.sin(x) @ x, jnp.ones((4, 4)),
                       checks=["zero_collectives"])
    n_ops = int(rep.result("zero_collectives").details["note"].split()[0])
    assert n_ops > 0                                  # the walk saw the module


# --------------------------------------------------------------------------- #
# (2) vmem_budget — known-bad: a pallas_call over an explicit tiny budget
# --------------------------------------------------------------------------- #

def test_vmem_budget_flags_over_budget_kernel():
    from repro.kernels.hash_encoding.ops import hash_encode

    coords = SDS((128, 3), jnp.float32)
    tables = SDS((2, 256, 2), jnp.float32)
    with pytest.raises(StaticCheckError) as e:
        assert_clean(lambda c, t: hash_encode(c, t, (4, 8), impl="pallas"),
                     coords, tables, checks=["vmem_budget"],
                     vmem_limit_bytes=1024)
    msg = str(e.value)
    assert "exceeds" in msg and "budget" in msg
    assert "x2" in msg or "x1" in msg                 # per-buffer breakdown rows


def test_vmem_budget_skips_without_a_budget():
    from repro.kernels.hash_encoding.ops import hash_encode

    rep = assert_clean(lambda c, t: hash_encode(c, t, (4, 8), impl="pallas"),
                       SDS((128, 3), jnp.float32), SDS((2, 256, 2), jnp.float32),
                       checks=["vmem_budget"])       # no backend, no limit
    res = rep.result("vmem_budget")
    assert res.skipped and "no VMEM budget" in res.skip_reason
    assert res.details["footprints"]                 # estimator still ran


# --------------------------------------------------------------------------- #
# (3) precision_flow — known-bad: f32 matmul under a bf16 policy, and a
#     bf16 param output with no f32 master shadow
# --------------------------------------------------------------------------- #

def test_precision_flow_flags_f32_dot_under_bf16():
    with pytest.raises(StaticCheckError, match="bfloat16"):
        assert_clean(lambda x, w: x @ w, jnp.ones((8, 8)), jnp.ones((8, 8)),
                     checks=["precision_flow"], precision="bf16")


def test_precision_flow_flags_missing_master_shadow():
    x = jnp.ones((4, 4), jnp.bfloat16)
    with pytest.raises(StaticCheckError, match="master"):
        assert_clean(lambda w: w @ w, x, checks=["precision_flow"],
                     precision="bf16")


def test_precision_flow_clean_with_shadow():
    x = jnp.ones((4, 4), jnp.bfloat16)
    rep = assert_clean(lambda w: (w @ w, (w @ w).astype(jnp.float32)), x,
                       checks=["precision_flow"], precision="bf16")
    assert int(rep.result("precision_flow").details["note"].split()[0]) >= 1


# --------------------------------------------------------------------------- #
# (4) rng_gather_placement — known-bad: host-side RNG / missing pallas_call
# --------------------------------------------------------------------------- #

def test_rng_placement_flags_host_rng():
    with pytest.raises(StaticCheckError, match="RNG primitive"):
        assert_clean(lambda k: jax.random.uniform(k, (8,)),
                     jax.random.PRNGKey(0), checks=["rng_gather_placement"],
                     fuse_sampling=True)


def test_rng_placement_flags_missing_pallas_and_gather():
    with pytest.raises(StaticCheckError, match="no pallas_call"):
        assert_clean(lambda v, i: v[i], jnp.ones((16,)),
                     jnp.arange(4), checks=["rng_gather_placement"],
                     fuse_sampling=True, expect_pallas=True)


def test_rng_placement_skips_when_not_fused():
    rep = assert_clean(lambda k: jax.random.uniform(k, (8,)),
                       jax.random.PRNGKey(0), checks=["rng_gather_placement"])
    assert rep.result("rng_gather_placement").skipped


# --------------------------------------------------------------------------- #
# (5) donation — known-bad: donated arg that lowering cannot alias
# --------------------------------------------------------------------------- #

def test_donation_flags_unaliased_donation():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")               # jax's own donation warn
        with pytest.raises(StaticCheckError, match="not aliased"):
            assert_clean(lambda x: jnp.zeros((x.shape[0] + 1,), x.dtype),
                         jnp.ones((4,)), checks=["donation"],
                         donate_argnums=(0,))


def test_donation_passes_when_aliased():
    rep = assert_clean(lambda x: x + 1.0, jnp.ones((4,)), checks=["donation"],
                       donate_argnums=(0,))
    assert "1/1" in rep.result("donation").details["note"]


# --------------------------------------------------------------------------- #
# closed-form sampling footprint == traced estimator
# --------------------------------------------------------------------------- #

def test_closed_form_sampling_footprint_matches_traced():
    from repro.analysis import build_trainer, trainer_programs
    from repro.kernels.fused_train_step import ops as fts_ops

    cfg = dvnr_cfg.SMOKE
    tr = build_trainer(cfg, backend="pallas", n_partitions=2,
                       local_shape=(10, 10, 10), ghost=1)
    assert tr.fuse_sampling
    (step_prog, _), *_rest = trainer_programs(tr, n_steps=2)
    traced = max(f.total_bytes for f in estimate_jaxpr(step_prog.jaxpr))
    closed = fts_ops.sampling_vmem_footprint(
        tr.volume_shape, fts_ops._cfg_state_shapes(cfg),
        tr.precision.param_dtype, tr.precision.needs_master,
        P=tr.P).total_bytes
    assert traced == closed


# --------------------------------------------------------------------------- #
# trainer build-time rejection + static_checks config hook
# --------------------------------------------------------------------------- #

def test_trainer_rejects_over_budget_sampling_at_build_time():
    from repro.core.trainer import DVNRTrainer

    with pytest.raises(ValueError) as e:
        DVNRTrainer(dvnr_cfg.PRODUCTION, 1, impl="pallas",
                    volume_shape=(258, 258, 258))
    msg = str(e.value)
    assert "VMEM" in msg and "exceeds" in msg
    assert "fuse_sampling='off'" in msg               # actionable escape hatch
    assert "volume" in msg                            # per-buffer breakdown


def _tiny_vmem_backend():
    # same pallas backend, absurd 1 KiB budget: every kernel is "over budget"
    return dataclasses.replace(backends.resolve("pallas"),
                               name="pallas_tiny_vmem",
                               vmem_limit_bytes=1024)


def test_static_checks_error_mode_raises_on_violation():
    from repro.core.trainer import DVNRTrainer

    cfg = dvnr_cfg.SMOKE.replace(fuse_sampling="off", static_checks="error")
    with pytest.raises(StaticCheckError, match="vmem_budget"):
        DVNRTrainer(cfg, 2, impl=_tiny_vmem_backend(),
                    volume_shape=(12, 12, 12))


def test_static_checks_warn_mode_warns_and_builds():
    from repro.core.trainer import DVNRTrainer

    cfg = dvnr_cfg.SMOKE.replace(fuse_sampling="off", static_checks="warn")
    with pytest.warns(UserWarning, match="static checks failed"):
        tr = DVNRTrainer(cfg, 2, impl=_tiny_vmem_backend(),
                         volume_shape=(12, 12, 12))
    assert tr is not None                             # warn mode still builds


def test_static_checks_error_mode_passes_on_clean_config():
    from repro.core.trainer import DVNRTrainer

    cfg = dvnr_cfg.SMOKE.replace(static_checks="error")
    tr = DVNRTrainer(cfg, 2, impl="pallas", volume_shape=(12, 12, 12))
    rep = tr.run_static_checks(strict=True)
    assert rep.passed


# --------------------------------------------------------------------------- #
# per-kernel vmem_footprint hooks
# --------------------------------------------------------------------------- #

def test_kernel_vmem_footprint_hooks():
    from repro.kernels.composite.ops import vmem_footprint as comp_fp
    from repro.kernels.flash_attention.ops import vmem_footprint as fa_fp
    from repro.kernels.fused_mlp.ops import vmem_footprint as mlp_fp
    from repro.kernels.hash_encoding.ops import vmem_footprint as he_fp

    coords, tables = SDS((128, 3), jnp.float32), SDS((2, 256, 2), jnp.float32)
    fps = he_fp(coords, tables, (4, 8), impl="pallas")
    assert fps and all(f.total_bytes > 0 for f in fps)
    assert he_fp(coords, tables, (4, 8), impl="ref") == []

    x = SDS((128, 16), jnp.float32)
    ws = [SDS((16, 16), jnp.float32), SDS((16, 4), jnp.float32)]
    assert mlp_fp(x, ws, impl="pallas")

    assert comp_fp(SDS((64, 32, 4), jnp.float32), impl="pallas")

    q = SDS((1, 128, 2, 16), jnp.float32)
    fa = fa_fp(q, q, q, impl="pallas")
    assert fa and all(f.total_bytes > 0 for f in fa)
    assert fa[0].breakdown().strip()                  # per-buffer rows render


# --------------------------------------------------------------------------- #
# CLI
# --------------------------------------------------------------------------- #

def test_cli_list_checks(capsys):
    from repro.analysis.__main__ import main

    assert main(["--list-checks"]) == 0
    out = capsys.readouterr().out
    for name in available_checks():
        assert name in out


def test_cli_smoke_ref_jaxpr_passes(capsys):
    from repro.analysis.__main__ import main

    assert main(["--config", "smoke", "--backend", "ref",
                 "--max-level", "jaxpr"]) == 0
    assert "PASS" in capsys.readouterr().out


def test_cli_passes_production256_on_pallas(capsys):
    """The brick-tiled sampling kernel turned the production256 gate green:
    the 256^3 partition streams through VMEM brick by brick (and the III-B
    strong-scaled PRODUCTION256 table keeps the state groups small), so the
    vmem_budget check passes — the CI repro-lint step runs this very config
    at --max-level lowered on the pallas leg."""
    from repro.analysis.__main__ import main

    assert main(["--config", "production256", "--backend", "pallas",
                 "--max-level", "jaxpr"]) == 0
    out = capsys.readouterr().out
    assert "PASS" in out and "REJECTED" not in out


def test_cli_production256_pinned_negative_control(capsys):
    """Forcing sampling_brick='pinned' on the same 256^3 config must still be
    REJECTED at trainer build time — the gate is non-vacuous: the tiled
    layout, not a loosened budget, is what makes production256 pass."""
    from repro.core.trainer import DVNRTrainer

    with pytest.raises(ValueError) as e:
        DVNRTrainer(dvnr_cfg.PRODUCTION256.replace(sampling_brick="pinned"),
                    1, impl="pallas", volume_shape=(258, 258, 258))
    msg = str(e.value)
    assert "exceeds" in msg and "volume" in msg
    assert "sampling_brick='auto'" in msg             # actionable escape hatch
