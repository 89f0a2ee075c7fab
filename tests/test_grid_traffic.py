"""Grid write-race/coverage detector + HBM-traffic model + analysis lockfile.

Every check gets a committed known-bad fixture (a pallas_call built to violate
exactly its invariant), the in-repo kernels must pass both checks on both
backends, the production256 brick-tiled owner sweep is proven statically, and
the lockfile round-trips: write -> verify clean, hand-edit -> readable drift.
"""
import json

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro.analysis import (CheckContext, StaticCheckError, assert_clean,
                            run_checks)
from repro.analysis.programs import (cached_render_program, get_config,
                                     render_program, serving_tick_program)

SDS = jax.ShapeDtypeStruct


# --------------------------------------------------------------------------- #
# known-bad fixtures (committed negative controls)
# --------------------------------------------------------------------------- #
def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _overstream_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def _racing_call(x):
    """Output index map i % 2 over grid 4: block 0 is revisited AFTER block 1
    was written — a write race on real hardware."""
    return pl.pallas_call(
        _copy_kernel, grid=(4,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i % 2, 0)),
        out_shape=SDS((16, 128), jnp.float32), interpret=True)(x)


def _undeclared_multi_call(x):
    """Constant output window over grid 2: two consecutive writers with no
    declared accumulate/last_write discipline."""
    return pl.pallas_call(
        _copy_kernel, grid=(2,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
        out_shape=SDS((8, 128), jnp.float32), interpret=True)(x)


def _uncovered_call(x):
    """Grid 2 writing into a 4-block output: half the output is never
    written and keeps uninitialized memory."""
    return pl.pallas_call(
        _copy_kernel, grid=(2,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
        out_shape=SDS((32, 128), jnp.float32), interpret=True)(x)


def _overstream_call(x):
    """Input re-fetched i % 2 over grid 8: 8 fetches for 2 distinct blocks =
    4x the ideal input traffic (declared refetch, so only hbm_traffic
    fires)."""
    return pl.pallas_call(
        _overstream_kernel, grid=(8,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i % 2, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
        out_shape=SDS((64, 128), jnp.float32), interpret=True)(x)


def test_grid_check_flags_write_race():
    with pytest.raises(StaticCheckError, match="WRITE RACE"):
        assert_clean(_racing_call, SDS((32, 128), jnp.float32),
                     checks=["grid_write_safety"])


def test_grid_check_flags_undeclared_multi_writer():
    with pytest.raises(StaticCheckError, match="undeclared multi-writer"):
        assert_clean(_undeclared_multi_call, SDS((16, 128), jnp.float32),
                     checks=["grid_write_safety"])


def test_grid_check_flags_uncovered_output():
    with pytest.raises(StaticCheckError, match="uncovered output"):
        assert_clean(_uncovered_call, SDS((16, 128), jnp.float32),
                     checks=["grid_write_safety"])


def test_grid_check_flags_undeclared_input_refetch():
    # the overstream fixture WITHOUT its refetch declaration
    with pytest.raises(StaticCheckError, match="undeclared input re-fetch"):
        assert_clean(lambda x: pl.pallas_call(
            _copy_kernel, grid=(4,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i % 2, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=SDS((32, 128), jnp.float32), interpret=True)(x),
            SDS((16, 128), jnp.float32), checks=["grid_write_safety"])


def test_traffic_check_flags_overstreaming():
    from repro.analysis.grid import register_discipline

    # declare the refetch so grid_write_safety is clean and the failure is
    # isolated to the traffic model (8 fetches / 2 distinct = 4.00x ideal in)
    register_discipline("_overstream_kernel", input_refetch=("in[0]",))
    with pytest.raises(StaticCheckError, match="ideal traffic"):
        assert_clean(_overstream_call, SDS((16, 128), jnp.float32),
                     checks=["grid_write_safety", "hbm_traffic"])


def test_traffic_factor_none_is_report_only():
    from repro.analysis.grid import register_discipline

    register_discipline("_overstream_kernel", input_refetch=("in[0]",),
                        traffic_factor=None)
    try:
        rep = assert_clean(_overstream_call, SDS((16, 128), jnp.float32),
                           checks=["hbm_traffic"])
        (kt,) = rep.result("hbm_traffic").details["traffic"]
        # 8 fetches for 2 distinct input blocks + ideal output traffic
        # = 1.60x overall: over the default 1.25 cap, reported but not failed
        assert kt.streaming_factor > 1.5
    finally:
        register_discipline("_overstream_kernel", input_refetch=("in[0]",))


# --------------------------------------------------------------------------- #
# in-repo kernels pass on both backends; declarations are load-bearing
# --------------------------------------------------------------------------- #
GRID_CHECKS = ["grid_write_safety", "hbm_traffic"]


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("builder", [render_program, cached_render_program,
                                     serving_tick_program])
def test_render_serving_programs_pass_grid_and_traffic(builder, backend):
    cfg, _shape = get_config("smoke")
    program, ctx = builder(cfg, backend=backend)
    rep = run_checks(program, ctx, checks=GRID_CHECKS, max_level="jaxpr")
    assert rep.passed, rep.render()


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_train_programs_pass_grid_and_traffic(backend):
    from repro.analysis.programs import build_trainer, trainer_programs

    cfg, shape = get_config("smoke")
    trainer = build_trainer(cfg, backend=backend, local_shape=shape)
    for program, ctx in trainer_programs(trainer):
        rep = run_checks(program, ctx, checks=GRID_CHECKS, max_level="jaxpr")
        assert rep.passed, rep.render()


def test_production256_owner_sweep_proven_statically():
    """The PR 8 invariant — the brick-tiled sampling kernel's owner sweep
    visits EVERY volume brick (each corner voxel banked exactly once) — as a
    static full-coverage proof over the real production256 grid."""
    from repro.analysis.programs import build_trainer, trainer_programs

    cfg, shape = get_config("production256")
    trainer = build_trainer(cfg, backend="pallas", local_shape=shape)
    program, ctx = trainer_programs(trainer)[0]         # train_step
    rep = run_checks(program, ctx, checks=["grid_write_safety"],
                     max_level="jaxpr")
    assert rep.passed, rep.render()
    kernels = rep.result("grid_write_safety").details["kernels"]
    (tiled,) = [ka for name, ka in kernels.items()
                if "tiled_sampling" in name]
    (vol,) = [a for a in tiled.operands if a.name == "in[0]"]
    assert vol.distinct == vol.n_blocks_total > 1       # every brick visited
    assert vol.fetches == vol.distinct                  # each DMA'd once


def test_flash_attention_gqa_grid_discipline():
    """GQA flash attention: k/v re-fetch per query tile is declared, the
    last-write output discipline holds, traffic is report-only."""
    from repro.kernels.flash_attention.kernel import flash_attention_bhsd

    q = SDS((1, 4, 512, 64), jnp.float32)
    kv = SDS((1, 2, 512, 64), jnp.float32)
    rep = assert_clean(lambda q, k, v: flash_attention_bhsd(q, k, v, interpret=True),
                       q, kv, kv, checks=GRID_CHECKS)
    (kt,) = rep.result("hbm_traffic").details["traffic"]
    assert kt.intensity > 10                            # compute-bound regime


def test_batched_kernel_inherits_base_discipline():
    """vmap of a pallas_call renames the kernel <name>_batched; the base
    kernel's declaration must carry over (the render path vmaps the hash
    encode over partitions)."""
    from repro.analysis.grid import get_discipline

    base = get_discipline("_encode_kernel")
    assert get_discipline("_encode_kernel_batched").input_refetch == \
        base.input_refetch


# --------------------------------------------------------------------------- #
# serving-stack precision flow (+ bf16 negative control)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("builder", [render_program, serving_tick_program])
def test_serving_precision_flow_passes(builder):
    cfg, _shape = get_config("smoke")
    program, ctx = builder(cfg, backend="pallas")
    assert ctx.precision is not None
    assert ctx.expect_master_state is False
    rep = run_checks(program, ctx, checks=["precision_flow"],
                     max_level="jaxpr")
    assert rep.passed, rep.render()
    assert rep.result("precision_flow").details["n_matmuls"] > 0


def test_render_bf16_negative_control():
    """A render traced under the f32 policy must FAIL a bf16 expectation —
    the serving precision check is not vacuous."""
    from repro.precision import resolve_precision

    cfg, _shape = get_config("smoke")
    program, ctx = render_program(cfg, backend="pallas")
    bf16_ctx = CheckContext(backend=ctx.backend,
                            precision=resolve_precision("bf16"),
                            expect_master_state=False)
    rep = run_checks(program, bf16_ctx, checks=["precision_flow"],
                     max_level="jaxpr")
    assert not rep.passed


# --------------------------------------------------------------------------- #
# BrickCache decode: closed-form vs traced VMEM parity
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("config", ["smoke", "production256"])
def test_brickcache_decode_vmem_parity(config):
    from repro.serving.cache import BrickCache

    cfg, _shape = get_config(config)
    cache = BrickCache(cfg, backend="pallas", grid_shape=(16, 16, 16),
                       brick_edge=8)
    closed = cache.decode_vmem_closed_form(n_bricks=3)
    traced = cache.decode_vmem_footprint(n_bricks=3)
    assert [fp.kernel for fp in closed] == [fp.kernel for fp in traced]
    for c, t in zip(closed, traced):
        assert c.grid == t.grid, c.kernel
        assert c.total_bytes == t.total_bytes, \
            f"{c.kernel}:\n{c.breakdown()}\nvs traced:\n{t.breakdown()}"


def test_brickcache_decode_footprint_empty_on_ref():
    from repro.serving.cache import BrickCache

    cfg, _shape = get_config("smoke")
    cache = BrickCache(cfg, backend="ref", grid_shape=(16, 16, 16),
                       brick_edge=8)
    assert cache.decode_vmem_footprint() == []          # no pallas_call


# --------------------------------------------------------------------------- #
# lockfile: round-trip, drift diff, CLI exit codes
# --------------------------------------------------------------------------- #
TINY_MATRIX = (("smoke", ("ref",), "jaxpr"),)


@pytest.fixture(scope="module")
def tiny_lock(tmp_path_factory):
    from repro.analysis.lock import write_lock

    path = tmp_path_factory.mktemp("lock") / "ANALYSIS_LOCK.json"
    lock = write_lock(str(path), matrix=TINY_MATRIX)
    return str(path), lock


def test_lock_write_then_verify_clean(tiny_lock):
    from repro.analysis.lock import verify_lock

    path, lock = tiny_lock
    assert {k.split("/")[2] for k in lock["entries"]} == {
        "train_step", "train_chunk", "train_chunk_degraded",
        "render", "render_cached", "serving_tick"}
    assert verify_lock(path) == []


def test_lock_hand_edit_fails_with_readable_diff(tiny_lock, tmp_path):
    from repro.analysis.lock import verify_lock

    path, _lock = tiny_lock
    doc = json.loads(open(path).read())
    entry = doc["entries"]["smoke/ref/train_step"]
    entry["precision_flow"]["n_matmuls"] += 7
    edited = tmp_path / "edited.json"
    edited.write_text(json.dumps(doc))
    drift = verify_lock(str(edited))
    assert len(drift) == 1
    assert "smoke/ref/train_step" in drift[0]
    assert "precision_flow.n_matmuls" in drift[0]
    assert "lock=" in drift[0] and "current=" in drift[0]


def test_lock_backend_filter_skips_other_legs(tiny_lock, tmp_path):
    from repro.analysis.lock import verify_lock

    path, _lock = tiny_lock
    doc = json.loads(open(path).read())
    doc["entries"]["smoke/ref/train_step"]["donation"]["status"] = "fail"
    edited = tmp_path / "edited.json"
    edited.write_text(json.dumps(doc))
    # a pallas-leg verify must not even re-derive the ref entries
    assert verify_lock(str(edited), backends=["pallas"]) == []


def test_lock_cli_verify_drift_exits_1(tiny_lock, tmp_path, capsys):
    from repro.analysis.__main__ import main

    path, _lock = tiny_lock
    doc = json.loads(open(path).read())
    doc["entries"]["smoke/ref/render"]["vmem_budget"]["status"] = "fail"
    edited = tmp_path / "edited.json"
    edited.write_text(json.dumps(doc))
    assert main(["lock", "verify", "--path", str(edited)]) == 1
    out = capsys.readouterr().out
    assert "DRIFT" in out and "smoke/ref/render" in out
    assert "lock write" in out                          # the fix is suggested


def test_lock_cli_missing_lockfile_exits_2(tmp_path, capsys):
    from repro.analysis.__main__ import main

    assert main(["lock", "verify", "--path",
                 str(tmp_path / "nope.json")]) == 2
    assert "not found" in capsys.readouterr().err


def test_committed_lockfile_exists_and_parses():
    """The repo-root ANALYSIS_LOCK.json is committed, canonical, and covers
    the full matrix (CI additionally verifies its fingerprints per leg)."""
    import os

    from repro.analysis.lock import (DEFAULT_LOCK_PATH, LOCK_MATRIX,
                                     dump_lock, read_lock)

    root = os.path.join(os.path.dirname(__file__), "..")
    path = os.path.join(root, DEFAULT_LOCK_PATH)
    lock = read_lock(path)
    assert lock["version"] == 1
    assert set(lock["matrix"]) == {c for c, _b, _l in LOCK_MATRIX}
    for config, backends_, _level in LOCK_MATRIX:
        for b in backends_:
            assert f"{config}/{b}/train_step" in lock["entries"]
            assert f"{config}/{b}/serving_tick" in lock["entries"]
    # canonical serialization: a re-dump is byte-identical to the file
    assert dump_lock(lock) == open(path).read()


# --------------------------------------------------------------------------- #
# CLI usage errors exit 2 (distinct from check failures' exit 1)
# --------------------------------------------------------------------------- #
def test_cli_unknown_config_exits_2(capsys):
    from repro.analysis.__main__ import main

    assert main(["--config", "no-such-config"]) == 2
    err = capsys.readouterr().err
    assert "unknown config" in err and "quickstart" in err


def test_cli_unknown_check_exits_2(capsys):
    from repro.analysis.__main__ import main

    assert main(["--config", "smoke", "--checks",
                 "vmem_budget,bogus_check"]) == 2
    err = capsys.readouterr().err
    assert "bogus_check" in err and "vmem_budget" in err


def test_cli_report_dir_writes_artifacts(tmp_path, capsys):
    from repro.analysis.__main__ import main

    assert main(["--config", "smoke", "--backend", "ref", "--max-level",
                 "jaxpr", "--report-dir", str(tmp_path)]) == 0
    text = (tmp_path / "smoke.ref.txt").read_text()
    assert "grid_write_safety" in text and "hbm_traffic" in text
