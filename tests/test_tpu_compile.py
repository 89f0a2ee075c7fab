"""Compile rehearsals for the TPU v5e: the main-path programs at real widths,
compiled by the chip's own compiler for one chip of a DESCRIBED v5e:2x2
topology (no chip attached; nothing runs).

Every program that ``backend="auto"`` runs on a TPU must compile: the
PRODUCTION256 train chunk (8 ranks of 256^3 on one chip), the ABLATION
train chunk (4 ranks of 512^3 on one chip, within its 16 GB), the 512^2 x
64 direct frame, the cached service tick and the brick-cache decode; the
PRODUCTION256 chunk's scan body holds no scatter (the tables' gradient
sorts and searches each row's run), nor does the ABLATION chunk (levels
with more rows than samples are written by tiles, with a one-hot product).
The ``pallas_tpu`` kernels the compiler refuses are strict xfails carrying
its refusal, so the change that makes one compile has to flip it — and may
then let ``auto`` choose it.

The topology is described inside a module fixture (never at import): only
one process may hold the TPU library, and only the worker given this file
loads it.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import backends
from repro.configs.dvnr import ABLATION, PRODUCTION256
from repro.kernels.hash_encoding import ops as he_ops

P = 8                                    # ranks of 256^3 on one chip
VOLUME = (258, 258, 258)                 # 256^3 owned + 1 ghost layer
FRAME, SAMPLES = 512, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # noqa: BLE001 - any failure skips
        jax.config.update("jax_enable_compilation_cache", cache_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def auto_tpu():
    """What ``backend="auto"`` resolves to on a TPU (any test pin lifted)."""
    pinned = backends._DEFAULT_OVERRIDE
    backends.set_default_backend(None)
    try:
        return backends.resolve_auto("tpu")
    finally:
        backends.set_default_backend(pinned)


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _compile(fn, sharding, *args):
    return jax.jit(fn).lower(*_on(sharding, args)).compile()


def _stacked_params(P_):
    from repro.api import DVNRModel
    return jax.eval_shape(lambda: DVNRModel.init(
        PRODUCTION256, jax.random.PRNGKey(0), P_).params)


def _metas(P_):
    return tuple(jax.ShapeDtypeStruct(s, jnp.float32)
                 for s in ((P_, 3), (P_, 3), (P_, 2)))


def test_peaks_keyed_by_the_v5e_device_kind(one_chip):
    from repro.utils import hw
    (dev,) = one_chip.device_set
    assert hw.peaks(dev.device_kind) is hw.PEAKS[hw.V5E]
    with pytest.raises(ValueError, match="no published peaks"):
        hw.peaks("TPU v0 unknown")


# --------------------------------------------------------------------------- #
# The auto path: every main-path program compiles
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def auto_train_chunk(one_chip, auto_tpu):
    """The PRODUCTION256 64-step train chunk ``backend="auto"`` runs,
    compiled once for the module."""
    from repro.core.trainer import DVNRTrainer

    tr = DVNRTrainer(PRODUCTION256, P, impl=auto_tpu, volume_shape=VOLUME)
    return jax.jit(tr._chunk_body(64), donate_argnums=(0, 1)).lower(
        *_on(one_chip, tr.abstract_chunk_args(64))).compile()


def test_auto_train_chunk_compiles(auto_train_chunk, auto_tpu):
    compiled = auto_train_chunk
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 10**9
    assert ("tpu_custom_call" in compiled.as_text()) == auto_tpu.is_pallas


def _computations(text):
    """{name: instruction lines} of a compiled HLO module's computations."""
    return {m.group(1): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?%(\S+) [^\n]*\{\n(.*?)\n\}$", text, re.M | re.S)}


def test_auto_train_chunk_scan_body_holds_no_scatter(auto_train_chunk):
    """The tables' gradient sorts each level's corner indices and sums the
    runs: no scatter (of the per-corner updates or any other) is left in the
    scan's body or in what it calls."""
    comps = _computations(auto_train_chunk.as_text())
    todo = [c for body in comps.values()
            for c in re.findall(r"body=%([\w.\-]+)", body)]
    assert todo, "no while loop in the chunk"
    seen = set()
    while todo:
        name = todo.pop()
        if name not in seen and name in comps:
            seen.add(name)
            todo += re.findall(r"%([\w.\-]+)", comps[name])
    scatters = [line.strip()[:120] for name in seen
                for line in comps[name].splitlines() if " scatter(" in line]
    assert not scatters, scatters
    assert any(" sort(" in comps[name] for name in seen)


def test_auto_ablation_chunk_fits_one_chip(one_chip, auto_tpu):
    """The ABLATION network's chunk at its published widths, 4 ranks of
    512^3 on one chip (10 x 8 levels, T = 2^19 over 8N = 131,072 corners a
    level): it fits the chip's 16 GB, and its tables' gradient writes the
    run totals of the levels with more rows than samples by tiles, with no
    scatter: two sorts (the corners', the run ends') and a one-hot product
    whose one-hot the compiler makes inside the product, never in memory."""
    from repro.core.trainer import DVNRTrainer

    tr = DVNRTrainer(ABLATION, 4, impl=auto_tpu, volume_shape=(514,) * 3)
    compiled = jax.jit(tr._chunk_body(4), donate_argnums=(0, 1)).lower(
        *_on(one_chip, tr.abstract_chunk_args(4))).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 10**9
    text = compiled.as_text()
    assert " scatter(" not in text
    assert text.count(" sort(") == 2
    comps = _computations(text)
    B = he_ops._TILE
    fused = {c for body in comps.values()
             for c in re.findall(r"\bcalls=%([\w.\-]+)", body)}
    onehots = [line.strip()[:120] for name, body in comps.items()
               if name not in fused for line in body.splitlines()
               if re.search(rf"= (pred|bf16)\[[\d,]*,{B},{B}\]", line)]
    assert not onehots, onehots
    products = [line for line in text.splitlines() if "convolution(" in line
                and "table_rows" in line]
    assert products, "no one-hot product in the tables' gradient"


@pytest.mark.parametrize("cached", [False, True], ids=["direct", "cached"])
def test_auto_frame_program_compiles(one_chip, auto_tpu, cached):
    """The 512^2 x 64 frame over 8 ranks: ``api.render``'s program (direct)
    and a 4-request ``RenderService`` tick over a 128^3-per-rank brick pool
    (cached)."""
    from repro.serving.service import batched_frame_program

    B = 4 if cached else 1
    grid, edge = 128, 16
    nb = -(-grid // edge)
    prog = batched_frame_program(
        PRODUCTION256, fov=45.0, width=FRAME, height=FRAME,
        n_samples=SAMPLES, density=50.0, backend=auto_tpu, cached=cached,
        view_geom=((grid,) * 3, edge) if cached else None)
    cams = [jax.ShapeDtypeStruct((B, 3), jnp.float32)] * 3
    tfs = jax.ShapeDtypeStruct((B, 64, 4), jnp.float32)
    if cached:
        pool = jax.ShapeDtypeStruct((P * nb ** 3,) + (edge + 1,) * 3,
                                    jnp.float32)
        slots = jax.ShapeDtypeStruct((P, nb, nb, nb), jnp.int32)
        params = None
    else:
        pool = jax.ShapeDtypeStruct((), jnp.float32)
        slots = jax.ShapeDtypeStruct((), jnp.int32)
        params = _stacked_params(P)
    _compile(prog, one_chip, *cams, tfs, pool, slots, _metas(P),
             jax.ShapeDtypeStruct((2,), jnp.float32), params)


def test_auto_brick_decode_compiles(one_chip, auto_tpu):
    from repro.serving import BrickCache

    cache = BrickCache(PRODUCTION256, grid_shape=(128,) * 3, brick_edge=16,
                       budget_bytes=17 ** 3 * 4, backend=auto_tpu)
    n = cache.bricks_per_partition(0) * 17 ** 3
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                          _stacked_params(1))
    _compile(cache._decode_impl, one_chip, params,
             jax.ShapeDtypeStruct((n, 3), jnp.float32))


# --------------------------------------------------------------------------- #
# pallas_tpu kernels, one by one
# --------------------------------------------------------------------------- #
# the compiler's refusals, as (message pattern, xfail reason)
_TABLE_LOOKUP = ("Shape mismatch in input, indices and output",
                 "v5e compiler: 'Shape mismatch in input, indices and "
                 "output' from the in-kernel table lookup (jnp.take) and "
                 "table-gradient scatter")
_TILING = ("block shape are divisible by 8 and 128",
           "v5e compiler: output block (1024, 1, 4) breaks the (8, 128) "
           "tiling rule; the kernel also looks the table up with jnp.take")


def _kernel_program(name):
    """(fn, args) of one ``pallas_tpu`` kernel at PRODUCTION256 widths."""
    from repro.kernels.composite.kernel import composite_pallas
    from repro.kernels.fused_mlp.kernel import (fused_mlp_bwd_pallas,
                                                fused_mlp_fwd_pallas)
    from repro.kernels.hash_encoding.kernel import hash_encode_pallas

    cfg = PRODUCTION256
    L, T, F, W = (cfg.n_levels, cfg.table_size, cfg.n_features_per_level,
                  cfg.n_neurons)
    N = cfg.batch_size
    sds = jax.ShapeDtypeStruct
    mlp = (sds((N, L * F), jnp.float32), sds((L * F, W), jnp.float32),
           sds((1, W, W), jnp.float32), sds((W, 1), jnp.float32))
    if name == "fused_mlp_fwd":
        return (lambda *a: fused_mlp_fwd_pallas(*a, n_hidden=2,
                                                interpret=False)), mlp
    if name == "fused_mlp_bwd":
        return (lambda *a: fused_mlp_bwd_pallas(*a, n_hidden=2,
                                                interpret=False)), \
            mlp + (sds((N, 1), jnp.float32),)
    if name == "composite":
        return (lambda r: composite_pallas(r, interpret=False)), \
            (sds((FRAME * FRAME, SAMPLES, 4), jnp.float32),)
    if name == "hash_encode":
        res = jnp.asarray(cfg.level_resolutions(), jnp.int32)
        return (lambda c, t: hash_encode_pallas(c, t, res, interpret=False)), \
            (sds((N, 3), jnp.float32), sds((L, T, F), jnp.float32))
    from repro.core.trainer import DVNRTrainer
    fuse, brick, volume = {
        "train_step": ("off", "auto", VOLUME),
        "train_step_sampling_pinned": ("on", "pinned", (10, 10, 10)),
        "train_step_sampling_tiled": ("on", "auto", VOLUME),
    }[name]
    tr = DVNRTrainer(cfg.replace(fuse_sampling=fuse, sampling_brick=brick),
                     1, impl="pallas_tpu", volume_shape=volume)
    return tr._chunk_body(2), tr.abstract_chunk_args(2)


def _refused(name, refusal):
    return pytest.param(name, refusal[0], id=name, marks=pytest.mark.xfail(
        strict=True, raises=ValueError, reason=refusal[1]))


@pytest.mark.parametrize("name,refusal", [
    pytest.param("fused_mlp_fwd", None, id="fused_mlp_fwd"),
    pytest.param("fused_mlp_bwd", None, id="fused_mlp_bwd"),
    pytest.param("composite", None, id="composite"),
    _refused("hash_encode", _TILING),
    _refused("train_step", _TABLE_LOOKUP),
    _refused("train_step_sampling_pinned", _TABLE_LOOKUP),
    _refused("train_step_sampling_tiled", _TABLE_LOOKUP),
])
def test_pallas_tpu_kernel_compiles(one_chip, name, refusal):
    fn, args = _kernel_program(name)
    try:
        compiled = _compile(fn, one_chip, *args)
    except ValueError as e:
        # only the recorded refusal keeps a strict xfail green; any other
        # error fails the case
        if refusal is None or refusal not in str(e):
            pytest.fail(f"{name}: unexpected compile error: {e}")
        raise
    assert "tpu_custom_call" in compiled.as_text()
