"""Stage scopes in the compiled chunk, and compiles counted by program.

Every step path (unfused, fused, fused with in-op sampling) carries the
stage scopes of :mod:`repro.tracing` into the ``op_name`` of the compiled
chunk's instructions: each stage is found in the scan's body, the hash
encode both forward and under ``transpose(`` (the tables' gradient:
its sort, segmented scan and row readout, with no scatter left; where a
level has more rows than samples, its readout by the tile writer too).
:func:`repro.tracing.compiles` counts a placement-only recompile, which does
not retrace.
"""
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import tracing
from repro.configs import dvnr as dvnr_cfg
from repro.core.trainer import DVNRTrainer

CFG = dvnr_cfg.SMOKE.replace(batch_size=512, n_levels=2, log2_hashmap_size=8,
                             n_neurons=8, n_hidden_layers=1, lrate=1e-2)
PATHS = {"unfused": ("off", "off"), "fused": ("on", "off"),
         "fused_sampling": ("on", "on")}


@pytest.fixture(scope="module", params=sorted(PATHS))
def program(request):
    """(compiles of the first chunk's dispatch, compiles of chunk_program
    after it, chunk_program's text)."""
    fuse, sampling = PATHS[request.param]
    cfg = CFG.replace(fuse_train_step=fuse, fuse_sampling=sampling)
    tr = DVNRTrainer(cfg, 2, impl="ref", volume_shape=(10, 10, 10))
    assert (tr.fuse_train_step, tr.fuse_sampling) == (fuse == "on",
                                                      sampling == "on")
    vols = jnp.linspace(0, 1, 2000, dtype=jnp.float32).reshape(2, 10, 10, 10)
    key = jnp.asarray([3, 4], jnp.uint32)
    state = tr.init(jax.random.PRNGKey(0))
    before = tracing.compiles(tracing.CHUNK_PROGRAM)
    state, losses = tr.train_chunk(state, vols, 2, key=key)
    jax.block_until_ready(losses)
    dispatched = tracing.compiles(tracing.CHUNK_PROGRAM) - before
    text = tr.chunk_program(state, vols, 2, key=key).as_text()
    served = tracing.compiles(tracing.CHUNK_PROGRAM) - before - dispatched
    return dispatched, served, text


@pytest.fixture(scope="module", params=sorted(PATHS))
def written_program(request):
    """(readouts traced, chunk_program's text) at T = 2^8 > 8N = 128 corners
    a level: every level's readout is the tile writer's."""
    fuse, sampling = PATHS[request.param]
    cfg = CFG.replace(batch_size=16, fuse_train_step=fuse,
                      fuse_sampling=sampling)
    tr = DVNRTrainer(cfg, 2, impl="ref", volume_shape=(10, 10, 10))
    vols = jnp.linspace(0, 1, 2000, dtype=jnp.float32).reshape(2, 10, 10, 10)
    state = tr.init(jax.random.PRNGKey(0))
    traced = len(tracing.table_readouts())
    text = tr.chunk_program(state, vols, 2,
                            key=jnp.asarray([3, 4], jnp.uint32)).as_text()
    return tracing.table_readouts()[traced:], text


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


def _innermost(op_name):
    """(innermost ``dvnr.*`` scope of ``op_name``, whether a transpose
    wraps it), or None."""
    found = None
    for part in op_name.split("/"):
        for m in re.finditer(r"dvnr\.[A-Za-z_]+", part):
            found = (m.group(0), "transpose(" in part[:m.start()])
    return found


def test_every_stage_holds_an_instruction_of_the_scan_body(program):
    _, _, text = program
    body = [op for op in _op_names(text) if "/while/body/" in op]
    found = {_innermost(op) for op in body} - {None}
    for scope in tracing.STAGES:
        assert (scope, False) in found, (scope, found)
    assert (tracing.ENCODE, True) in found, found      # the tables' gradient


def test_table_grad_runs_under_the_encode_transpose_without_scatter(program):
    """The tables' gradient (sort, segmented scan by padded shifts, row
    readout by gathers) carries ``dvnr.encode`` under ``transpose(``, so the
    stage readers count it as ``table_grad``; no scatter of the per-corner
    updates is left in the program."""
    _, _, text = program
    body = [op for op in _op_names(text) if "/while/body/" in op]
    grad = {op.rsplit("/", 1)[-1] for op in body
            if _innermost(op) == (tracing.ENCODE, True)}
    assert {"sort", "pad", "gather"} <= grad, grad
    sorts = [op for op in body if op.endswith("/sort")]
    assert sorts and all(_innermost(op) == (tracing.ENCODE, True)
                         for op in sorts), sorts
    assert not re.search(r"= \S+ scatter\(", text)


def test_tile_writer_runs_under_the_encode_transpose_without_scatter(
        written_program):
    """A level with more rows in use than samples is read out by the tile
    writer: its second sort, window gathers and one-hot product all carry
    ``table_rows`` inside the encode's transpose, so the stage readers count
    them as ``table_grad`` and ``table_rows``; nothing is scattered."""
    readouts, text = written_program
    assert readouts and set(readouts) == {(0, CFG.n_levels)}, readouts
    assert not re.search(r"= \S+ scatter\(", text)
    body = [op for op in _op_names(text) if "/while/body/" in op]
    rows = [op for op in body if tracing.TABLE_ROWS in op]
    kinds = {op.rsplit("/", 1)[-1] for op in rows}
    assert {"sort", "gather", "dot_general", "eq"} <= kinds, kinds
    for op in rows:
        assert _innermost(op) == (tracing.ENCODE, True), op
        encode = op.index(tracing.ENCODE)
        assert f"/{tracing.TABLE_ROWS}/" in op[encode:], op
    # the product is the writer's: no dot of the tables' gradient outside it
    grad = [op for op in body if _innermost(op) == (tracing.ENCODE, True)]
    assert all(tracing.TABLE_ROWS in op for op in grad
               if op.endswith("dot_general")), grad


def test_no_instruction_names_a_scope_outside_the_list(program):
    _, _, text = program
    scopes = {m for op in _op_names(text)
              for m in re.findall(r"dvnr\.[A-Za-z_]+", op)}
    assert scopes and scopes <= set(tracing.STAGES), scopes


def test_the_chunk_is_named_and_its_program_served_from_the_cache(program):
    dispatched, served, text = program
    assert text.startswith(f"HloModule jit_{tracing.CHUNK_PROGRAM}")
    assert (dispatched, served) == (1, 0)


def test_compiles_counts_by_function_name_and_wall_clock():
    @jax.jit
    def dvnr_scope_probe(x):
        return x * 3

    t0 = time.time_ns()
    assert tracing.compiles("dvnr_scope_probe") == 0
    dvnr_scope_probe(jnp.ones(3))
    dvnr_scope_probe(jnp.ones(3))
    dvnr_scope_probe(jnp.ones(4))
    t1 = time.time_ns()
    assert tracing.compiles("dvnr_scope_probe") == 2
    assert tracing.compiles("dvnr_scope_probe", t0, t1) == 2
    assert tracing.compiles("dvnr_scope_probe", t1) == 0


PLACEMENT_SCRIPT = """
import jax, jax.numpy as jnp
from repro import tracing
from repro.configs import dvnr as dvnr_cfg
from repro.core.trainer import DVNRState, DVNRTrainer

traced = []                     # a count taken at trace time
body = DVNRTrainer._chunk_body

def counted(self, *a, **kw):
    chunk = body(self, *a, **kw)
    def dvnr_train_chunk(*args):
        traced.append(1)
        return chunk(*args)
    return dvnr_train_chunk

DVNRTrainer._chunk_body = counted
cfg = dvnr_cfg.SMOKE.replace(batch_size=256, n_levels=2, log2_hashmap_size=6,
                             n_neurons=8, n_hidden_layers=1)
tr = DVNRTrainer(cfg, 2, impl="ref", volume_shape=(6, 6, 6))
vols = jnp.full((2, 6, 6, 6), 0.5, jnp.float32)
key = jnp.asarray([1, 2], jnp.uint32)
state = tr.init(jax.random.PRNGKey(0))
seen = []
for dev in jax.devices()[:1] + jax.devices()[:2]:
    put = lambda x: jax.device_put(x, dev)
    state = DVNRState(*put((state.params, state.opt, state.loss_ma,
                            state.active)), state.step)
    state, losses = tr.train_chunk(state, put(vols), 2, key=put(key))
    jax.block_until_ready(losses)
    seen.append((tracing.compiles(tracing.CHUNK_PROGRAM), len(traced)))
print("SEEN", seen)
"""


def test_compiles_counts_a_placement_only_recompile():
    """On four virtual CPU devices: the same chunk twice on device 0, then on
    state and inputs moved to device 1. The move compiles again without
    retracing, so a count taken at trace time misses it."""
    src = Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(src))
    out = subprocess.run([sys.executable, "-c", PLACEMENT_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SEEN [(1, 1), (1, 1), (2, 1)]" in out.stdout, \
        out.stdout + out.stderr[-3000:]
