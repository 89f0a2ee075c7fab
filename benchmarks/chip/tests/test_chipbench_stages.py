"""Device time by stage of the training step: the stage rule on op names,
the map from a compiled program's text, the split of the trace's leaf ops,
the window's program from a tiny run, and the window's compile count."""
import lzma
import sys
from types import SimpleNamespace as NS

import pytest

from chip import harness, stages, trace
from chip.kinds import train
from chip.tests import tiny
from chip.tests.test_chipbench_trace import RECORDED, PRINTED, synthetic

BODY = "jit(dvnr_train_chunk)/while/body/closed_call"


@pytest.mark.parametrize("op_name, stage", [
    (f"{BODY}/vmap(dvnr.sample)/gather", "sample"),
    (f"{BODY}/vmap(jvp(dvnr.encode))/gather", "encode"),
    (f"{BODY}/vmap(transpose(vmap(jvp(dvnr.encode))))/scatter-add",
     "table_grad"),
    (f"{BODY}/transpose(jvp(dvnr.encode))/mul", "table_grad"),
    (f"{BODY}/vmap(jvp(dvnr.mlp))/dot_general", "mlp"),
    (f"{BODY}/vmap(transpose(vmap(jvp(dvnr.mlp))))/transpose(jvp())/"
     "dot_general", "mlp"),
    (f"{BODY}/vmap(dvnr.adam)/mul", "adam"),
    (f"{BODY}/shard_map/vmap(dvnr.sample)/gather", "sample"),
    # the innermost scope decides
    (f"{BODY}/vmap(dvnr.adam)/vmap(transpose(jvp(dvnr.encode)))/add",
     "table_grad"),
    (f"{BODY}/vmap(transpose(jvp(dvnr.mlp)))/vmap(jvp(dvnr.encode))/mul",
     "encode"),
    ("jit(chunk)/while/body/closed_call/vmap(jvp())/gather", "other"),
    (f"{BODY}/dvnr.render/add", "other"),
    ("", "other"),
])
def test_stage_of_an_op_name(op_name, stage):
    assert stages.stage(op_name) == stage


HLO = """HloModule jit_dvnr_train_chunk, is_scheduled=true

%fused_computation.7 (param_0: f32[64,4], param_1: f32[8,4]) -> f32[64,4] {
  %param_0 = f32[64,4]{0,1:T(4,128)} parameter(0)
  %mul.2 = f32[8,4]{0,1} multiply(%param_1, %param_1), metadata={op_name="a/transpose(jvp(dvnr.encode))/mul" stack_frame_id=3}
  ROOT %scatter.1 = f32[64,4]{0,1:T(4,128)} scatter(%param_0, %mul.2)
}

%body.3 (arg: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %arg = (s32[], f32[8,4]{0,1}) parameter(0)
  %gte.4 = f32[8,4]{0,1} get-tuple-element(%arg), index=1
  %dus.5 = f32[8,4]{0,1} dynamic-update-slice(%gte.4, %gte.4)
  ROOT %tuple.6 = (s32[], f32[8,4]{0,1}) tuple(%gte.4, %dus.5)
}

ENTRY %main.9 (p: f32[64,4], q: f32[8,4]) -> f32[64,4] {
  %p = f32[64,4]{0,1:T(4,128)} parameter(0)
  %q = f32[8,4]{0,1} parameter(1)
  %sort.8 = (s32[8]{0}, s32[8]{0}) sort(%q), dimensions={0}, to_apply=%compare.1
  %gte.9 = s32[8]{0} get-tuple-element(%sort.8), index=0
  %while.10 = (s32[], f32[8,4]{0,1}) while(%q), condition=%cond.2, body=%body.3
  %gte.11 = f32[8,4]{0,1} get-tuple-element(%while.10), index=1
  %fusion.5 = f32[8,4]{0,1} fusion(%gte.11), kind=kLoop, calls=%fused_computation.4, metadata={op_name="a/vmap(dvnr.sample)/gather"}
  %fusion.7 = f32[64,4]{0,1:T(4,128)} fusion(%p, %fusion.5, %gte.9), kind=kCustom, calls=%fused_computation.7
  %copy.1 = f32[64,4]{0,1:T(4,128)} copy(%fusion.7)
  %copy.12 = f32[64,4]{0,1:T(4,128)} copy(%p)
  ROOT %tuple.2 = (f32[64,4]{0,1:T(4,128)}) tuple(%copy.1), metadata={op_name="a/vmap(dvnr.adam)/add"}
}
"""


def test_program_op_names_keyed_as_the_trace_names_ops():
    names = stages.program_op_names(HLO)
    stage = {k: stages.stage(v) for k, v in names.items()}
    assert names["%fusion.5 = f32[8,4]"] == "a/vmap(dvnr.sample)/gather"
    # compiler-made, no op_name: its called computation's (a split
    # scatter's piece), its users' (the scatter's index sort, a copy), its
    # caller's users' (a copy loop feeding the gather), else none
    assert names["%fusion.7 = f32[64,4]"] \
        == "a/transpose(jvp(dvnr.encode))/mul"
    assert stage["%fusion.7 = f32[64,4]"] == "table_grad"
    assert stage["%sort.8 = (s32[8]"] == "table_grad"
    assert stage["%dus.5 = f32[8,4]"] == "sample"
    assert names["%copy.1 = f32[64,4]"] == "a/vmap(dvnr.adam)/add"
    assert names["%copy.12 = f32[64,4]"] == ""
    assert names["%tuple.2 = (f32[64,4]"] == "a/vmap(dvnr.adam)/add"


def test_stages_split_the_leaf_ops_and_unmatched_ops_stay_apart():
    red = trace.reduce(synthetic(), [0, 1])
    names = {"fusion.1": f"{BODY}/vmap(jvp(dvnr.encode))/gather",
             "scatter": f"{BODY}/vmap(transpose(jvp(dvnr.encode)))/add"}
    got = stages.by_stage(red.ops, names)
    assert list(got) == list(stages.STAGES) + ["unmatched"]
    assert sum(got.values()) == pytest.approx(sum(red.ops.values()))
    assert got["encode"] == pytest.approx(red.ops["fusion.1"])
    assert got["table_grad"] == pytest.approx(red.ops["scatter"])
    assert got["unmatched"] == pytest.approx(red.ops["gather"])  # not in map
    assert got["sample"] == got["mlp"] == got["adam"] == got["other"] == 0.0


def test_window_on_the_wall_clock_from_a_recorded_trace():
    import jax

    raw = lzma.decompress(RECORDED.read_bytes())
    planes = list(jax.profiler.ProfileData.from_serialized_xspace(raw).planes)
    lo, hi = stages.window_wall_ns(planes)
    assert (hi - lo) / 1e9 == pytest.approx(PRINTED["window_s"], rel=1e-9)
    session = dict(next(p for p in planes if p.name == "Task Environment")
                   .stats)
    assert session["profile_start_time"] < lo < hi \
        < session["profile_stop_time"]
    assert stages.window_wall_ns(planes[:2]) is None


@pytest.fixture(scope="module")
def driven():
    run = tiny.run("train", "production256-x8.train")
    tiny.drive(run)
    return run


def _traced(run, ops):
    """The run as a traced run whose window held ``ops``."""
    out = harness.Run(cell=run.cell, seed=run.seed, seconds=run.seconds,
                      devices=run.devices, mesh=run.mesh,
                      state=dict(run.state), window=dict(run.window),
                      peaks=run.peaks)
    out.reduction = trace.Reduction(window_s=1.0, busy=[1.0], ops=ops,
                                    gaps={})
    return out


@pytest.fixture(scope="module")
def window_ops(driven):
    """``{trace op name: 1 ms}`` of the tiny run's window program."""
    text = stages.window_program_text(driven)
    assert text.startswith("HloModule jit_dvnr_train_chunk")
    return dict.fromkeys(stages.program_op_names(text), 1e-3)


def test_stage_readers_on_the_window_program_of_a_tiny_run(driven,
                                                           window_ops):
    stray = 0.5 * stages.UNMATCHED_SHARE * sum(window_ops.values())
    run = _traced(driven, {**window_ops, "%stray.1 = f32[2]": stray})
    ms = {s: harness.metric_reader(f"train_ms.{s}")(run)
          for s in stages.STAGES}
    assert all(ms[s] > 0 for s in stages.STAGES), ms
    total = sum(ms.values()) * run.window["steps"] / 1e3
    # an op the program does not hold, under the share, is in no stage
    assert total == pytest.approx(sum(window_ops.values()), rel=1e-12)


def test_stage_readers_raise_on_ops_the_window_program_does_not_hold(
        driven, window_ops):
    stray = 2 * stages.UNMATCHED_SHARE * sum(window_ops.values())
    run = _traced(driven, {**window_ops, "%stray.1 = f32[2]": stray})
    with pytest.raises(ValueError, match="does not hold"):
        harness.metric_reader("train_ms.other")(run)


def test_readers_read_nothing_from_a_program_without_stages(
        driven, tmp_path, monkeypatch):
    """The parent of the stage scopes has no ``chunk_program`` and no
    ``repro.tracing``: every new reader reads nothing, and raises nothing."""
    import repro
    from repro.core.trainer import DVNRTrainer

    run = _traced(driven, {"%fusion.1 = f32[2]": 1.0})
    recorded = tmp_path / run.cell.name / "chip.xplane.pb"
    recorded.parent.mkdir()
    recorded.write_bytes(lzma.decompress(RECORDED.read_bytes()))
    monkeypatch.setattr(stages, "TRACE_DIR", tmp_path)
    assert harness.metric_reader("train_chunk_compiles")(run) == 0
    monkeypatch.delattr(DVNRTrainer, "chunk_program")
    monkeypatch.delattr(repro, "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    for name in [f"train_ms.{s}" for s in stages.STAGES] \
            + ["train_chunk_compiles"]:
        assert harness.metric_reader(name)(run) is None, name


def test_window_compiles_counts_the_chunk_program_inside_the_window(
        tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(stages, "TRACE_DIR", tmp_path)

    @jax.jit
    def dvnr_train_chunk(x):            # a compile under the chunk's name
        return x + 1

    for compile_inside, want in [(False, 0), (True, 1)]:
        run = tiny.run("train", "production256-x8.train")
        train.setup(run)                # compiles the chunk: not counted
        with harness.profiled(tmp_path / run.cell.name), \
                harness.span(trace.WINDOW_SPAN):
            if compile_inside:
                dvnr_train_chunk(jnp.ones(3))
            train.window(run)
        run.reduction = NS()
        assert stages.window_compiles(run) == want


MESH_SCRIPT = """
import re
import jax, jax.numpy as jnp
from chip import harness, stages
from chip.kinds import train
from chip.tests import tiny
from repro.core.trainer import DVNRTrainer
devs = jax.devices()[:4]
run = tiny.run("mesh-train", "cloverleaf1024-x64.mesh-train", ranks=8,
               devices=devs, mesh=harness.mesh_of(devs),
               ranks_checked_per_chip=1)
train.setup(run)
trainer = next(c.cell_contents for c in run.state["call"].__closure__
               if isinstance(c.cell_contents, DVNRTrainer))
vols, chunk = run.state["vols"], run.cell.traffic["chunk_steps"]
key = jnp.asarray(run.state["key"], jnp.uint32)
with jax.default_matmul_precision(run.cell.config["matmul_precision"]):
    window = trainer.chunk_program(run.state["train_state"], vols, chunk,
                                   key=key).as_text()
    first = trainer.chunk_program(trainer.init(jax.random.PRNGKey(0)), vols,
                                  chunk, key=key).as_text()
def program(text):      # without the stack-frame tables: they name callers
    return [re.sub(r" stack_frame_id=[0-9]+", "", line)
            for line in text.splitlines()
            if not re.match(r"([0-9]+ |FileNames|FunctionNames|FileLocations"
                            r"|StackFrames)", line)]

assert program(first) != program(window)
assert program(stages.window_program_text(run)) == program(window)
print("MESH PROGRAM OK")
"""


def test_the_window_program_on_a_mesh_is_the_one_the_window_ran():
    """On four virtual CPU devices: the program rebuilt for the stage map is
    the window's (a chunk's output placement), not the first chunk's."""
    import os
    import subprocess
    from pathlib import Path

    benchmarks = Path(__file__).resolve().parents[2]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(benchmarks),
                                           str(benchmarks.parent / "src")]))
    out = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "MESH PROGRAM OK" in out.stdout, out.stderr[-3000:]
