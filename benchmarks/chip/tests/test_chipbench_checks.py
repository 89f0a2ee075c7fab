"""The training check passes at a tiny size on the CPU and fails on a
perturbed result."""
import copy

import numpy as np
import pytest

from chip import harness
from chip.kinds import train
from chip.tests import tiny


def verdict(run, gaps):
    return {k: harness.Check(k, v, float(run.cell.limits[k])).ok
            for k, v in gaps.items()}


@pytest.fixture(scope="module")
def trained():
    run = tiny.run("train", "production256-x8.train",
                   ranks_checked_per_chip=2)
    checks = tiny.drive(run)
    return run, checks, train.reference(run)


def test_train_check_passes(trained):
    run, checks, _ = trained
    assert [c.name for c in checks] == ["loss_gap", "moment_gap",
                                        "change_gap", "nonfinite_ranks"]
    assert all(c.ok for c in checks), checks
    chunk = run.cell.traffic["chunk_steps"]
    assert run.state["prog_losses"].shape == (2, chunk)
    assert run.window["attempted"] % chunk == 0
    assert run.window["attempted"] >= chunk and run.window["failed"] == 0


def _halfway(run):
    """Every rank's parameters moved half as far as they did."""
    w0, p = run.state["w0"], run.state["prog_params"]
    return {"tables": (p["tables"] + w0["tables"]) / 2,
            "mlp": [(a + b) / 2 for a, b in zip(p["mlp"], w0["mlp"])]}


@pytest.mark.parametrize("key, fails", [
    ("prog_losses", "loss_gap"),
    ("prog_m", "moment_gap"),
    ("prog_params", "change_gap"),
])
def test_train_check_fails_on_a_perturbed_result(trained, key, fails):
    run, _, refs = trained
    bad = copy.copy(run)
    bad.state = dict(run.state)
    if key == "prog_params":
        bad.state[key] = _halfway(run)
    elif key == "prog_m":
        bad.state[key] = {"tables": run.state[key]["tables"] * 1.1,
                          "mlp": run.state[key]["mlp"]}
    else:
        losses = np.array(run.state[key])
        losses[:, -1] *= 1.01                     # the chunk's last step
        bad.state[key] = losses
    ok = verdict(run, train.compare(bad, refs))
    assert not ok[fails], ok


MESH_SCRIPT = """
import jax
from chip import harness
from chip.kinds import train
from chip.tests import tiny
devs = jax.devices()[:4]
run = tiny.run("mesh-train", "cloverleaf1024-x64.mesh-train", ranks=8,
               devices=devs, mesh=harness.mesh_of(devs),
               ranks_checked_per_chip=1)
clock = harness.CompileClock()
train.setup(run)
compiled = clock.count
train.window(run)
assert clock.count == compiled, "the window compiled"
train.release(run)
checks = train.check(run)
assert all(c.ok for c in checks), checks
assert len(run.state["vols"].sharding.device_set) == 4
ranks = run.state["ranks"]
assert sorted(r // 2 for r in ranks) == [0, 1, 2, 3], ranks
print("MESH OK")
"""


def test_mesh_train_check_covers_every_chip():
    """The mesh cell's path on four virtual CPU devices: volumes sharded
    over the mesh, nothing compiled in the window, one checked rank on each
    device, the check passing."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    benchmarks = Path(__file__).resolve().parents[2]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(benchmarks),
                                           str(benchmarks.parent / "src")]))
    out = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "MESH OK" in out.stdout, out.stderr[-3000:]


def test_the_program_runs_at_the_stated_matmul_precision(monkeypatch):
    """Every call of the window's program runs under the configuration's
    matmul precision (a TPU would otherwise run f32 dots in one bf16
    pass)."""
    import jax
    from repro.core.trainer import DVNRTrainer

    seen, chunk = [], DVNRTrainer.train_chunk

    def spy(self, *a, **kw):
        seen.append(jax.config.jax_default_matmul_precision)
        return chunk(self, *a, **kw)

    monkeypatch.setattr(DVNRTrainer, "train_chunk", spy)
    run = tiny.run("train", "production256-x8.train")
    train.setup(run)
    train.window(run)
    stated = run.cell.config["matmul_precision"]
    assert len(seen) >= 2 and set(seen) == {stated}
    assert jax.config.jax_default_matmul_precision is None
