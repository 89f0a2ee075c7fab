"""chip_smoke.py rehearsed on the CPU: its phase functions at a tiny size with
interpret-mode Pallas kernels (one chip in-process, four chips on virtual
CPU devices in a subprocess), and the script itself refusing to run without
a TPU or outside a checkout."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"

# the phases' checks loosened to what 32 SMOKE steps on 8^3 ranks can show
TINY = dict(cfg_name="SMOKE", ranks=8, local=8, chunk=8, chunks=4,
            parity_steps=2, frame=32, samples=8, cache_grid=8, brick_edge=4,
            decode_chunk=128, mesh_steps=8, swap_frame=32, psnr_floor_db=10.0, loss_fall=0.8,
            cache_mean_abs=0.05)


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    yield mod
    sys.modules.pop("chip_smoke", None)


def test_one_chip_phases_tiny_interpret(chip_smoke):
    from repro import backends

    sizes = chip_smoke.Sizes(**TINY)
    res = chip_smoke.run_one_chip(sizes, 0, backends.resolve("pallas"))
    assert set(res) == {"train", "parity", "compress", "render", "serve"}
    assert res["train"]["finite"] and res["train"]["steps"] == sizes.steps
    assert res["train"]["loss_last"] < res["train"]["loss_first"]
    # same device, same program: the parity leg is exact here
    assert res["parity"]["max_rel_diff"] == 0.0
    assert res["compress"]["psnr_decoded_db"] >= sizes.psnr_floor_db
    assert res["serve"]["hits"] > 0 and res["serve"]["evictions"] == 0
    assert all(res["serve"][f"tick{i}_responses"] == sizes.requests + 1
               for i in range(sizes.ticks))


_FOUR_CHIPS = textwrap.dedent("""
    import importlib.util, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    spec = importlib.util.spec_from_file_location("chip_smoke", {script!r})
    cs = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = cs
    spec.loader.exec_module(cs)
    from repro import backends
    res = cs.run_four_chips(cs.Sizes(**{tiny!r}), 0,
                            backends.resolve("pallas"))
    mt, bs = res["mesh_train"], res["binary_swap"]
    assert mt["collective_free"] and mt["hlo_ops_walked"] > 0, mt
    print("MESH_PARAM_REL", mt["max_param_rel_diff"])
    print("SWAP_ERR", bs["max_abs_diff"])
""")


def test_four_chip_phase_tiny_on_virtual_devices():
    code = _FOUR_CHIPS.format(script=str(SCRIPT), tiny=TINY)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    vals = dict(line.split() for line in r.stdout.splitlines()
                if line.startswith(("MESH_PARAM_REL", "SWAP_ERR")))
    assert float(vals["MESH_PARAM_REL"]) <= 1e-6
    assert float(vals["SWAP_ERR"]) <= 1e-5


def _run_script(path: Path, cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(path)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _prints_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return "ok" in json.loads(lines[-1])
    except ValueError:
        return False


def test_script_refuses_without_a_tpu():
    r = _run_script(SCRIPT, ROOT)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert not _prints_result(r.stdout)


def test_script_refuses_outside_a_checkout(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    r = _run_script(alone, tmp_path)
    assert r.returncode != 0
    assert not _prints_result(r.stdout)
