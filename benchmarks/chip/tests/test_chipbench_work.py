"""work.py's counts against hand arithmetic, for both configurations."""
import json
from pathlib import Path

import pytest

from chip import peaks, work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


# hand arithmetic: MLP 20 -> 16 -> 16 -> 1, 5 levels x 4 features
#   forward matmuls 2 * (20*16 + 16*16 + 16*1) = 1184, backward twice that
#   trilinear blend 2 * 8 * 5 * 4 = 320 forward, the same backward
#   bytes: 8 corners * 5 levels * 4 features, read and written, 4 B each,
#   plus 8 target voxels: (2 * 160 + 8) * 4 = 1312
@pytest.mark.parametrize("name, table, params", [
    ("production256-x8", 1 << 13, 5 * 8192 * 4 + 592),
    ("cloverleaf1024-x64", 1 << 10, 5 * 1024 * 4 + 592),
])
def test_train_counts(name, table, params):
    m = model(name)
    assert 1 << m["log2_hashmap_size"] == table
    assert work.param_count(m) == params
    assert work.train_flops_per_sample(m) == 3 * 1184 + 2 * 320 == 4192
    assert work.train_bytes_per_sample(m) == 1312
    assert work.train_bytes_per_rank_step(m) == 6 * params * 4
    flops, nbytes = work.train_step_work(m, 8)
    assert flops == 8 * 65536 * 4192
    assert nbytes == 8 * (65536 * 1312 + 24 * params)


def test_train_roofline_reads_the_window_busy_time():
    from types import SimpleNamespace as NS

    from chip import harness

    read = harness.metric_reader("train_roofline")
    m = model("production256-x8")
    flops, nbytes = work.train_step_work(m, 8)
    run = NS(reduction=NS(busy_s=nbytes * 3 / 819e9 * 4),
             window={"steps": 3, "step_work": (flops, nbytes)},
             peaks=peaks.peaks("TPU v5 lite"), devices=[None])
    assert read(run) == pytest.approx(25.0)
    assert run.window["bounds"]["train_roofline"] == "bytes"
    run.reduction = NS(busy_s=0.0)
    with pytest.raises(ValueError, match="no device op"):
        read(run)
    run.reduction = None                      # an untraced run reads nothing
    assert read(run) is None


def test_roofline_names_its_bound():
    v5e = peaks.peaks("TPU v5 lite")
    share, bound = work.roofline(0.0, 819e9, 2.0, v5e, 1)
    assert (share, bound) == (pytest.approx(50.0), "bytes")
    share, bound = work.roofline(4 * 197e12, 0.0, 4.0, v5e, 4)
    assert (share, bound) == (pytest.approx(25.0), "flops")
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v0 unknown")
