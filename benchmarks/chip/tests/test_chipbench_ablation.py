"""The ablation network (10 x 8 levels, a 64 x 3 MLP) through the train
cell's own path on the CPU, against the plain reference, and the readers of
the tables' gradient's row readout and roofline share."""
import json

import pytest

from chip import harness, stages, table_work
from chip.kinds import train
from chip.tests import tiny
from chip.tests.test_chipbench_stages import BODY, HLO, _traced

CONFIG = json.loads((tiny.CHIP / "configs" / "ablation512-x4.json")
                    .read_text())


@pytest.mark.parametrize("op_name", [
    f"{BODY}/transpose(jvp(dvnr.encode))/table_rows/gather",
    f"{BODY}/vmap(transpose(jvp(dvnr.encode)))/vmap(table_rows)/scatter",
])
def test_the_row_readout_reads_as_the_tables_gradient(op_name):
    assert stages.stage(op_name) == "table_grad"


@pytest.fixture(scope="module")
def ablation():
    """The first chunk of 2 ranks of 16^3 with the ablation network at
    T = 2^12 and a batch of 64 (8N = 512 corners a level, T = 8 x 8N),
    through ``api.train``'s trainer as the cell drives it, and the
    reference's readings of it."""
    config = dict(tiny.config(ranks=2, local=16),
                  model=dict(CONFIG["model"], log2_hashmap_size=12,
                             batch_size=64))
    cell = harness.Cell(name="ablation512-x4.train", chips=1, config=config,
                        traffic=tiny.traffic("train"),
                        limits=tiny.limits("ablation512-x4.train"),
                        end_to_end=[], per_layer=[])
    run = tiny.run("train", "ablation512-x4.train")
    run.cell = cell
    train.setup(run)
    train.release(run)
    return run, train.compare(run, train.reference(run))


def test_the_ablation_chunk_matches_the_reference(ablation):
    """Both sides are float32 on the CPU and differ in the order of their
    sums alone: the program sums each table row's corners after a sort, the
    reference's autodiff scatters them in place, and the matmuls block
    differently. The gaps read about 1e-7 (loss), 4e-8 (first moment) and
    5e-9 (change); the same program on its bf16 path reads 3.6e-3, 0.18 and
    2.6e-2 (CPU), so each bound fails it."""
    _, gaps = ablation
    # each step's loss, relative: a mean of 64 absolute errors, a few ulps
    assert gaps["loss_gap"] < 1e-5, gaps
    # Adam's first moment after 4 steps, the worst leaf's gap of norms: the
    # gradient's round-off carried through the MLP's four matmuls
    assert gaps["moment_gap"] < 1e-5, gaps
    # the parameters' change, the worst leaf's gap of norms: Adam divides by
    # the root of the second moment, so a gradient that is round-off moves
    # its entry by up to the learning rate; leaves whose gradient is under a
    # thousandth of the median leaf's are left out
    assert gaps["change_gap"] < 1e-5, gaps


@pytest.fixture(scope="module")
def window_text(ablation):
    """The tiny ablation run's window program, in which the tables'
    gradient writes each run's total at its row."""
    run, _ = ablation
    run.window["steps"] = 4
    return stages.window_program_text(run)


def test_row_readout_and_roofline_readers(ablation, window_text,
                                          monkeypatch):
    run, _ = ablation
    monkeypatch.setattr(stages, "window_program_text", lambda run: window_text)
    ops = dict.fromkeys(stages.program_op_names(window_text), 1e-3)
    traced = _traced(run, ops)
    rows = harness.metric_reader("train_ms.table_rows")(traced)
    grad = harness.metric_reader("train_ms.table_grad")(traced)
    assert 0 < rows < grad
    share = harness.metric_reader("table_grad_roofline")(traced)
    model = run.cell.config["model"]
    want = (table_work.table_grad_bytes(model, 2)
            / traced.peaks.hbm_bytes_per_s / (1e-3 * grad))
    assert share == pytest.approx(100 * want, rel=1e-12)


def test_row_readout_reads_nothing_without_its_scope(ablation, window_text,
                                                     monkeypatch):
    """A program without the ``table_rows`` scope, as its parent: the reader
    reads nothing and raises nothing."""
    run, _ = ablation
    ops = dict.fromkeys(stages.program_op_names(window_text), 1e-3)
    traced = _traced(run, ops)
    monkeypatch.setattr(stages, "window_program_text", lambda run: HLO)
    assert harness.metric_reader("train_ms.table_rows")(traced) is None


def test_table_grad_bytes_of_the_cells():
    # production256-x8: 8 ranks x 5 levels x (2 x 5 x 524,288 + 8,192 x 4)
    # words; ablation512-x4: 4 x 10 x (2 x 9 x 131,072 + 131,072 x 8)
    p256 = json.loads((tiny.CHIP / "configs" / "production256-x8.json")
                      .read_text())
    assert table_work.table_grad_bytes(p256["model"], 8) \
        == 4 * 8 * 5 * (2 * 5 * 524_288 + 8_192 * 4)
    assert table_work.table_grad_bytes(CONFIG["model"], 4) \
        == 4 * 4 * 10 * (2 * 9 * 131_072 + 131_072 * 8)
