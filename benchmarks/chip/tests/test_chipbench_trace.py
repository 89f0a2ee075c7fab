"""The trace reduction: busy union, idle share, per-op device time, and
idle gaps named by the benchmark's host spans."""
import lzma
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chip import trace


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def synthetic():
    host = plane("/host:CPU", python=[
        ev("bench.window", 1000, 1000),
        ev("bench.train_call", 1000, 500),
        ev("bench.tick", 1500, 500),
        ev("other", 0, 5000)])
    dev0 = plane("/device:TPU:0",
                 XLA_Ops=[ev("fusion.1", 900, 300),      # clipped to 1000
                          ev("gather", 1100, 200),       # overlaps fusion.1
                          ev("scatter", 1700, 100),
                          ev("fusion.1", 1950, 100)],    # clipped at 2000
                 XLA_Modules=[ev("jit_chunk(1)", 900, 500),
                              ev("jit_frames(2)", 1700, 350)])
    dev1 = plane("/device:TPU:1", XLA_Ops=[ev("gather", 1000, 1000)],
                 XLA_Modules=[])
    return [host, dev0, dev1]


def test_busy_union_idle_and_gaps():
    red = trace.reduce(synthetic(), [0])
    assert red.window_s == pytest.approx(1e-6)
    # busy on TPU:0: [1000,1300] + [1700,1800] + [1950,2000] = 450 ns
    assert red.busy == [pytest.approx(450e-9)]
    assert red.idle_share_max() == pytest.approx(0.55)
    assert red.ops["fusion.1"] == pytest.approx(250e-9)
    assert red.ops["gather"] == pytest.approx(200e-9)
    # idle: [1300,1500) under train_call, [1500,1700) and [1800,1950)
    # under tick
    assert red.gaps == {"bench.train_call": pytest.approx(200e-9),
                        "bench.tick": pytest.approx(350e-9)}
    b = red.breakdown()
    assert b["device_ops"][0][0] == "fusion.1"
    assert b["idle_gaps"][0][0] == "bench.tick"


def test_devices_average_and_worst_chip():
    red = trace.reduce(synthetic(), [0, 1])
    assert red.busy == [pytest.approx(450e-9), pytest.approx(1000e-9)]
    assert red.busy_s == pytest.approx(725e-9)
    assert red.idle_share_max() == pytest.approx(0.55)
    assert red.ops["gather"] == pytest.approx((200e-9 + 1000e-9) / 2)


def test_missing_window_or_device_is_an_error():
    planes = synthetic()
    with pytest.raises(ValueError, match="device planes"):
        trace.reduce(planes, [0, 5])
    planes[0].lines[0].events = planes[0].lines[0].events[1:]
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(planes, [0])


def test_a_device_without_an_op_line_is_busy_while_a_program_runs():
    planes = synthetic() + [plane("/device:TPU:2", XLA_Modules=[
        ev("jit_chunk(1)", 1200, 300), ev("jit_chunk(1)", 1400, 200)])]
    red = trace.reduce(planes, [2])
    assert red.busy == [pytest.approx(400e-9)]
    assert red.ops == {}


def test_a_loop_counts_by_the_ops_it_holds_and_ops_by_short_names():
    host = plane("/host:CPU", python=[ev("bench.window", 0, 1000)])
    dev = plane("/device:TPU:0", XLA_Ops=[
        ev("%while.3 = (s32[]{:T(128)}, f32[8]{0}) while(...)", 100, 800),
        ev("%fusion.7 = f32[64,4]{0,1:T(4,128)} fusion(...)", 100, 300),
        ev("%fusion.7 = f32[64,4]{0,1:T(4,128)} fusion(...)", 500, 300),
        ev("%copy.1 = f32[8]{0:T(128)} copy(...)", 950, 50)])
    red = trace.reduce([host, dev], [0])
    assert red.busy == [pytest.approx(850e-9)]
    assert red.ops == {"%fusion.7 = f32[64,4]": pytest.approx(600e-9),
                       "%copy.1 = f32[8]": pytest.approx(50e-9)}


# The traced run of production256-x8.train on one TPU v5 lite (seed
# 2200000003, --seconds 10, --trace 1): the busy and window seconds it
# printed, and its loss read-backs as the longest idle gap.
RECORDED = (Path(__file__).parent / "data"
            / "production256-x8.train.xplane.pb.xz")
PRINTED = {"busy_s": 17.169337508, "window_s": 17.174180902}


def test_a_trace_recorded_on_the_chip_reduces_to_what_its_run_printed():
    import jax

    raw = lzma.decompress(RECORDED.read_bytes())
    data = jax.profiler.ProfileData.from_serialized_xspace(raw)
    red = trace.reduce(data.planes, [0])
    assert red.busy_s == pytest.approx(PRINTED["busy_s"], rel=1e-12)
    assert red.window_s == pytest.approx(PRINTED["window_s"], rel=1e-12)
    assert 0 < red.idle_share_max() < 1e-3
    # the ops that hold no other op account for the busy time, the scan's
    # loop is not among them, and the table-gradient scatters lead
    assert sum(red.ops.values()) == pytest.approx(red.busy_s, rel=1e-2)
    assert not any(name.startswith("%while") for name in red.ops)
    top = red.breakdown()
    assert top["device_ops"][1][0].endswith("= f32[327680,4]")
    assert top["idle_gaps"][0][0] == "bench.read_losses"
    with pytest.raises(ValueError, match="device planes"):
        trace.reduce(data.planes, [0, 1])
