#!/usr/bin/env python3
"""Run one benchmark cell once on the chip(s) of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. One process:
set-up (inputs and weights from ``--seed`` on the device, the program built
and compiled, from the persistent cache in ``benchmarks/chip/.jax_cache``
after a first run), then a measured window of at least ``--seconds`` that
closes at the end of the unit of work running at that time, then the check of
what the window produced against the plain reference.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` records a
profiler trace of the window and prints the per-layer metrics, the device's
busy and window seconds, and a breakdown of device time and idle gaps.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, then ``checks``: each number compared
with its limit); the checks are also the last lines of stderr. Without a TPU,
with fewer chips than the cell asks for, or outside a checkout of the
repository, it prints no result and exits non-zero.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback
from pathlib import Path

T_START = time.perf_counter()
HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def _fail(msg: str, code: int) -> int:
    print(f"benchmarks/chip/run.py: {msg}", file=sys.stderr)
    return code


def measure(cell_name: str, seed: int, seconds: float, trace: bool):
    """Run the cell once: (the result line's object, the checks)."""
    from chip import harness, peaks
    from chip import trace as trace_mod

    cell = harness.find_cell(cell_name)
    kind = harness.kind_module(cell.traffic)
    devices = harness.chips(cell.chips)
    harness.use_compile_cache()
    run = harness.Run(cell=cell, seed=seed, seconds=seconds, devices=devices,
                      mesh=harness.mesh_of(devices),
                      peaks=peaks.peaks(devices[0].device_kind))
    clock = harness.CompileClock()
    try:
        kind.setup(run)
        setup_s = time.perf_counter() - T_START
        compiles = clock.count
        trace_dir = HERE / ".trace" / cell_name if trace else None
        with harness.profiled(trace_dir), harness.span(trace_mod.WINDOW_SPAN):
            kind.window(run)
        window_compiles = clock.count - compiles
    finally:
        clock.close()
    peak = harness.memory_peak(devices)
    kind.release(run)
    checks = kind.check(run)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": all(c.ok for c in checks),
              "attempted": run.window["attempted"],
              "failed": run.window["failed"],
              "window_compiles": window_compiles}
    if trace:
        run.reduction = trace_mod.reduce_dir(trace_dir, devices)
        device.update(busy_s=run.reduction.busy_s,
                      window_s=run.reduction.window_s)
        metrics = {}
        for m in cell.per_layer:
            value = harness.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = run.reduction.breakdown()
        result["bounds"] = run.window.get("bounds", {})
    else:
        values = dict(run.window["metrics"], setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro" / "__init__.py").is_file():
        return _fail(f"no repro package under {ROOT / 'src'}: run from a "
                     "checkout of the repository", 2)
    sys.path[:0] = [str(HERE.parent), str(ROOT / "src")]
    from chip import harness

    try:
        result, checks = measure(args.workload, args.seed, args.seconds,
                                 bool(args.trace))
    except harness.BenchError as e:
        return _fail(str(e), 3)
    except Exception:                       # noqa: BLE001 - reported, exit != 0
        traceback.print_exc()
        return _fail("the run failed", 1)
    harness.print_result(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
