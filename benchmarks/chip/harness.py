"""What every cell shares: finding the cell's files by name, the device
check, the compile clock, host spans, the traced window, the metric readers,
the checks against their limits and the result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Its configuration
is ``configs/<config>.json``, its traffic ``traffic/<traffic>.json`` (the
``kind`` key names its module in ``kinds/``), its limits
``limits/<cell>.json``, and each per-layer metric ``metrics/<metric>.py``.
Adding any of these needs new files only.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import sys
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional

HERE = Path(__file__).resolve().parent          # benchmarks/chip
ROOT = HERE.parents[1]                          # the checkout


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, a missing file, ...)."""


# --------------------------------------------------------------------------- #
# Files by name
# --------------------------------------------------------------------------- #
def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"missing {path.relative_to(ROOT)}") from None


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list          # BENCHMARK.json entries this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = load_json(ROOT / "BENCHMARK.json") if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[w["config"]]["file"])
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        traffic=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(HERE / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def kind_module(traffic: dict):
    return importlib.import_module(f"chip.kinds.{traffic['kind']}")


def metric_reader(name: str):
    """``metrics/<name>.py``'s ``read(run)``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chip.metrics.{name.replace('.', '_')}", path)
    if spec is None or not path.is_file():
        raise BenchError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------------- #
# Devices, compiles, spans
# --------------------------------------------------------------------------- #
def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program in it."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(HERE / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips(n: int):
    """The first ``n`` TPU devices; raises when there are fewer."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devices[0].platform!r} "
                         f"({devices[0].device_kind})")
    if len(devices) < n:
        raise BenchError(f"the cell needs {n} chips; JAX found "
                         f"{len(devices)}")
    return devices[:n]


def mesh_of(devices):
    """A 2-D mesh of the devices (2x2 for four), or None for one."""
    if len(devices) == 1:
        return None
    import numpy as np
    from jax.sharding import Mesh

    side = int(math.isqrt(len(devices)))
    return Mesh(np.asarray(devices).reshape(side, -1), ("data", "model"))


class CompileClock:
    """Backend compiles seen through JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def span(name: str):
    """A host span in the profiler's trace (free when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@contextmanager
def profiled(directory: Optional[Path]):
    """The profiler on around the block when ``directory`` is given."""
    if directory is None:
        yield
        return
    import jax

    directory.mkdir(parents=True, exist_ok=True)
    for old in directory.rglob("*.xplane.pb"):
        old.unlink()
    jax.profiler.start_trace(str(directory))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


# --------------------------------------------------------------------------- #
# One run
# --------------------------------------------------------------------------- #
@dataclass
class Run:
    """What a run hands from its traffic kind to the metric readers."""

    cell: Cell
    seed: int
    seconds: float
    devices: list
    mesh: Any = None
    program: dict = field(default_factory=dict)   # overrides (controls only)
    state: dict = field(default_factory=dict)     # the traffic kind's own
    window: dict = field(default_factory=dict)    # the traffic kind's readings
    reduction: Any = None                         # trace.Reduction
    peaks: Any = None


@dataclass(frozen=True)
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def print_result(result: dict, checks: list[Check]) -> None:
    """The checks as the last lines on stderr, then the result line."""
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r})"
              f"{'' if c.ok else '  FAILED'}", file=sys.stderr)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    sys.stderr.flush()
    print(json.dumps(result), flush=True)

