"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

- busy: the union of the intervals in which an XLA op ran on a device
  (the device plane's op line; its program line where it has no op
  line), clipped to the window;
- window: the benchmark's own ``bench.window`` host span;
- idle share: 1 - busy / window, per device;
- per-op device time: the sum of each op's durations, averaged over the
  devices, of the ops that hold no other op (a loop's body ops count, the
  loop that holds them does not); an op is named by its HLO name and
  result type (``%fusion.12 = f32[8,4]``);
- idle gaps: each stretch of the window in which no op ran on a device,
  cut where a ``bench.*`` host span opens or closes, each piece named by the
  innermost span open in it, summed by that name.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_name(text: str) -> str:
    """``%fusion.12 = f32[8,4]`` of the trace's whole HLO text of an op."""
    return " ".join(text.split("{", 1)[0].split(" ")[:3])


def leaves(events):
    """The events that hold no other event of their line."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.duration_ns))
    out = []
    for e, nxt in zip(evs, evs[1:] + [None]):
        end = e.start_ns + e.duration_ns
        if nxt is None or not (nxt.start_ns < end
                               and nxt.start_ns + nxt.duration_ns <= end):
            out.append(e)
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclass
class Reduction:
    window_s: float
    busy: list                      # per device: busy seconds in the window
    ops: dict                       # op name -> seconds, mean over devices
    gaps: dict                      # host span -> idle seconds, mean

    @property
    def busy_s(self) -> float:
        return sum(self.busy) / len(self.busy)

    def idle_share_max(self) -> float:
        return max(1.0 - b / self.window_s for b in self.busy)

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _host_spans(planes):
    """(start, end, name) of every ``bench.*`` host span."""
    spans = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
    return spans


def _innermost(spans, t):
    best = None
    for s, e, name in spans:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2]


def _split(spans, lo, hi):
    """(start, end, span name) pieces of [lo, hi), cut where a host span
    opens or closes, each named by the innermost span open in it."""
    cuts = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e)
                              if lo < t < hi})
    return [(a, b, _innermost(spans, (a + b) / 2))
            for a, b in zip(cuts, cuts[1:]) if b > a]


def reduce(planes, device_ids) -> Reduction:
    """Reduce the planes of one trace; ``device_ids`` are the ids of the
    devices the cell used."""
    planes = list(planes)
    spans = _host_spans(planes)
    windows = [(s, e) for s, e, name in spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = windows[-1]
    busy, gaps, ops = [], defaultdict(float), defaultdict(float)
    wanted = {f"{DEVICE_PREFIX}{i}" for i in device_ids}
    devices = [p for p in planes if p.name in wanted]
    if len(devices) != len(wanted):
        raise ValueError(f"trace has device planes "
                         f"{sorted(p.name for p in planes)}, wanted "
                         f"{sorted(wanted)}")
    n = len(devices)
    for plane in devices:
        intervals = {OP_LINE: [], MODULE_LINE: []}
        for line in plane.lines:
            if line.name not in intervals:
                continue
            for e in line.events:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if t > lo and s < hi:
                    intervals[line.name].append((s, t))
            if line.name == OP_LINE:
                for e in leaves(line.events):
                    s, t = e.start_ns, e.start_ns + e.duration_ns
                    if t > lo and s < hi:
                        ops[op_name(e.name)] += (min(t, hi) - max(s, lo)) \
                            / 1e9 / n
        # a device whose trace has no op line is busy while a program runs
        merged = clip(union(intervals[OP_LINE] or intervals[MODULE_LINE]),
                      lo, hi)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            for a, b, name in _split(spans, s, e):
                gaps[name] += (b - a) / 1e9 / n
    return Reduction(window_s=(hi - lo) / 1e9, busy=busy, ops=dict(ops),
                     gaps=dict(gaps))


def reduce_dir(directory: Path, devices) -> Reduction:
    """Reduce the newest trace under ``directory``."""
    import jax

    files = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise ValueError(f"no trace under {directory}")
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    return reduce(data.planes, [d.id for d in devices])
