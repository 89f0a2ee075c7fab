"""Names of the training step's stages in a profiler trace, and compiles
counted by program.

- Stage scopes (``jax.named_scope``) go where the work is, so every step path
  carries them. They are metadata only: the compiled instructions are the
  same, and each instruction's ``op_name`` holds its stage, wrapped in the
  transforms that made it (``vmap(jvp(dvnr.encode))`` forward,
  ``transpose(jvp(dvnr.encode))`` the tables' gradient).
- :func:`compile_log` holds the process's backend compiles (persistent-cache
  reads included) by jitted function name, end time and seconds;
  :func:`compiles` counts them, placement-only recompiles included (those
  do not retrace, so a count taken at trace time misses them);
  :func:`cache_hits` counts persistent-cache reads. The listeners are
  registered when this module is imported.
- :func:`table_readouts` holds, for each trace of the tables' gradient,
  how many levels its readout searched and how many it wrote (the choice is
  made from the shapes, at trace time, so no chip is needed to see it).
"""
from __future__ import annotations

import threading
import time

import jax

SAMPLE = "dvnr.sample"          # batch draws and the trilinear target gather
ENCODE = "dvnr.encode"          # hash-grid lookup (its transpose: table grad)
MLP = "dvnr.mlp"                # the fused MLP, forward and backward
ADAM = "dvnr.adam"              # the AdamW update
STAGES = (SAMPLE, ENCODE, MLP, ADAM)
# The tables' gradient's row readout, a sub-scope of the encode's transpose:
# not ``dvnr.``, so its ops keep the innermost stage scope ``dvnr.encode``.
TABLE_ROWS = "table_rows"

CHUNK_PROGRAM = "dvnr_train_chunk"      # the scan-fused chunk's function name

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_compiled: list[tuple[str, int, float]] = []    # (name, end time_ns, seconds)
_cache_hits: list[int] = []                     # time_ns
_readouts: list[tuple[int, int]] = []           # (levels searched, written)


def _on_duration(event: str, duration: float, *, fun_name: str = "", **_):
    if event == COMPILE_EVENT:
        if fun_name.startswith("jit(") and fun_name.endswith(")"):
            fun_name = fun_name[4:-1]
        with _lock:
            _compiled.append((fun_name, time.time_ns(), duration))


def _on_event(event: str, **_):
    if event == CACHE_HIT_EVENT:
        with _lock:
            _cache_hits.append(time.time_ns())


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def _within(t: int, since_ns: int, until_ns) -> bool:
    return since_ns <= t and (until_ns is None or t <= until_ns)


def compile_log(fun_name=None, since_ns: int = 0, until_ns=None) -> list:
    """Seconds of each backend compile of the jitted function ``fun_name``
    (of every function when None) since this module was imported, of those
    that ended in ``[since_ns, until_ns]`` on the wall clock
    (``time.time_ns()``) when bounds are given."""
    with _lock:
        return [s for name, t, s in _compiled
                if fun_name in (None, name) and _within(t, since_ns, until_ns)]


def compiles(fun_name=None, since_ns: int = 0, until_ns=None) -> int:
    """How many :func:`compile_log` holds."""
    return len(compile_log(fun_name, since_ns, until_ns))


def cache_hits(since_ns: int = 0, until_ns=None) -> int:
    """Persistent compilation-cache reads since this module was imported,
    within the same bounds as :func:`compile_log`."""
    with _lock:
        return sum(_within(t, since_ns, until_ns) for t in _cache_hits)


def record_table_readout(searched: int, written: int) -> None:
    """Called by the tables' gradient as it is traced: how many of its
    levels it reads out by a row search and how many by the tile writer."""
    with _lock:
        _readouts.append((searched, written))


def table_readouts() -> list:
    """(levels searched, levels written) of each trace of the tables'
    gradient since this module was imported, oldest first."""
    with _lock:
        return list(_readouts)
