"""Device time of the training window by stage of the step, and the
window's compiles of the chunk program.

An op's stage is read from the ``op_name`` of its instruction in the
compiled program the window ran: the innermost ``dvnr.*`` scope the
program put there (``repro.tracing``), with JAX's transforms around it.

- ``dvnr.sample`` is ``sample``, ``dvnr.mlp`` is ``mlp`` (forward and
  backward), ``dvnr.adam`` is ``adam``;
- ``dvnr.encode`` is ``encode``, and ``table_grad`` under ``transpose(``
  (the hash tables' gradient);
- an op with no scope, or with a scope not named here, is ``other``.

A fusion is one instruction: XLA gives it its root's ``op_name``. An
instruction a compiler pass made has none, and is named for what it serves
(``program_op_names``). The stages split the leaf ops of
``trace.Reduction.ops`` (the ops that hold no other op, mean over the
chips), so their times add up to the window's leaf-op device time.

The window's program is the trainer's ``chunk_program`` for a state placed
as the window's calls found it: a trainer built as set-up builds it (the
same inputs, weights and options), one chunk run (a chunk's output is what
every window call takes), then the compile, served from the persistent
cache. An op the program text does not hold is ``unmatched``, kept apart
from the stages: the rebuilt program is then not the one the window ran,
and past ``UNMATCHED_SHARE`` of the leaf-op time the readers raise. A
program without ``chunk_program`` (no stage scopes either) reads nothing.
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

from chip import trace

STAGES = ("sample", "encode", "table_grad", "mlp", "adam", "other")
SCOPES = ("sample", "encode", "mlp", "adam")       # dvnr.<scope>
CHUNK_PROGRAM = "dvnr_train_chunk"
TRACE_DIR = Path(__file__).resolve().parent / ".trace"
UNMATCHED_SHARE = 1e-3          # of the leaf-op time, at most

_SCOPE = re.compile(r"dvnr\.([A-Za-z_]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([^,\s]+)")
_REF = re.compile(r"%[\w.\-]+")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")


def stage(op_name: str) -> str:
    """The stage of an instruction whose metadata reads ``op_name``."""
    found = None
    for part in op_name.split("/"):
        for m in _SCOPE.finditer(part):
            found = (part, m)
    if found is None:
        return "other"
    part, m = found
    name = m.group(1) if m.group(1) in SCOPES else "other"
    if name == "encode" and "transpose(" in part[:m.start()]:
        return "table_grad"
    return name


def program_op_names(text: str) -> dict:
    """``{trace op name: op_name}`` of every instruction of a compiled
    program's text; the trace op name is ``trace.op_name`` of the
    instruction (``%fusion.12 = f32[8,4]``).

    An instruction with no ``op_name`` of its own is one a compiler pass
    made (the pieces of a split scatter, the sort of its indices, a copy
    loop that lays out an operand). It takes, in this order: that of the
    computation it calls (its root's, else its last instruction's that has
    one), that of the first of its users that has one, that of the
    instruction that calls its own computation; each found the same way.
    So the work is named for what it serves."""
    own, key, refs, users, callee, caller = {}, {}, {}, {}, {}, {}
    comps, comp = {}, None
    for raw in text.splitlines():
        line = raw.strip()
        head = _COMPUTATION.match(raw)
        if head:
            comp = comps.setdefault("%" + head.group(1), [])
            continue
        root = line.startswith("ROOT ")
        if root:
            line = line[5:]
        if not line.startswith("%") or " = " not in line:
            continue
        ident = line.split(" ", 1)[0]
        m, c = _OP_NAME.search(line), _CALLS.search(line)
        own[ident] = m.group(1) if m else ""
        key[ident] = trace.op_name(line)
        users[ident] = []
        if c:
            callee[ident] = "%" + c.group(1)
        refs[ident] = set(_REF.findall(line)) - {ident}
        if comp is not None:
            comp.append((root, ident))
    for ident, found in refs.items():
        for ref in found:
            if ref in comps:
                caller.setdefault(ref, ident)
            elif ref in users:
                users[ref].append(ident)
    home = {i: name for name, body in comps.items() for _, i in body}
    down, full = {}, {}

    def called(ident):          # its own, else its called computation's
        if ident not in down:
            body = comps.get(callee.get(ident), [])
            inner = [i for r, i in body if r] + [i for _, i in body[::-1]]
            down[ident] = own[ident] or next(
                (op for op in map(called, inner) if op), "")
        return down[ident]

    def named(ident):           # ... else its users', else its caller's
        if ident not in full:
            up = caller.get(home.get(ident))
            full[ident] = (called(ident)
                           or next((op for op in map(named, users[ident])
                                    if op), "")
                           or (named(up) if up else ""))
        return full[ident]

    return {key[i]: named(i) for i in own}


def by_stage(ops: dict, op_names: dict) -> dict:
    """Seconds per stage of ``ops`` (``{trace op name: seconds}``), and
    under ``unmatched`` those of the ops ``op_names`` does not hold."""
    out = dict.fromkeys(STAGES + ("unmatched",), 0.0)
    for name, seconds in ops.items():
        out[stage(op_names[name]) if name in op_names
            else "unmatched"] += seconds
    return out


def window_program_text(run):
    """The compiled text of the chunk program the window ran, or None when
    the program has no ``chunk_program``."""
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.configs.dvnr import DVNRConfig
    from repro.core.trainer import DVNRTrainer

    from chip import inputs
    from chip.kinds.train import Part

    if not hasattr(DVNRTrainer, "chunk_program"):
        return None
    config, traffic = run.cell.config, run.cell.traffic
    model, P = config["model"], config["ranks"]
    g, n = config["ghost"], config["local"]
    parts = [Part(o, e, g, (n, n, n), 0.0, 1.0) for o, e in inputs.boxes(P)]
    vols = run.state["vols"]
    key = jnp.asarray(run.state["key"], jnp.uint32)
    chunk = traffic["chunk_steps"]
    w0 = inputs.make_weights(model, P, inputs.draws(run.seed).weights_key,
                             traffic["table_range"])
    with jax.default_matmul_precision(config["matmul_precision"]):
        _, info = api.train(parts, DVNRConfig(**model), backend="auto",
                            mesh=run.mesh, steps=0, key=key,
                            cached_params=w0, volumes=vols, **run.program)
        trainer = info["trainer"]
        state, losses = trainer.train_chunk(info["state"], vols, chunk,
                                            key=key)
        jax.block_until_ready(losses)
        return trainer.chunk_program(state, vols, chunk, key=key).as_text()


def stage_seconds(run):
    """Seconds per stage of the traced window (mean over chips), or None;
    worked out once a run. Raises when more than ``UNMATCHED_SHARE`` of the
    leaf-op time is in ops the rebuilt program does not hold."""
    if "stage_seconds" not in run.state:
        text = None if run.reduction is None else window_program_text(run)
        seconds = None if text is None else by_stage(
            run.reduction.ops, program_op_names(text))
        run.state["stage_seconds"] = seconds
        if seconds is not None:
            lost, total = seconds["unmatched"], sum(seconds.values())
            print(f"stages: {lost} s of {total} s of leaf-op time in ops "
                  "the window's program does not hold", file=sys.stderr)
            if lost > UNMATCHED_SHARE * total:
                raise ValueError(
                    f"{lost} s of the window's {total} s of leaf-op time is "
                    "in ops the rebuilt chunk program does not hold: it is "
                    "not the program the window ran")
    return run.state["stage_seconds"]


def stage_ms(run, name: str):
    """Device milliseconds per training step in stage ``name``."""
    seconds = stage_seconds(run)
    if seconds is None:
        return None
    return 1e3 * seconds[name] / run.window["steps"]


def window_wall_ns(planes):
    """The ``bench.window`` span on the wall clock (``time.time_ns()``):
    the trace's times count from its session's ``profile_start_time``."""
    start = None
    for plane in planes:
        for k, v in getattr(plane, "stats", ()):
            if k == "profile_start_time":
                start = int(v)
    windows = [(s, e) for s, e, name in trace._host_spans(planes)
               if name == trace.WINDOW_SPAN]
    if start is None or not windows:
        return None
    lo, hi = windows[-1]
    return start + int(lo), start + int(hi)


def window_compiles(run):
    """Compiles of the chunk program that ended inside the traced window,
    by the program's own count (``repro.tracing.compiles``), or None."""
    import jax

    try:
        from repro import tracing
    except ImportError:
        return None
    files = sorted((TRACE_DIR / run.cell.name).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if run.reduction is None or not files:
        return None
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    bounds = window_wall_ns(list(data.planes))
    if bounds is None:
        return None
    return tracing.compiles(CHUNK_PROGRAM, *bounds)
