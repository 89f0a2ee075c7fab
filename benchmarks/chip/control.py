#!/usr/bin/env python3
"""Readings that set a training cell's limits: the sound program, the
control and the planted fault, on several seeds, in one process.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 11 12 13 \
        [--controls N] [--out FILE.json]

For every seed it prints one line of readings (the numbers the cell's check
compares) of:

- ``sound``: the program as the configuration states it;
- ``control``: the program with its own reduced-precision path switched on,
  the step that would tempt a later change (``precision="bf16"``: bf16
  weights and compute with an f32 master);
- ``half_batch``: the reference with half of each batch left out and the
  mean taken over the rest, read against the whole reference (a step that
  returns its state unchanged reads 1 by construction; a loss altered by a
  factor reads that factor less one).

The benchmark's own runs never run this; it runs where ``run.py`` does,
and at a tiny size in the tests.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

CONTROL = {"precision": "bf16"}


def as_program(run, refs) -> dict:
    """A reference's readings in the form the check reads the program's (a
    planted fault read against the whole reference)."""
    import jax
    import numpy as np

    def stack(trees):
        return jax.tree.map(lambda *x: np.stack(x), *trees)

    return {"prog_losses": np.asarray(refs["losses"]),
            "prog_m": stack(refs["m"]), "prog_params": stack(refs["params"])}


def readings(cell, seed: int, devices, mesh, program: dict, sound_refs=None):
    """(readings, reference) of one seed under ``program`` overrides; with
    no ``sound_refs``, the half-batch fault's readings too."""
    from chip import harness

    kind = harness.kind_module(cell.traffic)
    run = harness.Run(cell=cell, seed=seed, seconds=0.0, devices=devices,
                      mesh=mesh, program=program)
    kind.setup(run)
    kind.release(run)
    if sound_refs is not None:
        return kind.compare(run, sound_refs), sound_refs
    refs = kind.reference(run)
    half = kind.reference(run, batch_fraction=0.5)
    faulty = harness.Run(cell=cell, seed=seed, seconds=0.0, devices=devices,
                         state=dict(run.state, **as_program(run, half)))
    return {"sound": kind.compare(run, refs),
            "half_batch": kind.compare(faulty, refs)}, refs


def control_readings(cell, seeds, devices, mesh=None, controls=None):
    """One record per seed: the sound readings, the planted fault's, and on
    the first ``controls`` seeds (all by default) the control's."""
    for i, seed in enumerate(seeds):
        rec, refs = readings(cell, seed, devices, mesh, {})
        rec = {"seed": seed, **rec}
        if controls is None or i < controls:
            rec["control"], _ = readings(cell, seed, devices, mesh, CONTROL,
                                         sound_refs=refs)
        yield rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int,
                    help="run the control on the first N seeds only")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE.parent), str(ROOT / "src")]
    from chip import harness

    cell = harness.find_cell(args.workload)
    devices = harness.chips(cell.chips)
    harness.use_compile_cache()
    records = []
    for rec in control_readings(cell, args.seeds, devices,
                                harness.mesh_of(devices), args.controls):
        print(json.dumps(rec), flush=True)
        records.append(rec)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(records, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
