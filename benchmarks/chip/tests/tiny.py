"""Tiny cells for running the harness on the CPU in tests."""
from __future__ import annotations

import copy
import json
from pathlib import Path

from chip import harness

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent

TINY_MODEL = {
    "n_levels": 2, "n_features_per_level": 2, "log2_hashmap_size": 7,
    "base_resolution": 4, "per_level_scale": 2.0, "n_neurons": 16,
    "n_hidden_layers": 1, "out_dim": 1, "lrate": 0.005, "lrate_decay": -1,
    "epochs": 2, "batch_size": 512, "adam_beta1": 0.9, "adam_beta2": 0.999,
    "adam_eps": 1e-08, "weight_decay": 1e-09, "boundary_lambda": 0.15,
    "boundary_sigma": 0.005, "target_loss": 0.0, "precision": "f32",
}


def config(ranks: int = 2, local: int = 8) -> dict:
    return {"name": "tiny", "ranks": ranks, "local": local, "ghost": 1,
            "field": "cloverleaf", "chips": 1, "matmul_precision": "highest",
            "model": dict(TINY_MODEL)}


def traffic(name: str, **kw) -> dict:
    t = json.loads((CHIP / "traffic" / f"{name}.json").read_text())
    t.update(kw)
    return t


def limits(cell: str) -> dict:
    return json.loads((CHIP / "limits" / f"{cell}.json").read_text())


def run(traffic_name: str, cell: str, seed: int = 7, seconds: float = 0.0,
        program=None, ranks: int = 2, devices=None, mesh=None,
        **traffic_kw) -> harness.Run:
    import jax
    from chip import peaks

    c = harness.Cell(name=cell, chips=1, config=config(ranks),
                     traffic=traffic(traffic_name, **traffic_kw),
                     limits=limits(cell), end_to_end=[], per_layer=[])
    devices = devices or jax.devices()[:1]
    return harness.Run(cell=c, seed=seed, seconds=seconds, devices=devices,
                       mesh=mesh, program=copy.deepcopy(program or {}),
                       peaks=peaks.PEAKS["TPU v5 lite"])


def drive(r: harness.Run) -> list:
    """Set-up, window, release and check of one tiny run, off the chip."""
    kind = harness.kind_module(r.cell.traffic)
    kind.setup(r)
    kind.window(r)
    kind.release(r)
    return kind.check(r)
