"""Bytes the hash tables' gradient needs, counted from the configuration.

Per level of each rank and step, the least traffic of a gradient formed by
one sort of the level's 8N corner indices with their F weighted cotangents
(``repro.kernels.hash_encoding.ops.table_grad``): the sort's 1 + F words a
corner read once and written once, and then the rows the corners touch
written once, at most min(T, 8N) rows of F words. Rows no corner touches
and the AdamW update are not counted, so a share of this over the stage's
device time is a lower bound.
"""
from __future__ import annotations

from chip.work import WORD


def table_grad_bytes(model: dict, ranks: int) -> float:
    """Bytes of one training step's tables' gradient over ``ranks`` ranks."""
    L, F = model["n_levels"], model["n_features_per_level"]
    T = 1 << model["log2_hashmap_size"]
    corners = 8 * model["batch_size"]
    words = 2 * (1 + F) * corners + min(T, corners) * F
    return float(ranks * L * words * WORD)
