"""Model FLOP/s utilisation of the whole training step.

The FLOPs forward and backward need per sample (``work.py``), times the
window's trained samples per second on the host clock, over the chips' bf16
peak.
"""
from chip import work


def read(run):
    w = run.window
    if not w.get("samples"):
        return None
    flops = work.train_flops_per_sample(run.cell.config["model"])
    rate = w["samples"] / w["seconds"]
    return 100.0 * flops * rate / (len(run.devices) * run.peaks.bf16_flops)
