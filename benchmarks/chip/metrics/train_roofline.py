"""Share of the roofline of the training window's device time.

The least time the chips could take for the window's steps, the larger of
their FLOPs over peak and their bytes over peak bandwidth (``work.py``'s
counts), over the device's busy time in the traced window (the union of its
ops' intervals, averaged over the chips). Every program that runs on the
device in the window is the training's, whatever it is named. Which of the
two bounds it is in the result's ``bounds``.
"""
from chip import work


def read(run):
    red, w = run.reduction, run.window
    if red is None:
        return None
    if red.busy_s <= 0:
        raise ValueError("no device op ran in the training window")
    flops, nbytes = w["step_work"]
    share, bound = work.roofline(flops * w["steps"], nbytes * w["steps"],
                                 red.busy_s, run.peaks, len(run.devices))
    run.window.setdefault("bounds", {})["train_roofline"] = bound
    return share
