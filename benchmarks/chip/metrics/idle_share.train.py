"""Idle share of the device over the traced training window: 1 minus the
union of device-op intervals over the window, the highest of the chips."""


def read(run):
    red = run.reduction
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * red.idle_share_max()
