"""Device milliseconds per training step in stage ``sample``: the batch draws
and the trilinear target gather (``dvnr.sample``). Mean over the chips; the
stage rule is ``stages.py``'s."""
from chip import stages


def read(run):
    return stages.stage_ms(run, "sample")
