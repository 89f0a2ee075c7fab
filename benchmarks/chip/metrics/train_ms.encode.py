"""Device milliseconds per training step in stage ``encode``: the hash-grid
lookup, forward (``dvnr.encode``). Mean over the chips; the stage rule is
``stages.py``'s."""
from chip import stages


def read(run):
    return stages.stage_ms(run, "encode")
