"""Device milliseconds per training step in stage ``adam``: the AdamW update
(``dvnr.adam``). Mean over the chips; the stage rule is ``stages.py``'s."""
from chip import stages


def read(run):
    return stages.stage_ms(run, "adam")
