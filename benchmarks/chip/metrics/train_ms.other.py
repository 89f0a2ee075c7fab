"""Device milliseconds per training step in stage ``other``: ops of the
window's program under no stage scope. Mean over the chips; the stage rule
is ``stages.py``'s."""
from chip import stages


def read(run):
    return stages.stage_ms(run, "other")
