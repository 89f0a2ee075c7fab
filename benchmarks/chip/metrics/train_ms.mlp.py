"""Device milliseconds per training step in stage ``mlp``: the MLP, forward and
backward (``dvnr.mlp``). Mean over the chips; the stage rule is
``stages.py``'s."""
from chip import stages


def read(run):
    return stages.stage_ms(run, "mlp")
