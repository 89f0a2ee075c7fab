"""Device milliseconds per training step in stage ``table_grad``: the hash
tables' gradient (``dvnr.encode`` under a transpose). Mean over the chips;
the stage rule is ``stages.py``'s."""
from chip import stages


def read(run):
    return stages.stage_ms(run, "table_grad")
