"""Compiles of the chunk program (``dvnr_train_chunk``) during the traced
window, by the program's own count of backend compiles by function name: a
re-placed input recompiles without retracing, and is counted."""
from chip import stages


def read(run):
    return stages.window_compiles(run)
