"""Device milliseconds per training step of the hash tables' gradient's row
readout: the ops of stage ``table_grad`` whose ``op_name`` holds the
``table_rows`` scope (the per-row search, or the write of each run's total
at its row). Mean over the chips; the program's op names and the stage rule
are ``stages.py``'s. A program without the scope reads nothing."""
from chip import stages

SCOPE = "table_rows"


def read(run):
    if run.reduction is None:
        return None
    if "table_rows_op_names" not in run.state:
        text = stages.window_program_text(run)
        run.state["table_rows_op_names"] = (
            None if text is None else stages.program_op_names(text))
    names = run.state["table_rows_op_names"]
    if not names or not any(SCOPE in n for n in names.values()):
        return None
    seconds = sum(s for op, s in run.reduction.ops.items()
                  if op in names and SCOPE in names[op]
                  and stages.stage(names[op]) == "table_grad")
    return 1e3 * seconds / run.window["steps"]
