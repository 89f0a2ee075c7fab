"""Share of the roofline of the hash tables' gradient.

Its least bytes a step on a chip (``table_work.py``: the sort's operands
read and written once, the touched rows written once) over the peak
bandwidth, over the device time of stage ``table_grad`` a step (mean over
the chips; ``stages.py``). The compiler fuses the tables' AdamW update
into that stage, which adds time and no counted bytes: the share is a lower
bound. The bound is bytes: the stage does no matrix work.
"""
from chip import stages, table_work


def read(run):
    ms = stages.stage_ms(run, "table_grad")
    if not ms:
        return None
    config = run.cell.config
    nbytes = table_work.table_grad_bytes(config["model"], config["ranks"])
    per_chip = nbytes / len(run.devices)
    return 100.0 * per_chip / run.peaks.hbm_bytes_per_s / (1e-3 * ms)
