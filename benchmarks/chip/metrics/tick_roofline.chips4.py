"""The whole fabric's least tick time on the cell's chips over busy time, %.

`chip.work.tick_work` of all 16 simulated chips over the peaks of the
cell's four TPU chips, read as the offline cells read theirs.
"""

from chip import harness


def read(trace, record):
    return harness.reader("tick_roofline.offline")(trace, record)
