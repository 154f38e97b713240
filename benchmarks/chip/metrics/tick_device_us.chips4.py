"""Device busy time per simulated lane-tick of the sharded tick, in us.

Busy time is averaged over the chips that ran an operation, so each lane-
tick counts the time one TPU chip spends on its block of simulated chips.
"""

from chip import harness


def read(trace, record):
    return harness.reader("tick_device_us.offline")(trace, record)
