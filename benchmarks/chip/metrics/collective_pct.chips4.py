"""Share of the devices' operation time spent in collectives, in %.

The traced window's collective operations (all-gather, all-reduce,
all-to-all, collective-permute, reduce-scatter) over all operation time,
averaged over the chips that ran an operation: the exchange between the
chip mesh's devices that the sharded tick pays.
"""


def read(trace, record):
    return 100.0 * trace["collective_share"]
