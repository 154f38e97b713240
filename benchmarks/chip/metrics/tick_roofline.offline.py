"""The window's ticks' least time on the chip over device busy time, in %.

The least time is the larger of the bytes over the HBM peak and the
operations over the compute peak (`chip.work.roofline`), counted from the
configuration's shapes, the lanes, the ticks and the input events only.
"""

from chip import work


def read(trace, record):
    w = record.traced.get("work")
    if not w or trace["busy_s"] <= 0:
        return None
    least = work.roofline(w, record.traced["device_kind"],
                          record.traced["chips"])
    return 100.0 * least["seconds"] / trace["busy_s"]
