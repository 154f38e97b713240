"""Device busy time per simulated lane-tick in the traced window, in us."""


def read(trace, record):
    lane_ticks = record.traced.get("lane_ticks")
    if not lane_ticks or trace["busy_s"] <= 0:
        return None
    return 1e6 * trace["busy_s"] / lane_ticks
