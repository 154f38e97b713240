"""The comparison that decides ``correct``: program against reference.

Each number compared is a relative gap, the worst over every row (lane or
tenant) and every field of its group:

    |program - reference| / max(|reference|, 1)

for the accumulated `StepStats` fields, grouped by the layer that produces
them, and ``max |program - reference| / max |reference|`` over the
currents compared.  Each number has its own limit in ``limits/<cell>.json``.
"""

from __future__ import annotations

import numpy as np

from chip.reference import FIELDS

GROUPS = {
    "aer": ("events", "encode_energy"),
    "arbiter": ("encode_latency",),
    "cam": ("cam_searches", "cam_energy", "cam_time_ns"),
    "noc": ("noc_hops", "noc_latency", "noc_energy", "chip_hops",
            "chip_latency", "chip_energy"),
}


def stats_gaps(program, reference) -> dict:
    """{group: worst relative gap} of two (rows, len(FIELDS)) arrays."""
    program = np.asarray(program, np.float64)
    reference = np.asarray(reference, np.float64)
    if program.shape != reference.shape:
        raise ValueError(f"stats of shape {program.shape} against a "
                         f"reference of shape {reference.shape}")
    gap = np.abs(program - reference) / np.maximum(np.abs(reference), 1.0)
    gap = np.where(np.isfinite(gap), gap, np.inf)
    out = {}
    for group, fields in GROUPS.items():
        cols = [FIELDS.index(f) for f in fields]
        out[group] = float(gap[:, cols].max()) if len(gap) else np.inf
    return out


def currents_gap(program, reference) -> float:
    """Worst absolute gap of the currents over the largest reference value."""
    program = np.asarray(program, np.float64)
    reference = np.asarray(reference, np.float64)
    if program.shape != reference.shape:
        raise ValueError(f"currents of shape {program.shape} against a "
                         f"reference of shape {reference.shape}")
    gap = float(np.max(np.abs(program - reference), initial=0.0))
    scale = float(np.max(np.abs(reference), initial=0.0))
    return gap / max(scale, np.finfo(np.float64).tiny) if np.isfinite(gap) \
        else np.inf


def stats_rows(stats) -> np.ndarray:
    """(rows, len(FIELDS)) float64 from a `StepStats` with (rows,) leaves."""
    d = stats._asdict()
    return np.stack([np.asarray(d[f], np.float64).reshape(-1)
                     for f in FIELDS], axis=1)


def checks(readings: dict, limits: dict) -> list:
    """``[(name, value, limit)]`` for every number that has a limit.

    A reading without a limit, or a limit without a reading, is an error:
    the limits file and the driver have to name the same numbers.
    """
    if set(readings) != set(limits):
        raise ValueError(f"readings {sorted(readings)} and limits "
                         f"{sorted(limits)} name different numbers")
    return [(name, float(readings[name]), float(limits[name]))
            for name in sorted(readings)]


def passed(rows: list) -> bool:
    """Whether every number is at or under its limit."""
    return all(value <= limit for _, value, limit in rows)
