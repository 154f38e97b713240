"""The least work the tick's semantics need, from shapes and event counts.

`tick_work` counts the bytes a device has to move and the operations it
has to do for scans of ``lanes`` streams of ``ticks`` ticks carrying
``events`` input spikes in all, on the fabric of ``config``.  It reads nothing of
the program, its impl or its compiled HLO, so the count is the same
whichever path implements the tick:

- the spike frames in, one byte a neuron a tick (bool);
- the currents out, four bytes a neuron a tick (float32);
- the CAM routing entries, read once per scan step (source index, valid
  bit, weight and target: 13 bytes an entry);
- per event, its source's row of the accounting tables (subscribed cores,
  entries swept, matching entries, hops, depth and one load per mesh link,
  plus the chip tier's hops, depth and chip-link loads on a multi-chip
  fabric), four bytes each, with one multiply-add each;
- per event, one multiply-add for each CAM entry it drives: on average
  ``cores x entries x fan_in / neurons`` entries.

`roofline` turns that count into the least time on a chip from the table of
peaks (``peaks.json``, keyed by ``device_kind``).
"""

from __future__ import annotations

import json
import os

from chip.reference import mesh_links

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def tick_work(config: dict, lanes: int, ticks: int, events: float,
              calls: int = 1) -> dict:
    """``{"bytes": .., "flops": ..}`` of ``calls`` scans of ``ticks`` steps.

    Each scan step advances ``lanes`` streams by one tick; ``events`` are
    the input spikes of all of them.
    """
    fab = config["fabric"]
    cores, n = fab["cores"], fab["neurons_per_core"]
    entries, chips = fab["cam_entries_per_core"], fab["chips"]
    neurons = cores * n
    row = 5 + chips * mesh_links(cores // chips)
    if chips > 1:
        row += 2 + mesh_links(chips)
    driven = cores * entries * float(config["assumed"]["fan_in"]) / neurons
    steps = calls * ticks
    moved = (steps * lanes * neurons * (1 + 4) + steps * cores * entries * 13
             + events * row * 4)
    ops = events * 2 * (row + driven)
    return {"bytes": float(moved), "flops": float(ops)}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip; an unknown ``device_kind`` is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; known: {', '.join(sorted(table))}")
    return table[device_kind]


def roofline(work: dict, device_kind: str, chips: int = 1) -> dict:
    """Least seconds for ``work`` on ``chips`` chips, and what bounds it."""
    peak = peaks(device_kind)
    t_mem = work["bytes"] / (peak["hbm_bytes_per_s"] * chips)
    t_ops = work["flops"] / (peak["flops_per_s"] * chips)
    return {"seconds": max(t_mem, t_ops),
            "bound": "hbm_bytes" if t_mem >= t_ops else "flops"}
