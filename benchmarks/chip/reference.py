"""Plain reference of the fabric tick, in NumPy, for the benchmark's check.

Written from the model's description, importing nothing of the program.
One tick takes the (cores x neurons_per_core) spike frame of the fabric
and the CAM wiring (each entry: a global source neuron ``src``, a
``valid`` bit, a ``weight`` and a ``target`` neuron of its core) to:

currents   every valid entry whose source spiked adds its weight to its
           target neuron's current: the frame times the (sources x
           neurons) matrix of summed entry weights.
events     spikes in the frame.
encode_latency
           the HAT arbiter (hierarchical four-input tree, Su et al. 2023
           §III): a core with k > 0 spikes finishes after 2 L units for the
           first grant through L = log4(n) levels, 1 unit per further
           grant, and 1 more unit each time service moves to the next
           occupied cluster of 4^(L-1) neurons (q clusters in all):
           2 L + (k - 1) + (q - 1).  The tick's value is the slowest core.
encode_energy
           address-line toggles: the grant stream of a core is its active
           addresses in ascending order, padded to n entries with the
           address n, starting after address -1.  Level l re-drives its 2
           bits whenever the prefix ``addr >> 2 l`` changes between
           neighbours; the per-core mean over the n stream entries, times
           the core's k events, summed over cores.
cam_*      an event is searched in every core that holds a valid entry for
           its source (``subs``).  Per search: match entries (valid entries
           of the source, over all searches of the tick) and mismatch
           entries (valid entries swept minus matches); energy and cycle
           time follow the CSCD CAM model calibrated to the paper's 512 x
           11-bit design point (§IV).
noc_*      one XY multicast tree per event on each chip's core mesh (cores
           row-major on a near-square grid): a trunk along the source row
           over the destination columns plus one branch in each
           destination column.  Hops are tree edges; latency is the
           deepest destination times the hop latency plus the busiest
           link's event count times the serialisation time; energy is hops
           times the hop energy.  On a remote chip the event enters at
           core 0.
chip_*     the same tree over the grid of chips (DYNAPs' inter-chip router
           tier, Moradi et al. 2017) for events with remote destinations,
           with the chip tier's constants.

Every field of a tick is summed over the ticks of a stream.  The reference
computes in float64; `accumulate(..., bf16=True)` and
``currents(..., bf16=True)`` give the control, the same arithmetic in
bfloat16, which the check must refuse.
"""

from __future__ import annotations

import math

import ml_dtypes
import numpy as np

FIELDS = ("events", "encode_latency", "encode_energy", "cam_searches",
          "cam_energy", "cam_time_ns", "noc_hops", "noc_latency",
          "noc_energy", "chip_hops", "chip_latency", "chip_energy")

# CSCD CAM model at the paper's design point (Su et al. 2023, §IV-B..D):
# cycle = t_req + settle * t_dummy(E) + t_sense + t_reset with
# t_dummy(E) = D0 + D1 log2(E); energy per search in units of one
# full-window mismatch dissipation.
CAM_T_REQ, CAM_T_RESET, CAM_T_SENSE = 0.2, 0.5, 0.3
CAM_D0, CAM_D1 = 1.425916, 0.173986
CAM_SETTLE = {(False, False): 1.00, (True, False): 0.70,
              (False, True): 0.85, (True, True): 0.58}
CAM_M_CHARGE = 9.796          # match-line swing of a matching entry
CAM_FEEDBACK_SWING = 0.6      # feedback control cuts that swing by 40%
CAM_E_SENSE_NODE = 0.02       # mismatch closed early by speculative sense
CAM_F_CONV = 518.58           # fixed energy of a search
CAM_E_CSCD_NET = 25.0         # the CSCD block, net of the removed delay line

# Transport constants (DYNAPs hierarchy, Moradi et al. 2017).
NOC_HOP_NS, NOC_SERIAL_NS, NOC_HOP_ENERGY = 1.2, 0.8, 35.0
CHIP_HOP_NS, CHIP_SERIAL_NS, CHIP_HOP_ENERGY = 12.0, 4.0, 350.0


def bf16(x):
    """``x`` rounded to bfloat16, returned as float64."""
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def _mesh(k: int):
    """(width, height) of the near-square row-major grid of ``k`` nodes."""
    w = max(1, math.ceil(math.sqrt(k)))
    return w, math.ceil(k / w)


def mesh_links(k: int) -> int:
    """Links of the near-square row-major mesh of ``k`` nodes."""
    w, h = _mesh(k)
    return h * max(w - 1, 0) + max(h - 1, 0) * w


def _tree(src: int, dests, k: int):
    """XY multicast tree on the ``k``-node mesh: (edges, depth, links).

    ``links`` is a (links,) float64 vector of events each link carries.
    """
    w, h = _mesh(k)
    loads = np.zeros(mesh_links(k))
    if not len(dests):
        return 0, 0, loads
    sx, sy = src % w, src // w
    dx = np.array([d % w for d in dests])
    dy = np.array([d // w for d in dests])
    lo, hi = min(sx, dx.min()), max(sx, dx.max())
    loads[sy * (w - 1) + np.arange(lo, hi)] = 1.0
    edges = hi - lo
    for col in np.unique(dx):
        ys = dy[dx == col]
        ylo, yhi = min(sy, ys.min()), max(sy, ys.max())
        loads[h * (w - 1) + np.arange(ylo, yhi) * w + col] = 1.0
        edges += yhi - ylo
    depth = int(np.max(np.abs(dx - sx) + np.abs(dy - sy)))
    return int(edges), depth, loads


class Reference:
    """The reference fabric for one configuration and one wiring.

    config: the benchmark's configuration dict (its ``fabric`` part).
    conn:   the host copy of the wiring, NumPy ``src``, ``valid``,
            ``weights``, ``targets``, each (cores, entries).
    """

    def __init__(self, config: dict, conn: dict):
        fab = config["fabric"]
        if fab["scheme"] != "hier_tree" or fab["noc"] != "multicast_tree":
            raise ValueError("the reference models the hier_tree arbiter "
                             "on the multicast_tree NoC only")
        self.cores = c = fab["cores"]
        self.n = n = fab["neurons_per_core"]
        self.chips = chips = fab["chips"]
        self.cpc = cpc = c // chips
        self.levels = max(1, round(math.log(n, 4)))
        cam = fab["cam"]
        self.src = np.asarray(conn["src"], np.int64)
        self.valid = np.asarray(conn["valid"], bool)
        self.weights = np.asarray(conn["weights"], np.float64)
        self.targets = np.asarray(conn["targets"], np.int64)
        self._synapses = {}
        s = c * n

        subs = np.zeros((c, s), bool)
        for core in range(c):
            subs[core, self.src[core][self.valid[core]]] = True
        valid_cnt = self.valid.sum(1).astype(np.float64)
        self.dest_counts = subs.sum(0).astype(np.float64)
        self.swept = valid_cnt @ subs
        self.hits = np.bincount(self.src[self.valid], minlength=s).astype(
            np.float64)

        w_local, w_chip = mesh_links(cpc), mesh_links(chips)
        self.hops, self.depth = np.zeros(s), np.zeros(s)
        self.links = np.zeros((s, chips * w_local))
        self.chip_hops, self.chip_depth = np.zeros(s), np.zeros(s)
        self.chip_links = np.zeros((s, w_chip))
        for i in range(s):
            dests = np.nonzero(subs[:, i])[0]
            g = i // n
            s_chip, s_local = g // cpc, g % cpc
            remote = sorted({d // cpc for d in dests} - {s_chip})
            if chips > 1:
                e, d, ld = _tree(s_chip, remote, chips)
                self.chip_hops[i], self.chip_depth[i] = e, d
                self.chip_links[i] = ld
            for chip in range(chips):
                local = [d % cpc for d in dests if d // cpc == chip]
                start = s_local if chip == s_chip else 0
                e, d, ld = _tree(start, local, cpc)
                self.hops[i] += e
                self.depth[i] = max(self.depth[i], d)
                self.links[i, chip * w_local:(chip + 1) * w_local] = ld

        bits, sense = cam["bits"], cam["sense_bits"]
        p_close = (2.0 ** bits - 2.0 ** (bits - sense) + 1.0) / 2.0 ** bits
        self.e_mismatch = ((1.0 - p_close) + p_close * CAM_E_SENSE_NODE
                           if cam["speculative"] else 1.0)
        self.e_match = CAM_M_CHARGE * (CAM_FEEDBACK_SWING if cam["feedback"]
                                       else 1.0)
        self.e_fixed = CAM_F_CONV + (CAM_E_CSCD_NET if cam["cscd"] else 0.0)
        t_dummy = CAM_D0 + CAM_D1 * math.log2(fab["cam_entries_per_core"])
        if cam["cscd"]:
            settle = CAM_SETTLE[(cam["feedback"], cam["speculative"])]
            self.cam_cycle = CAM_T_REQ + settle * t_dummy + CAM_T_SENSE + \
                CAM_T_RESET
        else:
            self.cam_cycle = CAM_T_REQ + 1.3 * t_dummy + CAM_T_RESET

    def tick_stats(self, spikes, block: int = 2048) -> np.ndarray:
        """(T, len(FIELDS)) float64 stats of each tick of a (T, S) stream.

        Computed ``block`` ticks at a time, so that a long stream fits.
        """
        spikes = np.asarray(spikes, bool).reshape(len(spikes), -1)
        return np.concatenate(
            [self._tick_stats(spikes[i:i + block])
             for i in range(0, len(spikes), block)] or
            [np.zeros((0, len(FIELDS)))])

    def _tick_stats(self, spikes) -> np.ndarray:
        x = spikes.astype(np.float64)
        t, c, n, lv = len(x), self.cores, self.n, self.levels
        per_core = spikes.reshape(t, c, n)
        k = per_core.sum(2).astype(np.float64)
        cl = 4 ** (lv - 1)
        q = per_core.reshape(t, c, n // cl, cl).any(3).sum(2)
        lat = np.where(k > 0, 2.0 * lv + (k - 1) + (q - 1), 0.0)
        toggles = np.zeros((t, c))
        for lvl in range(lv):
            size = 4 ** lvl
            distinct = per_core.reshape(t, c, n // size, size).any(3).sum(2)
            toggles += 2.0 * (distinct + (k < n))
        enc = np.sum(toggles / n * k, axis=1)

        searches = x @ self.dest_counts
        denom = np.maximum(searches, 1.0)
        match = (x @ self.hits) / denom
        mismatch = (x @ self.swept) / denom - match
        cam_energy = searches * (match * self.e_match +
                                 mismatch * self.e_mismatch + self.e_fixed)

        def transport(hops, depth, links, hop_ns, serial_ns, hop_energy):
            h = x @ hops
            load = (x @ links).max(1, initial=0.0)
            deep = (x * depth).max(1, initial=0.0)
            return h, deep * hop_ns + load * serial_ns, h * hop_energy

        noc = transport(self.hops, self.depth, self.links, NOC_HOP_NS,
                        NOC_SERIAL_NS, NOC_HOP_ENERGY)
        chip = transport(self.chip_hops, self.chip_depth, self.chip_links,
                         CHIP_HOP_NS, CHIP_SERIAL_NS, CHIP_HOP_ENERGY)
        return np.stack([k.sum(1), lat.max(1), enc, searches, cam_energy,
                         searches * self.cam_cycle, *noc, *chip], axis=1)

    def synapses(self, bf16_control: bool = False) -> np.ndarray:
        """(S, cores * n) float64: entry ``[s, core * n + j]`` sums the
        weights of the valid entries of ``core`` that subscribe to source
        ``s`` and drive its neuron ``j``."""
        c, n = self.cores, self.n
        weights = bf16(self.weights) if bf16_control else self.weights
        cc, ee = np.nonzero(self.valid)
        out = np.zeros((c * n, c * n))
        np.add.at(out, (self.src[cc, ee], cc * n + self.targets[cc, ee]),
                  weights[cc, ee])
        return out

    def currents(self, spikes, bf16_control: bool = False) -> np.ndarray:
        """(T, cores, n) float64 currents of a (T, S) stream."""
        spikes = np.asarray(spikes, bool).reshape(len(spikes), -1)
        if bf16_control not in self._synapses:
            self._synapses[bf16_control] = self.synapses(bf16_control)
        out = spikes.astype(np.float64) @ self._synapses[bf16_control]
        out = out.reshape(len(spikes), self.cores, self.n)
        return bf16(out) if bf16_control else out


def accumulate(per_tick, bf16_control: bool = False) -> np.ndarray:
    """Sum per-tick stats over the tick axis (the second to last).

    ``per_tick``: (..., T, fields).  The reference sums in float64; the
    control adds tick by tick in bfloat16, as a bfloat16 accumulator
    would.
    """
    per_tick = np.asarray(per_tick, np.float64)
    if not bf16_control:
        return per_tick.sum(-2)
    acc = np.zeros(per_tick.shape[:-2] + per_tick.shape[-1:])
    for i in range(per_tick.shape[-2]):
        acc = bf16(acc + bf16(per_tick[..., i, :]))
    return acc
