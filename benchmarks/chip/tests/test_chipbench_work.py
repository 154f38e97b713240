"""Work counts, peaks and the roofline share."""

import pytest

from chip import work

TINY = {"fabric": {"chips": 2, "cores_per_chip": 2, "cores": 4,
                   "neurons_per_core": 64, "cam_entries_per_core": 64},
        "assumed": {"fan_in": 0.5}}


def test_tiny_configuration_matches_a_hand_count():
    # 2 chips x 2 cores: each chip's 2 x 1 mesh has 1 link, the 2 x 1 chip
    # grid 1 link.  Table row per event: 5 + 2 chips x 1 link + 2 + 1 = 10
    # words.  Driven entries per event: 4 x 64 x 0.5 / 256 = 0.5.
    # 2 calls x 5 ticks = 10 scan steps of 3 lanes of 256 neurons.
    w = work.tick_work(TINY, lanes=3, ticks=5, events=100, calls=2)
    frames = 10 * 3 * 256 * (1 + 4)
    routing = 10 * 4 * 64 * 13
    tables = 100 * 10 * 4
    assert w["bytes"] == frames + routing + tables
    assert w["flops"] == 100 * 2 * (10 + 0.5)


def test_flat_fabric_has_no_chip_tier_in_its_count():
    flat = {"fabric": dict(TINY["fabric"], chips=1, cores_per_chip=4),
            "assumed": TINY["assumed"]}
    # one 2 x 2 mesh of 4 links: row 5 + 4 = 9 words
    w = work.tick_work(flat, lanes=1, ticks=1, events=1)
    assert w["bytes"] == 256 * 5 + 4 * 64 * 13 + 9 * 4


def test_roofline_takes_the_larger_bound_from_the_table():
    w = {"bytes": 819e9, "flops": 1.0}
    r = work.roofline(w, "TPU v5 lite")
    assert r == {"seconds": pytest.approx(1.0), "bound": "hbm_bytes"}
    r = work.roofline({"bytes": 1.0, "flops": 2 * 197e12}, "TPU v5 lite",
                      chips=2)
    assert r == {"seconds": pytest.approx(1.0), "bound": "flops"}


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.roofline({"bytes": 1.0, "flops": 1.0}, "cpu")
