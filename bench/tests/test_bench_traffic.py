"""The generator is deterministic per seed, and every seed sends one fixed
schedule of sizes and gaps."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spec  # noqa: E402
from bench.traffic import Source, quantile  # noqa: E402


@pytest.mark.parametrize("name", ["long-in", "long-in-1k"])
def test_same_seed_same_requests(name):
    t = spec.traffic_file(name)
    a = Source(t, 2 ** 31 + 5, 49155, rate_rps=1.5)
    b = Source(t, 2 ** 31 + 5, 49155, rate_rps=1.5)
    for i in range(0, 130, 7):
        x, y = a.item(i), b.item(i)
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.gap_s) == (y.max_new, y.gap_s)


@pytest.mark.parametrize("name", ["long-in", "long-in-1k"])
def test_block_holds_the_quantiles(name):
    """A block holds each distribution's quantiles at its midpoints, the
    gaps those of an exponential at the rate."""
    t = spec.traffic_file(name)
    n = int(t["block"])
    src = Source(t, 2 ** 31 + 11, 49155, rate_rps=1.5)
    items = [src.item(i) for i in range(n, 2 * n)]
    mids = [(j + 0.5) / n for j in range(n)]
    assert sorted(len(x.prompt) for x in items) == \
        sorted(quantile(t["prompt"], p) for p in mids)
    assert sorted(x.max_new for x in items) == \
        sorted(quantile(t["output"], p) for p in mids)
    assert sorted(x.gap_s for x in items) == pytest.approx(
        sorted(-np.log(1.0 - p) / 1.5 for p in mids))


#: the first requests of each cell's schedule, as measured on the chip
MEASURED = {
    "long-in": (1.5, [(942, 40, 0.570444), (626, 80, 0.262028),
                      (1683, 96, 0.429571), (684, 20, 1.16198),
                      (1826, 76, 0.451079), (1010, 18, 0.331355)]),
    "long-in-1k": (1.7, [(625, 20, 0.290479), (403, 43, 0.17502),
                         (344, 16, 0.119485), (803, 63, 0.088518),
                         (600, 17, 0.031084), (428, 52, 0.319534)]),
}


@pytest.mark.parametrize("name", sorted(MEASURED))
def test_cells_schedule_is_the_measured_one(name):
    """The bounds and limits were measured on this schedule: a change to
    the generator that moves it shows here."""
    rate, want = MEASURED[name]
    src = Source(spec.traffic_file(name), 2 ** 31 + 77, 49155,
                 rate_rps=rate)
    got = [(len(x.prompt), x.max_new, round(x.gap_s, 6))
           for x in map(src.item, range(len(want)))]
    assert got == want


def test_quantiles_and_mean_gap():
    d = {"dist": "lognormal", "median": 768, "sigma": 0.5, "min": 256,
         "max": 2048}
    assert quantile(d, 0.5) == 768
    assert quantile(d, 1e-9) == 256 and quantile(d, 1 - 1e-9) == 2048
    u = {"dist": "uniform", "min": 16, "max": 96}
    assert quantile(u, 0.0) == 16 and quantile(u, 0.999999) == 96
    t = {"loop": "open", "block": 200, "prompt": u, "output": u}
    src = Source(t, 3, 100, rate_rps=2.0)
    gaps = [src.item(i).gap_s for i in range(200)]
    assert np.mean(gaps) == pytest.approx(0.5, rel=0.03)


def test_open_loop_needs_a_rate():
    t = spec.traffic_file("long-in")
    with pytest.raises(ValueError):
        Source(t, 1, 100)


@pytest.mark.parametrize("name", ["long-in", "long-in-1k"])
def test_cells_send_one_schedule_for_every_seed(name):
    """Every seed sends the same sizes and gaps in the same order, with
    its own token ids."""
    t = spec.traffic_file(name)
    a = Source(t, 2 ** 31 + 3, 49155, rate_rps=1.5)
    b = Source(t, 17, 49155, rate_rps=1.5)
    for i in range(int(t["block"]) + 5):
        x, y = a.item(i), b.item(i)
        assert (len(x.prompt), x.max_new, x.gap_s) == \
            (len(y.prompt), y.max_new, y.gap_s)
        assert not np.array_equal(x.prompt, y.prompt)


def test_blocks_hold_one_set_of_sizes_in_orders_of_their_own():
    t = spec.traffic_file("long-in")
    n = int(t["block"])
    src = Source(t, 5, 100, rate_rps=1.5)
    b0 = [len(src.item(i).prompt) for i in range(n)]
    b1 = [len(src.item(i).prompt) for i in range(n, 2 * n)]
    assert sorted(b0) == sorted(b1) and b0 != b1
