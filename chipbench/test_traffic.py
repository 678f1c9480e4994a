"""Open-loop traffic offers the same work on every seed, in another
order, and fits the image pool."""
import json
from pathlib import Path

import numpy as np

from chipbench.traffic import open_loop

BENCH = Path(__file__).parent


def _mix():
    traffic = json.loads((BENCH / "traffic" / "poisson.json").read_text())
    cell = json.loads((BENCH / "cells" / "resnet50.paper_split.poisson.json")
                      .read_text())
    return traffic, cell


def test_every_seed_offers_the_same_work():
    traffic, cell = _mix()
    a = open_loop.schedule(traffic, cell, 1, 20.0)
    b = open_loop.schedule(traffic, cell, 2**31 + 12345, 20.0)
    assert len(a["due"]) == len(b["due"]) == round(cell["rate_per_s"] * 20)
    assert sorted(a["rows"]) == sorted(b["rows"])
    assert sorted(a["tenant"]) == sorted(b["tenant"])
    assert not np.array_equal(a["due"], b["due"])
    for s in (a, b):
        assert np.all(np.diff(s["due"]) >= 0)
        assert 0 <= s["due"][0] and s["due"][-1] < 20.0
        assert np.all(s["first"] + s["rows"] <= traffic["pool_images"])
        assert s["rows"].min() == traffic["rows_min"]
        assert s["rows"].max() == traffic["rows_max"]


def test_same_seed_same_schedule():
    traffic, cell = _mix()
    a = open_loop.schedule(traffic, cell, 99, 5.0)
    b = open_loop.schedule(traffic, cell, 99, 5.0)
    assert all(np.array_equal(a[k], b[k]) for k in a)
