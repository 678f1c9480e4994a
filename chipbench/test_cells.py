"""Every cell in BENCHMARK.json is one pair of configuration and traffic,
and its files are where the runner looks for them."""
import json
import re
from pathlib import Path

import pytest

BENCH = Path(__file__).parent
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_each_pair_of_config_and_traffic_is_one_cell():
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    names = [w["name"] for w in SPEC["workloads"]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("wl", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_files_exist_and_names_fit(wl):
    configs = {c["name"]: c for c in SPEC["configs"]}
    for key in ("name", "config", "traffic"):
        assert NAME.match(wl[key]), wl[key]
    assert 1 <= len(wl["why"]) <= 200 and "\n" not in wl["why"]
    assert (ROOT / configs[wl["config"]]["file"]).is_file()
    assert (BENCH / "cells" / f"{wl['name']}.json").is_file()
    assert (BENCH / "traffic" / f"{wl['traffic']}.json").is_file()


def test_unsplit_cell_offers_the_paper_split_mix():
    """The unsplit cell is cell 1's deployment without its cuts: its
    traffic file differs from cell 1's only by the note that says so."""
    def mix(name):
        return json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    unsplit = mix("poisson_unsplit")
    assert unsplit.pop("mix_of").startswith("poisson:")
    assert unsplit == mix("poisson")
