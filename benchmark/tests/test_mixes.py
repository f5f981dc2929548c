"""Job mixes that share their parameters.  BENCHMARK.json may pair a
configuration with a mix once, so a mix that one configuration runs on
one chip and on four exists as two files; ``same_as`` names the other,
and only the words for the reader may differ."""
import glob
import json
import os

import pytest

from conftest import ROOT

WORDS = {"why", "same_as", "same_as_why"}
TRAFFIC = os.path.join(ROOT, "benchmark", "traffic")


def _load(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


def _parameters(mix):
    out = {k: v for k, v in mix.items() if k not in WORDS}
    out["data"] = {k: v for k, v in mix["data"].items() if k != "stands_for"}
    return out


COPIES = sorted(os.path.basename(p)[:-len(".json")]
                for p in glob.glob(os.path.join(TRAFFIC, "*.json"))
                if "same_as" in json.load(open(p)))


def test_there_is_a_copy_to_check():
    assert "resident_b3072_dp" in COPIES


@pytest.mark.parametrize("name", COPIES)
def test_a_copy_keeps_every_parameter_of_its_original(name):
    mix = _load(name)
    assert _parameters(mix) == _parameters(_load(mix["same_as"]))
