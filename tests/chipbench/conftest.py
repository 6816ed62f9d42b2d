"""The benchmark's tests import ``chipbench`` from the repository root."""

import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

SMALL_DIM = {"medline_bow": 3000, "ctr_criteo_hashed": 4096}


@pytest.fixture
def small_config():
    """``small(name, round_len=32, dim=None)``: a configuration cut to a size
    a CPU test holds, on the reference backend."""
    from chipbench import spec

    def small(name, round_len=32, dim=None):
        c = copy.deepcopy(spec.config(name))
        c["data"]["dim"] = dim or SMALL_DIM[name]
        if "informative_pool" in c["data"]:
            c["data"]["informative_pool"] = 1000
        if "cardinalities" in c["data"]:
            c["data"]["cardinalities"] = [min(x, 2000) for x in c["data"]["cardinalities"]]
        c["train"]["round_len"] = round_len
        c["backend"] = "reference"
        return c

    return small
