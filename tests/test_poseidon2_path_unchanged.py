"""The Blake2s tree hasher was added BESIDE the Poseidon2 path (ISSUE 42):
a Poseidon2-tree prove creates no device array, keeps no reference and
dispatches no program that it did not before.

`tests/data/poseidon2_path_parent.json` is `tests/poseidon2_path_probe.py`'s
line at the commit before the hasher existed (PR 40's tree): the jitted
programs one warm 2^10 prove calls, in order, the recorder's upload,
`merkle.*` and `ntt.*` counters, and the live device arrays after `import
boojum_tpu` and after the proves are dropped. The probe runs again here, in
a process of its own, and every part has to read the same. PR 41 built the
same hasher without this rule and moved 23 MiB of round 5's memory in the
2^19-row benchmark cell, which lost it.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def sides():
    # a fixture, so outside the time limit of a test's call: in a checkout
    # whose compile cache is cold the probe compiles the 2^10 library
    with open(os.path.join(HERE, "data", "poseidon2_path_parent.json")) as f:
        parent = json.load(f)
    env = {k: v for k, v in os.environ.items() if k != "BOOJUM_TPU_REPORT"}
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "poseidon2_path_probe.py")],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return parent, json.loads(out.stdout.strip().splitlines()[-1])


def test_the_same_programs_in_the_same_order(sides):
    parent, now = sides
    assert len(parent["programs"]) > 300  # the probe sees the prove
    assert now["programs"] == parent["programs"]
    assert not any("blake2s" in name for name in now["programs"])


@pytest.mark.parametrize("family", ["transfer.h2d_", "merkle.", "ntt."])
def test_the_same_counters(sides, family):
    parent, now = sides
    pick = lambda d: {  # noqa: E731
        k: v for k, v in d["counters"].items() if k.startswith(family)
    }
    assert pick(parent) and pick(now) == pick(parent)


@pytest.mark.parametrize("when", ["live_after_import", "live_after_prove"])
def test_the_same_live_arrays(sides, when):
    parent, now = sides
    assert now[when] == parent[when]
    if when == "live_after_import":
        assert now[when] == 0
