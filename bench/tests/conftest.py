"""Shared set-up of the benchmark's own tests (run on the CPU):

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They are not part of the repository's tier-1 run (``pytest.ini`` collects
``tests/`` only). ``tiny_cell`` builds a cell on a 256-machine cluster
(``tiny.json``) under one of the real traffic mixes, with the arrival
rate raised so that a few seconds hold some tens of rounds.
"""

import copy
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def _load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="session")
def spec():
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.fixture
def tiny_cell(spec):
    import harness

    def make(mix: str, **traffic):
        local = copy.deepcopy(spec)
        tr = _load(os.path.join(BENCH, "traffic", mix + ".json"))
        tr.update(traffic)
        cfg = _load(os.path.join(BENCH, "tests", "tiny.json"))
        name = f"tiny.{mix}"
        for m in local["end_to_end"] + local["per_layer"]:
            if "workloads" in m and any(w.endswith("." + mix) for w in m["workloads"]):
                m.setdefault("workloads", []).append(name)
        return harness.Cell(name, "tiny", mix, 1, cfg, tr, local)

    return make
