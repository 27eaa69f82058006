"""Tests of the benchmark's own code: CPU only, seconds.

    python -m pytest chipbench/tests -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(scope="session")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def tiny_bench(bench):
    """The real metrics and the real ``coin16`` configuration over traffic
    small enough for the pure-Python ``eager`` worker, and a decrypt phase
    (``hb4``) that is a configuration file, a traffic file and these
    entries: what a PR that adds ``hb16.decrypt`` brings, and no more; and
    ``tiny.wide``, whose flushes of 20 are more than the reference judges."""
    tiny = dict(bench)
    tiny["paths"] = ["."]
    tiny["configs"] = [
        {"name": "coin16", "file": "../../configs/coin16.json"},
        {"name": "hb4", "file": "configs/hb4.json"},
    ]
    tiny["workloads"] = [
        {"name": "tiny.clean", "config": "coin16", "traffic": "tiny_clean", "chips": 1},
        {"name": "tiny.byz", "config": "coin16", "traffic": "tiny_byz", "chips": 1},
        {"name": "hb4.decrypt", "config": "hb4", "traffic": "tiny_decrypt", "chips": 1},
        {"name": "tiny.wide", "config": "coin16", "traffic": "tiny_wide", "chips": 1},
    ]
    for group in ("end_to_end", "per_layer"):
        tiny[group] = [
            {k: v for k, v in m.items() if k != "workloads"} for m in bench[group]
        ]
    return tiny
