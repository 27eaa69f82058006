"""The generator ``decrypt_burst`` (``chipbench/generators/decrypt_burst.py``):
``decrypt_flushes``' traffic, refused before any process is started where the
program under test needs more than one scan program for a decrypt burst and
the groups bisection makes of it.

PR 30's cell was refused over the run of a parent that could serve the
traffic, but in three programs and 447-1195 s: the driver cut it and the
worker stayed on the chip.  These tests hold the refusal: what it decides on
(the program's shape rule, asked without jax), where it is made (before
``Worker.start`` and before the helpers' pool) and what the command then
gives (exit code 2, nothing on stdout).
"""

import concurrent.futures
import importlib
import io
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.generators import decrypt_flushes  # noqa: E402
from chipbench.harness import bench  # noqa: E402
from chipbench.harness.worker import Worker  # noqa: E402
from hbbft_tpu.crypto import flush_shapes  # noqa: E402

BURST = "chipbench.generators.decrypt_burst"
SHAPES = "hbbft_tpu.crypto.flush_shapes"


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture
def fresh_import():
    """The check runs on import: drop the module first and after."""
    sys.modules.pop(BURST, None)
    yield lambda: importlib.import_module(BURST)
    sys.modules.pop(BURST, None)


def parents_scan_shape(reqs, g1_rows, g2_rows, legs):
    """``TpuBackend._scan_prep``'s buckets up to 50005b9, written out: the
    floor counts rows, whatever the requests' kinds."""

    def bucket(n, floor=16):
        b = floor
        while b < n:
            b *= 2
        return b

    return bucket(max(g1_rows, 1)), bucket(max(g2_rows, 1)), bucket(max(legs, 1), floor=2)


def test_the_check_refuses_a_row_counting_bucket_and_passes_the_programs(fresh_import):
    burst = fresh_import()  # the program's own rule passes, or this raises
    programs = dict(burst.scan_programs(flush_shapes.scan_shape))
    # the burst, the burst with the check, 1-14 shares, the check with 0-14
    assert len(programs) == 2 + 14 + 15
    assert set(programs.values()) == {(32, 16, 2)}
    # which is the configuration's one scan program, and its pair program
    n1, n2, legs = programs["15 dec_share"]
    assert _load("chipbench", "configs", "hb16.json")["programs"] == [
        f"scan({n1},{n2},{legs})", f"pair({flush_shapes.pairs_bucket(1 + legs)})"
    ]
    by_rows = dict(burst.scan_programs(parents_scan_shape))
    assert by_rows["15 dec_share"] == by_rows["9 dec_share"] == (32, 16, 2)
    assert by_rows["8 dec_share"] == by_rows["0 dec_share with the ciphertext check"] == (16, 16, 2)
    with pytest.raises(ValueError, match="two scan programs") as refused:
        burst.hold_to_one_scan_program(parents_scan_shape)
    reason = str(refused.value)
    assert "\n" not in reason
    assert "scan(16, 16, 2)" in reason and "scan(32, 16, 2)" in reason
    # of the rule's answer, not of its name: any rule that keeps them in one passes
    burst.hold_to_one_scan_program(lambda reqs, g1, g2, legs: (64, 64, 4))


@pytest.mark.parametrize("trace", [False, True])
def test_a_program_without_the_shape_rule_is_refused_before_any_process(
    fresh_import, monkeypatch, trace
):
    """``run_cell`` on ``hb16.decrypt`` as every commit up to 50005b9 sees it:
    2, one reason on ``err``, nothing on ``out``, no worker, no helper."""

    def started(*args, **kwargs):
        pytest.fail("a process was started for a workload that cannot be loaded")

    monkeypatch.setitem(sys.modules, SHAPES, None)  # import of it now fails
    monkeypatch.setattr(Worker, "start", started)
    monkeypatch.setattr(concurrent.futures, "ProcessPoolExecutor", started)
    out, err = io.StringIO(), io.StringIO()
    rc = bench.run_cell(
        _load("BENCHMARK.json"), "hb16.decrypt", 3100003101, 10.0, trace,
        t0=0.0, out=out, err=err,
    )
    assert rc == bench.EXIT_USAGE == 2
    assert out.getvalue() == ""
    lines = err.getvalue().splitlines()
    assert len(lines) == 1
    assert lines[0].startswith("chipbench: cannot load workload 'hb16.decrypt': ")
    assert "flush_shapes" in lines[0] and "scan(16,16,2)" in lines[0]
    assert BURST not in sys.modules  # a refused import leaves nothing behind


def test_the_cell_loads_on_this_program_and_its_flushes_are_decrypt_flushes(fresh_import):
    burst = fresh_import()
    assert burst.make_keys is decrypt_flushes.make_keys
    bench_json = _load("BENCHMARK.json")
    cell = bench.Cell(bench_json, "hb16.decrypt", ROOT)
    assert cell.generator is burst
    assert cell.traffic["params"] == {
        "requests": 15, "ciphertext_checks": 0, "wrong": 0, "payload_bytes": 4000,
    }
    seed = 2**31 + 31
    flushes = []
    for generator in (burst, decrypt_flushes):
        keys = generator.make_keys(cell.config, cell.traffic["params"], seed)
        flushes.append(generator.make_flush(cell.config, cell.traffic["params"], seed, 1, keys))
    ours, theirs = flushes
    assert ours.wire == theirs.wire and ours.kinds == theirs.kinds == ["dec_share"] * 15
    assert ours.expected == theirs.expected == [True] * 15
    assert {len(w[2]) for w in ours.wire} == {4000}  # V, the whole proposal
