"""The generator ``decrypt_burst103`` (``chipbench/generators/decrypt_burst103.py``)
and the cell ``wan104.decrypt`` that names it: ``decrypt_flushes``' traffic at
N = 104, refused before any process is started where the program under test
needs more than one scan program for a burst of 103 decryption shares and the
groups bisection makes of it.

``tests/test_decrypt_burst.py`` holds the same for ``decrypt_burst`` and a
burst of 15.  What is new here: the burst is over the floor of 16 requests,
so the program keeps it in one program only by handing the flush's shape down
to its groups (``flush_shapes.group_shape``, PR 35); the rule up to PR 34
(``scan_shape`` on every group's own rows) needs four scan programs, 600 s
and more of a run that may take 360, and is refused.
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

BURST = "chipbench.generators.decrypt_burst103"
CELL = "wan104.decrypt"
NEW_METRICS = ["scan256_ms", "scan256_roofline", "burst_decode_ms", "burst_pack_ms"]
# added after the cell, for every cell: device time by the programs' named scopes
SCOPE_METRICS = ["miller_loop_ms", "final_exp_ms", "scan_g1_ms", "device_unscoped_ms"]


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture
def fresh_import():
    """The check runs on import: drop the module first and after."""
    sys.modules.pop(BURST, None)
    yield lambda: importlib.import_module(BURST)
    sys.modules.pop(BURST, None)


def test_the_check_refuses_pr34s_rule_and_passes_the_programs(fresh_import):
    burst = fresh_import()  # the program's own rule passes, or this raises
    programs = burst.scan_programs(flush_shapes.scan_shape, flush_shapes.group_shape)
    assert programs[0] == ("103 dec_share", (256, 16, 2))
    assert programs[1] == ("103 dec_share with the ciphertext check", (256, 16, 2))
    whats = [what for what, _ in programs]
    assert len(whats) == len(set(whats))  # one program a group
    # down to a lone share and the lone check
    assert {"1 dec_share", "0 dec_share with the ciphertext check"} <= set(whats)
    assert {shape for _, shape in programs} == {(256, 16, 2)}
    # which is the configuration's one scan program, and its pair program
    n1, n2, legs = programs[0][1]
    assert _load("chipbench", "configs", "wan104.json")["programs"] == [
        f"scan({n1},{n2},{legs})", f"pair({flush_shapes.pairs_bucket(1 + legs)})"
    ]
    # PR 34's rule: every group in the bucket of its own rows
    own = dict(burst.scan_programs(flush_shapes.scan_shape))
    assert own["103 dec_share"] == (256, 16, 2)
    assert own["52 dec_share"] == own["51 dec_share with the ciphertext check"] == (128, 16, 2)
    assert own["26 dec_share"] == (64, 16, 2)
    assert own["13 dec_share"] == own["1 dec_share"] == (32, 16, 2)
    assert set(own.values()) == {(256, 16, 2), (128, 16, 2), (64, 16, 2), (32, 16, 2)}
    with pytest.raises(ValueError, match="3 more scan programs") as refused:
        burst.hold_to_one_scan_program(flush_shapes.scan_shape)
    reason = str(refused.value)
    assert "\n" not in reason
    assert "scan(256, 16, 2)" in reason and "scan(32, 16, 2)" in reason
    # of the rule's answer, not of its name: any rule that keeps them in one passes
    burst.hold_to_one_scan_program(lambda reqs, g1, g2, legs: (512, 64, 4))
    burst.hold_to_one_scan_program(flush_shapes.scan_shape, lambda chunk, own: chunk)
    # and one that hands down a shape of its own making does not
    with pytest.raises(ValueError, match="more scan programs"):
        burst.hold_to_one_scan_program(
            flush_shapes.scan_shape, lambda chunk, own: (max(own[0], 64),) + chunk[1:]
        )


@pytest.mark.parametrize("trace", [False, True])
def test_a_program_that_hands_no_shape_down_is_refused_before_any_process(
    fresh_import, monkeypatch, trace
):
    """``run_cell`` on ``wan104.decrypt`` as the parent ffe9a29 sees it
    (``flush_shapes`` with ``scan_shape`` and no ``group_shape``): 2, one reason
    on ``err``, nothing on ``out``, no worker, no helper."""

    def started(*args, **kwargs):
        pytest.fail("a process was started for a workload that cannot be loaded")

    monkeypatch.delattr(flush_shapes, "group_shape")
    monkeypatch.setattr(Worker, "start", started)
    monkeypatch.setattr(concurrent.futures, "ProcessPoolExecutor", started)
    out, err = io.StringIO(), io.StringIO()
    rc = bench.run_cell(
        _load("BENCHMARK.json"), CELL, 3500003501, 10.0, trace, t0=0.0, out=out, err=err,
    )
    assert rc == bench.EXIT_USAGE == 2
    assert out.getvalue() == ""
    lines = err.getvalue().splitlines()
    assert len(lines) == 1
    assert lines[0].startswith(f"chipbench: cannot load workload '{CELL}': ")
    assert "group_shape" in lines[0] and "scan(128,16,2)" in lines[0]
    assert BURST not in sys.modules  # a refused import leaves nothing behind


def test_the_cell_loads_on_this_program_and_its_flushes_are_decrypt_flushes(fresh_import):
    burst = fresh_import()
    assert burst.make_keys is decrypt_flushes.make_keys
    assert burst.make_flush is decrypt_flushes.make_flush
    cell = bench.Cell(_load("BENCHMARK.json"), CELL, ROOT)
    assert cell.generator is burst and cell.requests_per_flush == 103
    assert cell.cell == {
        "name": CELL, "config": "wan104", "traffic": "decrypt103", "chips": 1,
        "why": cell.cell["why"],
    }
    assert cell.traffic["params"] == {
        "requests": 103, "ciphertext_checks": 0, "wrong": 0, "payload_bytes": 4000,
    }
    assert cell.traffic["pool_flushes"] % 8 == 0
    config = cell.config
    assert (config["validators"], config["threshold"]) == (104, 34)
    hb16 = _load("chipbench", "configs", "hb16.json")
    assert list(config) == list(hb16)  # key for key
    assert config["guarantees"] == hb16["guarantees"]  # letter for letter
    assert config["guarantees"] == _load("chipbench", "configs", "coin16.json")["guarantees"]
    assert config["worker"] == hb16["worker"]
    assert config["reduced"] == hb16["reduced"] == sorted(config["reduced_why"], reverse=True)
    assert len(config["source"]) <= 200
    seed = 2**31 + 35
    keys = burst.make_keys(config, cell.traffic["params"], seed)
    assert len(keys.secrets) == 104  # a degree-34 key set's shares, the probe's too
    ours = burst.make_flush(config, cell.traffic["params"], seed, 1, keys)
    theirs = decrypt_flushes.make_flush(config, cell.traffic["params"], seed, 1, keys)
    assert ours.wire == theirs.wire and ours.kinds == theirs.kinds == ["dec_share"] * 103
    assert ours.expected == theirs.expected == [True] * 103
    assert {len(w[2]) for w in ours.wire} == {4000}  # V, the whole proposal
    assert len({w[0] for w in ours.wire}) == len({w[4] for w in ours.wire}) == 103
    # the probe: the check, then 103 shares of which a next_key one and the
    # point at infinity lie on one path of the bisection: 7 failing groups
    probe = burst.make_flush(config, cell.traffic["probe"], seed, bench.PROBE, keys)
    assert probe.kinds == ["ciphertext"] + ["dec_share"] * 103
    wrong = [i for i, ok in enumerate(probe.expected) if not ok]
    assert len(wrong) == 2 and wrong[0] > 0
    assert decrypt_flushes.hit_nodes(104, wrong) == 7


def test_the_benchmark_gained_one_configuration_one_cell_and_four_metrics():
    bench_json = _load("BENCHMARK.json")
    assert [c["name"] for c in bench_json["configs"]] == ["coin16", "hb16", "wan104"]
    assert [w["name"] for w in bench_json["workloads"]] == [
        "coin16.clean", "coin16.byz5", "hb16.decrypt", CELL,
    ]
    entry = bench_json["configs"][-1]
    assert entry["source"] == _load(entry["file"])["source"]
    assert entry["reduced"] == _load(entry["file"])["reduced"]
    cell = bench.Cell(bench_json, CELL, ROOT)
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "verifies_per_s", "flush_ms.p50", "setup_s",
    ]
    assert [m["name"] for m in cell.metrics("per_layer")] == NEW_METRICS + SCOPE_METRICS
    names = [m["name"] for m in bench_json["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert names[first:first + 4] == NEW_METRICS
    assert set(names[first + 4:]) >= set(SCOPE_METRICS)
    # and no cell accepted before it reports them
    for m in bench_json["per_layer"]:
        if m["name"] not in SCOPE_METRICS:
            assert (CELL in m["workloads"]) == (m["name"] in NEW_METRICS)


def _window(**over):
    """What ``bench.layer_metrics`` hands a reader, of a traced window in
    which nothing of this PR is: no device trace, no jax device."""
    obs = {
        "cell": {}, "config": {}, "traffic": {"params": {"wrong": 0}},
        "device_kind": None, "flushes": 1, "client_s": 0.1, "worker_flush_s": 0.1,
        "worker_flushes": 1, "trace": None, "trace_cut": False, "host": None,
        "work": None, "notes": {},
    }
    obs.update(over)
    return obs


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_reader_finds_nothing_in_a_window_without_its_module_or_span(name, monkeypatch):
    reader = importlib.import_module("chipbench.layer_metrics." + name)
    assert reader.read(_window()) is None
    # a device trace of another cell's programs: no ``jit_hbbft_scan_256_*``
    from chipbench.harness import reduce_spans

    reduced = {
        "modules_per_flush": {"scan": {"seconds": 0.0727, "launches": 1}},
        "spans_per_flush": {"crypto.flush": {"ms": 150.0, "count": 1}},
    }
    monkeypatch.setattr(reduce_spans, "for_run", lambda obs: reduced)
    other = _window(
        device_kind="TPU v5 lite",
        trace={"modules": ["jit_hbbft_scan_32_16_2", "jit_hbbft_pair_3", "jit_hbbft_join_3"]},
    )
    assert reader.read(other) is None
    # and, for the two that read a device module, its own
    if name.startswith("scan256"):
        from chipbench.harness import work

        own = dict(other, trace={"modules": ["jit_hbbft_scan_256_16_2", "jit_hbbft_pair_3"]})
        own["work"] = work.compose(["dec_share"] * 2, [(b"k", b"u", b"v", b"w", b"s")] * 2)
        assert reader.read(own) > 0
    else:
        span = {"burst_decode_ms": "crypto.rpc.decode", "burst_pack_ms": "crypto.tpu.pack"}[name]
        reduced["spans_per_flush"][span] = {"ms": 12.5, "count": 1}
        assert reader.read(other) == 12.5
