"""The span reduction on a small hand-checked trace (its layout is drawn at
the top of data/small_spans.pbtxt), and the readers that use it."""

import importlib
import os

import pytest

from chipbench.harness import reduce_spans as rs
from chipbench.harness import work

from .conftest import DATA

MS = 1e6  # ns

NEW_READERS = [
    "scan_ms", "pair_ms", "scan_roofline", "pair_roofline", "host_prep_ms",
    "hash_to_g2_ms", "checks_per_flush", "rpc_server_decode_ms",
]

# what the one whole flush of the fixture holds: name -> (ms, count)
WHOLE_FLUSH = {
    "crypto.flush": (10.0, 1), "crypto.window": (1.0, 1),
    "crypto.tpu.well_formed": (0.5, 1), "crypto.tpu.check": (9.5, 1),
    "crypto.tpu.scan_prep": (3.0, 1), "crypto.tpu.coefficients": (0.5, 1),
    "crypto.tpu.hash_to_g2": (1.5, 1), "crypto.tpu.pack": (1.0, 1),
    "crypto.tpu.scan_dispatch": (0.5, 1), "crypto.tpu.pair_dispatch": (0.5, 1),
    "crypto.tpu.verdict_sync": (5.5, 1), "crypto.rpc.serve": (12.4, 1),
    "crypto.rpc.decode": (0.3, 1), "crypto.rpc.wait": (11.25, 1),
    "crypto.rpc.reply": (0.6, 1),
}

# the idle 15.2 ms of the 28.5 ms from the anchor to the last span's end
IDLE_MS = {
    "outside_worker": 0.7, "crypto.rpc.decode": 0.7, "unattributed": 0.25,
    "crypto.rpc.wait": 0.25, "crypto.window": 2.0,
    "crypto.tpu.well_formed": 1.0, "crypto.tpu.coefficients": 0.5,
    "crypto.tpu.hash_to_g2": 2.5, "crypto.tpu.pack": 1.0,
    "crypto.tpu.scan_dispatch": 1.4, "crypto.tpu.verdict_sync": 1.3,
    "crypto.rpc.reply": 0.6, "crypto.tpu.scan_prep": 3.0,
}


def _profile(text):
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text)
    )


@pytest.fixture(scope="module")
def text():
    with open(os.path.join(DATA, "small_spans.pbtxt")) as f:
        return "".join(line for line in f if not line.startswith("#"))


@pytest.fixture(scope="module")
def profile(text):
    return _profile(text)


@pytest.fixture(scope="module")
def cut_profile(text):
    """The same window without its whole flush's span: what a window that
    was closed inside the flush holds."""
    lines = text.splitlines(keepends=True)
    flush = [i for i, line in enumerate(lines) if 'str_value: "1:1"' in line
             and "metadata_id: 3 " in line]  # the flush's own event
    assert len(flush) == 1
    del lines[flush[0]]
    return _profile("".join(lines))


def test_spans_and_the_flush_they_belong_to(profile):
    spans = rs.read_spans(profile)
    assert len(spans) == 29
    assert {s.line for s in spans} == {(1, 1), (1, 2)}
    (flush, members), = rs.flush_members(spans)
    assert flush.args == {"flush": 1, "requests": 16, "jobs": 1, "spans": "1:1"}
    # the second flush's spans and the RPC "1:2" belong to no whole flush
    assert len(members) == 15
    got = rs.spans_per_flush([(flush, members)])
    assert set(got) == set(WHOLE_FLUSH)
    for name, (ms, count) in WHOLE_FLUSH.items():
        assert got[name]["ms"] == pytest.approx(ms, rel=1e-9), name
        assert got[name]["count"] == count, name


def test_programs_by_name_prefix(profile):
    modules, ops = rs.read_device(profile)
    assert [m.kind for m in modules] == [
        "other", "scan", "pair", "scan", "pair", "scan", "pair"
    ]
    spans = rs.read_spans(profile)
    per_flush = rs.modules_per_flush(modules, rs.flush_members(spans))
    assert per_flush["scan"] == {"seconds": pytest.approx(3e-3), "launches": 1}
    assert per_flush["pair"] == {"seconds": pytest.approx(2e-3), "launches": 1}
    assert per_flush["other"] == {"seconds": pytest.approx(1e-4), "launches": 1}
    # three whole checks: scan 3, 3, 2.5 ms; pair 2, 0.9, 1.8 ms
    per_check, whole = rs.modules_per_check(modules, spans)
    assert whole == 3
    assert per_check["scan"]["seconds"] == pytest.approx(8.5e-3 / 3)
    assert per_check["pair"]["seconds"] == pytest.approx(4.7e-3 / 3)
    assert per_check["scan"]["launches"] == per_check["pair"]["launches"] == 1
    assert sum(e - s for s, e in rs.union(ops)) == pytest.approx(13.3e6)


def test_a_whole_flush_in_the_device_window(profile):
    got = rs.reduce_profiles(profile, None)
    assert got["spans_from"] == "device_window" and got["whole_flushes"] == 1
    assert got["modules_from"] == "whole_flushes"
    assert got["modules_per_flush"]["scan"]["seconds"] == pytest.approx(3e-3)
    assert got["idle_window_s"] == pytest.approx(28.5e-3)
    assert got["idle_s"] == pytest.approx(15.2e-3)
    assert set(got["idle_by_span_s"]) == set(IDLE_MS)
    for name, ms in IDLE_MS.items():
        assert got["idle_by_span_s"][name] == pytest.approx(ms / 1e3, rel=1e-9), name


def test_a_window_cut_inside_the_flush_takes_its_flush_from_the_host_window(
    profile, cut_profile
):
    # the device's window holds three whole checks and no whole flush; the
    # host-only window's flush has one check
    got = rs.reduce_profiles(cut_profile, profile)
    assert got["spans_from"] == "host_only_window"
    assert got["spans_per_flush"]["crypto.tpu.check"]["count"] == 1
    assert got["modules_from"] == "3_whole_checks_x_1_checks_per_flush"
    assert got["modules_per_flush"]["scan"]["seconds"] == pytest.approx(8.5e-3 / 3)
    assert got["modules_per_flush"]["pair"]["launches"] == pytest.approx(1.0)
    # 21 checks a flush would make it 21 times the mean check
    per_check, _ = rs.modules_per_check(*_modules_and_spans(cut_profile))
    assert rs._mean([per_check], times=21)["pair"]["seconds"] == pytest.approx(
        21 * 4.7e-3 / 3
    )
    # no span names what lies outside the checks: not the client's time
    idle = got["idle_by_span_s"]
    assert "outside_worker" not in idle
    assert idle["unnamed_in_cut_window"] == pytest.approx(0.7e-3)
    assert sum(idle.values()) == pytest.approx(15.2e-3)
    # neither window: nothing per flush, and no reader's zero
    assert rs.reduce_profiles(cut_profile, None)["spans_per_flush"] is None
    assert rs.reduce_profiles(cut_profile, None)["modules_per_flush"] is None


def _modules_and_spans(profile):
    return rs.read_device(profile)[0], rs.read_spans(profile)


def test_a_program_without_spans_reads_nothing():
    small = os.path.join(DATA, "small_trace.pbtxt")
    with open(small) as f:
        old = _profile("".join(line for line in f if not line.startswith("#")))
    got = rs.reduce_profiles(old, old)
    assert got["spans_per_flush"] is None and got["modules_per_flush"] is None
    # the gaps are still the gaps; nothing names them
    assert set(got["idle_by_span_s"]) == {"unnamed_in_cut_window"}


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_returns_none_without_a_trace(name):
    reader = importlib.import_module("chipbench.layer_metrics." + name)
    obs = {
        "trace": None, "host": None, "trace_cut": False, "notes": {},
        "traffic": {"params": {"requests": 16, "wrong": 0}},
        "config": {}, "device_kind": None,
        "work": work.Composition({"sig_share": 16}, 2, 16 * 290 + 40),
    }
    assert reader.read(obs) is None
    assert obs["notes"] == {}


def test_readers_on_the_cached_reduction(profile, monkeypatch, tmp_path):
    """The readers' arithmetic, on the fixture's reduction put where a run's
    would be."""
    import json

    trace = tmp_path / "device" / "plugins" / "profile" / "run"
    trace.mkdir(parents=True)
    (trace / "worker.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(rs, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(rs, "CACHE", str(tmp_path / "spans.json"))
    reduction = rs.reduce_profiles(profile, None)
    reduction["reduce_spans_s"] = 0.5
    reduction["key"] = rs._key([str(trace / "worker.xplane.pb"), None])
    (tmp_path / "spans.json").write_text(json.dumps(reduction))
    obs = {
        "trace": {"busy_s": 1.0}, "host": None, "trace_cut": False, "notes": {},
        "traffic": {"params": {"requests": 16, "wrong": 0}},
        "config": {}, "device_kind": "TPU v5 lite",
        "work": work.Composition({"sig_share": 16}, 2, 16 * 290 + 40),
    }

    def read(name):
        return importlib.import_module("chipbench.layer_metrics." + name).read(obs)

    assert read("scan_ms") == pytest.approx(3.0)
    assert read("pair_ms") == pytest.approx(2.0)
    assert read("host_prep_ms") == pytest.approx(3.5)
    assert read("hash_to_g2_ms") == pytest.approx(1.5)
    assert read("checks_per_flush") == 1
    assert read("rpc_server_decode_ms") == pytest.approx(0.3)
    peak = 393e12
    scan_least = 16 * 6640 * work.INT8_OPS_PER_FQ_MUL / peak
    assert read("scan_roofline") == pytest.approx(scan_least / 3e-3 * 100)
    pair_least = (125830 - 16 * 6640) * work.INT8_OPS_PER_FQ_MUL / peak
    assert read("pair_roofline") == pytest.approx(pair_least / 2e-3 * 100)
    # a decrypt phase's flush reads its own composition, not the coin's
    obs["work"] = work.Composition({"ciphertext": 1, "dec_share": 15}, 3, 8190)
    scan_least = (7658 + 15 * 4204) * work.INT8_OPS_PER_FQ_MUL / peak
    assert read("scan_roofline") == pytest.approx(scan_least / 3e-3 * 100)
    pair_least = (94740 - 7658 - 15 * 4204) * work.INT8_OPS_PER_FQ_MUL / peak
    assert read("pair_roofline") == pytest.approx(pair_least / 2e-3 * 100)
    assert obs["notes"]["spans_from"] == "device_window"
    assert obs["notes"]["reduce_spans_s"] == 0.5
    assert obs["notes"]["spans_per_flush"]["crypto.flush"]["count"] == 1
    assert sum(obs["notes"]["idle_by_span_s"].values()) == pytest.approx(15.2e-3)
    # a faulty round's share would read the bisection, not the kernels
    obs["traffic"]["params"]["wrong"] = 5
    assert read("scan_roofline") is None and read("pair_roofline") is None
