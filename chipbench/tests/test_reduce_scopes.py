"""The scope reduction on small hand-built traces: a flush whose scan, join
and pair launches hold nested op events, some of them under no scope, and
the same checks in a window that was closed inside its flush.

One check, in milliseconds on the trace's clock (each launch's ops cover
it, so its scopes plus ``<unscoped>`` are its duration):

  scan [2,8]:  fusion.1 [2,3] scan_g1; cond.4 [3,7] g2_stage holding
               while.5 [3.5,6.5] g2_stage/scan_g2 holding fusion.6 [4,5]
               and copy.7 [6.6,6.9] (no scope); fusion.8 [7,8] (no scope)
  join [8,9]:  fusion.1 [8,9], a module without a map
  pair [9,18]: while.2 [9,13] miller_loop holding fusion.3 [9.5,10];
               while.6 [13,15] and while.7 [15,17] final_exp/pow_x_abs;
               fusion.9 [17,18] final_exp
"""

import importlib

import pytest

from chipbench.harness import reduce_scopes as rsc
from chipbench.harness import reduce_spans as rs
from hbbft_tpu.utils import op_scopes

SCAN_MAP = op_scopes.encode("jit_hbbft_scan_16_16_2", {
    "fusion.1": "scan_g1", "cond.4": "g2_stage", "while.5": "g2_stage/scan_g2",
    "fusion.6": "g2_stage/scan_g2",
})
PAIR_MAP = op_scopes.encode("jit_hbbft_pair_3", {
    "while.2": "miller_loop", "fusion.3": "miller_loop",
    "while.6": "final_exp/pow_x_abs", "while.7": "final_exp/pow_x_abs",
    "fusion.9": "final_exp",
})

# one check's launches and ops, relative to its start: (start, end, name)
MODULES = [
    (2, 8, "jit_hbbft_scan_16_16_2(11)"), (8, 9, "jit_hbbft_join_3(12)"),
    (9, 18, "jit_hbbft_pair_3(13)"),
]
OPS = [
    (2, 3, "fusion.1"), (3, 7, "cond.4"), (3.5, 6.5, "while.5"), (4, 5, "fusion.6"),
    (6.6, 6.9, "copy.7"), (7, 8, "fusion.8"), (8, 9, "fusion.1"),
    (9, 13, "while.2"), (9.5, 10, "fusion.3"), (13, 15, "while.6"),
    (15, 17, "while.7"), (17, 18, "fusion.9"),
]
# what one check reads, ms
CHECK_MS = {
    "scan": {"scan_g1": 1.0, "g2_stage": 4.0, "g2_stage/scan_g2": 3.0,
             "<unscoped>": 1.0, "<module>": 6.0},
    "other": {"<unscoped>": 1.0, "<module>": 1.0},
    "pair": {"miller_loop": 4.0, "final_exp": 5.0, "final_exp/pow_x_abs": 4.0,
             "<unscoped>": 0.0, "<module>": 9.0},
}


def _profile(host, device=True, checks=(0.0,)):
    """An XSpace with ``host``'s (name, start ms, end ms, {arg: str}) events
    on one line and, with ``device``, one check's launches at each start in
    ``checks``."""
    from jax.profiler import ProfileData

    names, stats = {}, {}

    def meta(table, name):
        return table.setdefault(name, len(table) + 1)

    def events(rows):
        out = []
        for name, start, end, args in rows:
            got = "".join(
                f' stats {{ metadata_id: {meta(stats, k)} str_value: "{v}" }}'
                for k, v in args.items()
            )
            out.append(
                f"events {{ metadata_id: {meta(names, name)} "
                f"offset_ps: {round(start * 1e9)} duration_ps: {round((end - start) * 1e9)}{got} }}"
            )
        return "\n".join(out)

    def metadata():
        return "\n".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in names.items()
        ) + "\n" + "\n".join(
            f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in stats.items()
        )

    text = ""
    if device:
        modules = [(n, s + t, e + t, {}) for t in checks for s, e, n in MODULES]
        ops = [(f"%{n} = s32[16]{{0}} fusion()", s + t, e + t, {})
               for t in checks for s, e, n in OPS]
        text += (
            'planes { id: 1 name: "/device:TPU:0"\n'
            f'lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0\n{events(modules)} }}\n'
            f'lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0\n{events(ops)} }}\n'
            f"{metadata()} }}\n"
        )
        names.clear()
        stats.clear()
    text += (
        'planes { id: 2 name: "/host:CPU"\n'
        f'lines {{ id: 1 name: "python" timestamp_ns: 0\n{events(host)} }}\n'
        f"{metadata()} }}\n"
    )
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text)
    )


def _check(t, maps=True):
    """A check's spans starting at ``t`` ms, its dispatches with their maps."""
    scan = {"scopes": SCAN_MAP} if maps else {}
    pair = {"scopes": PAIR_MAP} if maps else {}
    return [
        ("crypto.tpu.check", t + 0.5, t + 19, {}),
        ("crypto.tpu.scan_dispatch", t + 1, t + 1.5, scan),
        ("crypto.tpu.pair_dispatch", t + 1.5, t + 2, {"pairs": "3", **pair}),
        ("crypto.tpu.verdict_sync", t + 2, t + 18.5, {}),
    ]


def _flush(start, end):
    return ("crypto.flush", start, end, {"spans": "1:1"})


def test_the_maps_come_back_from_the_dispatch_spans():
    maps = rsc.read_maps(rs.read_spans(_profile(_check(0), device=False)))
    assert set(maps) == {"jit_hbbft_scan_16_16_2", "jit_hbbft_pair_3"}
    assert maps["jit_hbbft_pair_3"]["while.7"] == "final_exp/pow_x_abs"
    assert rsc.read_maps(rs.read_spans(_profile(_check(0, maps=False)))) == {}


def test_a_launch_takes_the_map_of_its_own_module():
    maps = {"jit_hbbft_scan_16_16_2": {"a": "x"}, "jit_hbbft_scan_16_16_25": {"a": "y"}}
    assert rsc.map_of("jit_hbbft_scan_16_16_2(833)", maps) == {"a": "x"}
    assert rsc.map_of("jit_hbbft_scan_16_16_256(9)", maps) == {}
    assert rsc.map_of("jit_hbbft_join_3(12)", maps) == {}


def test_nested_ops_count_once_and_an_op_without_a_scope_is_unscoped():
    got = rsc.reduce_profiles(_profile([_flush(0, 20)] + _check(0)), None)
    assert got["scopes_from"] == "1_whole_flushes"
    per = got["device_by_scope_ms"]
    assert set(per) == set(CHECK_MS)
    for kind, want in CHECK_MS.items():
        assert set(per[kind]) == set(want), kind
        for key, ms in want.items():
            assert per[kind][key] == pytest.approx(ms, abs=1e-9), (kind, key)
    # the top-level scopes and what none claims make up each launch
    for kind, ms in per.items():
        top = sum(v for k, v in ms.items() if "/" not in k and k != "<module>")
        assert top == pytest.approx(ms["<module>"], abs=1e-9), kind


def test_a_window_cut_inside_its_flush_is_read_per_whole_check():
    # the device's window holds two whole checks of a flush it did not hold
    # whole, and a third one cut (its pair launch outside the check's span)
    host_window = [_flush(0, 100)] + _check(0) + _check(20) + _check(40)
    device = _profile(_check(0) + _check(20) + [
        ("crypto.tpu.check", 40.5, 45, {}),
    ], checks=(0.0, 20.0, 40.0))
    got = rsc.reduce_profiles(device, _profile(host_window, device=False))
    assert got["scopes_from"] == "2_whole_checks_x_3_checks_per_flush"
    per = got["device_by_scope_ms"]
    for kind, want in CHECK_MS.items():
        for key, ms in want.items():
            assert per[kind][key] == pytest.approx(3 * ms, abs=1e-9), (kind, key)
    # without the host-only window nothing says how many checks a flush holds
    assert rsc.reduce_profiles(device, None)["device_by_scope_ms"] is None


def test_a_program_without_maps_reads_nothing():
    got = rsc.reduce_profiles(_profile([_flush(0, 20)] + _check(0, maps=False)), None)
    assert got["device_by_scope_ms"] is None
    assert rsc.reduce_profiles(None, None)["device_by_scope_ms"] is None


READERS = {
    "miller_loop_ms": 4.0, "final_exp_ms": 5.0, "scan_g1_ms": 1.0,
    "scan_g2_ms": 4.0, "device_unscoped_ms": 2.0,
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_the_cached_reduction(name, monkeypatch, tmp_path):
    import json

    trace = tmp_path / "device" / "plugins" / "profile" / "run"
    trace.mkdir(parents=True)
    (trace / "worker.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(rs, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(rs, "CACHE", str(tmp_path / "spans.json"))
    reduction = rsc.reduce_profiles(_profile([_flush(0, 20)] + _check(0)), None)
    reduction["reduce_scopes_s"] = 0.25
    reduction["key"] = rs._key([str(trace / "worker.xplane.pb"), None])
    (tmp_path / "scopes.json").write_text(json.dumps(reduction))
    reader = importlib.import_module("chipbench.layer_metrics." + name)
    obs = {"device_kind": "TPU v5 lite", "notes": {}}
    assert reader.read(obs) == pytest.approx(READERS[name], abs=1e-9)
    assert obs["notes"]["reduce_scopes_s"] == 0.25
    assert obs["notes"]["device_by_scope_ms"]["pair"]["miller_loop"] == pytest.approx(4.0)
    # no device, no trace to read: None, never 0
    assert reader.read({"device_kind": None, "notes": {}}) is None


def test_scan_g2_ms_reads_an_executable_compiled_before_g2_stage(monkeypatch):
    """Its map has the G2 scan at the top level, under no ``g2_stage``."""
    per = {"scan": {"scan_g1": 1.0, "scan_g2": 3.0, "<unscoped>": 2.0}}
    monkeypatch.setattr(rsc, "for_run", lambda obs: {"device_by_scope_ms": per})
    reader = importlib.import_module("chipbench.layer_metrics.scan_g2_ms")
    assert reader.read({}) == 3.0
    per["scan"].pop("scan_g2")
    assert reader.read({}) is None
