"""Work formulas, the table of peaks, percentiles and the layer readers."""

import pytest

from chipbench.harness import peaks, stats, work
from chipbench.layer_metrics import (
    device_busy_ms,
    device_programs_per_flush,
    flush_host_ms,
    flush_roofline,
    rpc_overhead_ms,
    worker_flush_ms,
)


def test_fq_muls_fixed_values():
    # per share 1593 (G1) + 3888 (G2) + 1159 (subgroup) = 6640; two Miller
    # loops 2268 + 2 * 4432 = 11132; final exponentiation 8458
    assert work.PER_SHARE["sig_share"] == 6640
    assert work.fq_muls("sig_share", 16, 1) == 16 * 6640 + 11132 + 8458 == 125830
    assert work.fq_muls("sig_share", 2048, 1) == 13618310
    # a second document is one more Miller loop, nothing else
    assert work.fq_muls("sig_share", 16, 2) - work.fq_muls("sig_share", 16, 1) == 4432
    with pytest.raises(KeyError):
        work.fq_muls("dec_share", 16, 1)
    with pytest.raises(ValueError):
        work.fq_muls("sig_share", 0, 1)


def test_least_seconds_names_its_bound():
    v5e = peaks.peaks_for("TPU v5 lite")
    least = work.least_seconds("sig_share", 16, 1, 40, v5e)
    assert least["bound"] == "compute_int8"
    assert least["seconds"] == pytest.approx(125830 * 13824 / 393e12)
    assert least["memory_s"] == pytest.approx((16 * 290 + 40) / 819e9)


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peaks_for("TPU v9")


def test_quantile_interpolates_between_ranks():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.quantile(xs, 0.5) == 30.0
    assert stats.quantile(xs, 0.95) == pytest.approx(48.0)
    assert stats.quantile(xs, 0.0) == 10.0 and stats.quantile(xs, 1.0) == 50.0
    assert stats.quantile([7.0], 0.95) == 7.0
    assert stats.quantile([4.0, 1.0, 3.0, 2.0], 0.5) == 2.5
    with pytest.raises(ValueError):
        stats.quantile([], 0.5)


def _obs(**over):
    obs = {
        "config": {"share_kind": "sig_share"},
        "traffic": {"params": {"requests": 16, "wrong": 0}},
        "device_kind": "TPU v5 lite",
        "flushes": 4, "client_s": 1.0, "worker_flush_s": 0.8, "worker_flushes": 4,
        "trace": {"busy_s": 0.2, "launches": 8}, "trace_cut": False, "host": None,
        "documents_per_flush": 1, "document_bytes": 40, "notes": {},
    }
    obs.update(over)
    return obs


def test_layer_readers_on_fixed_observations():
    obs = _obs()
    assert rpc_overhead_ms.read(obs) == pytest.approx(50.0)
    assert worker_flush_ms.read(obs) == pytest.approx(200.0)
    assert flush_host_ms.read(obs) == pytest.approx(150.0)
    assert device_programs_per_flush.read(obs) == 2.0
    assert device_busy_ms.read(obs) == pytest.approx(50.0)
    share = flush_roofline.read(obs)
    assert share == pytest.approx(125830 * 13824 / 393e12 / 0.05 * 100)
    assert obs["notes"]["roofline_bound"] == "compute_int8"


def test_readers_return_nothing_where_there_is_nothing_to_read():
    for nothing in (_obs(trace=None), _obs(trace_cut=True)):
        for reader in (flush_host_ms, device_programs_per_flush, device_busy_ms, flush_roofline):
            assert reader.read(nothing) is None
    # a window cut inside a flush: a whole flush's launches from the
    # host-only window, its device time from launches times module time
    cut = _obs(
        trace={"busy_s": 0.6, "launches": 54, "module_s_per_launch": 0.0125},
        trace_cut=True,
        host={"launches": 231, "flushes": 1, "worker_flush_s": 4.3, "worker_flushes": 1},
    )
    assert device_programs_per_flush.read(cut) == 231.0
    assert device_busy_ms.read(cut) == pytest.approx(2887.5)
    assert flush_host_ms.read(cut) == pytest.approx(4300.0 - 2887.5)
    assert flush_roofline.read(cut) is None
    assert cut["notes"] == {
        "device_programs_from": "host_only_window",
        "device_busy_from": "launches_x_module_time",
    }
    # a faulty round's device time is not the least work's: no share
    faulty = _obs(traffic={"params": {"requests": 16, "wrong": 5}})
    assert flush_roofline.read(faulty) is None
    assert rpc_overhead_ms.read(_obs(worker_flushes=3)) is None
    assert worker_flush_ms.read(_obs(worker_flushes=0)) is None
