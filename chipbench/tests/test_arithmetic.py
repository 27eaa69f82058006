"""Work formulas, the table of peaks, percentiles and the layer readers."""

import pytest

from chipbench import kinds
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
    assert kinds.load("sig_share").SCAN_FQ_MULS == 6640
    assert work.fq_muls({"sig_share": 16}, 2) == 16 * 6640 + 11132 + 8458 == 125830
    assert work.fq_muls({"sig_share": 2048}, 2) == 13618310
    # a second document is one more Miller loop, nothing else
    assert work.fq_muls({"sig_share": 16}, 3) - work.fq_muls({"sig_share": 16}, 2) == 4432
    with pytest.raises(KeyError, match="chipbench/kinds/key_share.py"):
        work.fq_muls({"key_share": 16}, 2)
    with pytest.raises(ValueError):
        work.fq_muls({"sig_share": 0}, 2)
    with pytest.raises(ValueError):
        work.fq_muls({"sig_share": 16}, 1)


def test_fq_muls_of_the_decrypt_phase_fixed_values():
    # G1 subgroup check: two chains of 63 doublings at 7, 5 mixed additions
    # at 11 and 5 general ones at 16, one product for the endomorphism
    assert work.G1_SUBGROUP_CHECK == 2 * 63 * 7 + 5 * 11 + 5 * 16 + 1 == 1018
    # a decryption share: two G1 scalar multiplications and that check
    assert kinds.load("dec_share").SCAN_FQ_MULS == 2 * 1593 + 1018 == 4204
    # a ciphertext check: a signature share's work and the G1 check of U
    assert kinds.load("ciphertext").SCAN_FQ_MULS == 6640 + 1018 == 7658
    # one ciphertext check: the generator's pair and its hash's
    assert work.fq_muls({"ciphertext": 1}, 2) == 7658 + 11132 + 8458 == 27248
    # 15 shares on one ciphertext: H(U, V) and W, no generator pair
    assert work.fq_muls({"dec_share": 15}, 2) == 15 * 4204 + 11132 + 8458 == 82650
    # both in one flush: the generator's, the hash's (shared) and W's
    assert work.fq_muls({"ciphertext": 1, "dec_share": 15}, 3) == (
        7658 + 15 * 4204 + 2268 + 3 * 4432 + 8458
    ) == 94740


U, V, W, W2 = b"u" * 97, b"v" * 40, b"w" * 193, b"x" * 193


def _shares(n, u=U, v=V, w=W):
    return ["dec_share"] * n, [
        (bytes([i]) * 97, u, v, w, bytes([100 + i]) * 97) for i in range(n)
    ]


@pytest.mark.parametrize(
    "kinds_,wire,requests,pairs,wire_bytes",
    [
        # 16 signature shares on one document: the document is sent once
        (["sig_share"] * 16, [(bytes([i]) * 97, b"d" * 40, b"s" * 193) for i in range(16)],
         {"sig_share": 16}, 2, 16 * 290 + 40),
        # on two documents: one more pair
        (["sig_share"] * 4, [(b"p" * 97, b"d%d" % (i % 2), b"s" * 193) for i in range(4)],
         {"sig_share": 4}, 3, 4 * 290 + 2 * 2),
        (["ciphertext"], [(U, V, W)], {"ciphertext": 1}, 2, 97 + 40 + 193),
        # a decryption share carries its whole ciphertext, every time
        (*_shares(15), {"dec_share": 15}, 2, 15 * (97 + 97 + 97 + 40 + 193)),
        # the check's W is the shares' W: generator, H(U, V), W
        (["ciphertext"] + _shares(15)[0], [(U, V, W)] + _shares(15)[1],
         {"ciphertext": 1, "dec_share": 15}, 3, 330 + 15 * 524),
        # a check sent with another's W shares the hash's pair alone
        (["ciphertext"] + _shares(3)[0], [(U, V, W2)] + _shares(3)[1],
         {"ciphertext": 1, "dec_share": 3}, 3, 330 + 3 * 524),
        # shares on two ciphertexts: two hashes, two Ws
        (_shares(2)[0] * 2, _shares(2)[1] + _shares(2, u=b"t" * 97, w=W2)[1],
         {"dec_share": 4}, 4, 4 * 524),
        # a coin round and a decrypt phase in one flush
        (["sig_share"] + _shares(2)[0], [(b"p" * 97, b"doc", b"s" * 193)] + _shares(2)[1],
         {"sig_share": 1, "dec_share": 2}, 4, 290 + 3 + 2 * 524),
    ],
)
def test_a_flushs_composition_and_its_two_parts(kinds_, wire, requests, pairs, wire_bytes):
    flush = work.compose(kinds_, wire)
    assert flush == work.Composition(requests, pairs, wire_bytes)
    # the scan program's part and the pair program's sum to the whole, exactly
    assert (
        work.scan_fq_muls(flush.requests) + work.pair_fq_muls(flush.pairs)
        == work.fq_muls(flush.requests, flush.pairs)
    )
    assert work.scan_fq_muls(flush.requests) == sum(
        n * kinds.load(k).SCAN_FQ_MULS for k, n in requests.items()
    )
    assert work.pair_fq_muls(pairs) == 2268 + pairs * 4432 + 8458


def test_least_seconds_names_its_bound():
    v5e = peaks.peaks_for("TPU v5 lite")
    least = work.least_seconds(work.Composition({"sig_share": 16}, 2, 16 * 290 + 40), v5e)
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
        "config": {},
        "traffic": {"params": {"requests": 16, "wrong": 0}},
        "device_kind": "TPU v5 lite",
        "flushes": 4, "client_s": 1.0, "worker_flush_s": 0.8, "worker_flushes": 4,
        "trace": {"busy_s": 0.2, "launches": 8}, "trace_cut": False, "host": None,
        "work": work.Composition({"sig_share": 16}, 2, 16 * 290 + 40), "notes": {},
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
