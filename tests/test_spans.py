"""Spans inside the flush: ``Metrics.span``, the worker's span tree under a
profiler session on the CPU backend, the counters held to the spans, the
``stats`` op, the two programs' names.

No flush program is compiled: the two kernels are stubs whose verdicts are
scripted, so that one flush passes whole, one bisects down to a leaf and one
is the benchmark's 5 wrong of 16.  The oracle is patched to raise: every
verdict is the (stub) device's.
"""

import json
import os
import random
import subprocess
import sys
import time
from collections import Counter

import pytest

import jax
import jax.numpy as jnp

from hbbft_tpu.crypto.backend import VerifyRequest
from hbbft_tpu.crypto import flush_shapes
from hbbft_tpu.crypto.bls.suite import BLSSuite
from hbbft_tpu.crypto.keys import SecretKeySet
from hbbft_tpu.crypto.suite import ScalarSuite
from hbbft_tpu.crypto.tpu import backend as B
from hbbft_tpu.crypto.tpu import curve as dc
from hbbft_tpu.cryptoplane.proc_service import (
    CryptoRpcServer,
    RpcServiceClient,
    ServiceProcess,
    fetch_stats,
)
from hbbft_tpu.cryptoplane.service import CryptoPlaneService
from hbbft_tpu.utils.metrics import Metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Session:
    """A profiler session on the CPU backend; ``spans()`` closes it and
    gives every ``crypto.*`` event as (line, name, start, end, args)."""

    def __init__(self):
        from jax._src.lib import _profiler

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.devices()
        self._session = _profiler.ProfilerSession(options)

    def spans(self):
        from jax.profiler import ProfileData

        data = ProfileData.from_serialized_xspace(self._session.stop())
        out = []
        for p, plane in enumerate(data.planes):
            for l, line in enumerate(plane.lines):
                for ev in line.events:
                    name = ev.name.split("#")[0]
                    if name.startswith("crypto."):
                        out.append((
                            (p, l), name, ev.start_ns,
                            ev.start_ns + ev.duration_ns, dict(ev.stats),
                        ))
        return out


def test_span_is_a_timer_in_a_process_without_jax():
    code = (
        "import sys, json\n"
        "from hbbft_tpu.utils.metrics import Metrics\n"
        "m = Metrics()\n"
        "with m.span('crypto.t', rows=3) as note:\n"
        "    note(legs=2)\n"
        "print(json.dumps(['jax' in sys.modules, m.to_json()['timers']]))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        check=True, timeout=60,
    )
    has_jax, timers = json.loads(out.stdout.strip().splitlines()[-1])
    assert has_jax is False
    assert timers["crypto.t"]["count"] == 1


def test_span_is_an_event_of_an_open_profiler_session():
    m = Metrics()
    with m.span("crypto.before"):  # no session: a timer and nothing else
        pass
    session = Session()
    with m.span("crypto.t", rows=3, spans="1:5 2:7") as note:
        note(legs=2)
    (got,) = session.spans()
    assert got[1] == "crypto.t"
    assert got[4] == {"rows": 3, "spans": "1:5 2:7", "legs": 2}
    assert m.timers["crypto.t"].count == m.timers["crypto.before"].count == 1


# -- the worker's span tree ---------------------------------------------------

BUCKET = 16       # the smallest G1 and G2 bucket
BYZ5 = (1, 6, 7, 12, 14)  # one of the 1536 sets chipbench's byz5of16 admits

# ``verdicts``: the pair stub's answers in order; ``wrong``: the stub fails a
# check whose group holds one of these indices -> what the flush must count
SCRIPTS = {
    # one check, passes whole
    "whole": dict(
        n=2, verdicts=[True], answers=[True, True], checks=1, failed=0,
        leaves=0, rows=2, padded=2 * (BUCKET - 2), depths=[0], ahead=0,
    ),
    # the flush's check fails, the first half's too (a group of one: its
    # request is convicted by that check, a leaf), the second half passes
    "bisects_to_a_leaf": dict(
        n=2, verdicts=[False, False, True], answers=[False, True], checks=3,
        failed=2, leaves=1, rows=2 + 1 + 1,
        padded=2 * (BUCKET - 2) + 4 * (BUCKET - 1), depths=[0, 1, 1], ahead=1,
    ),
    # levels of 1, 2, 4, 6 and 8 checks; below the root every check but a
    # level's first was prepared ahead
    "byz5_of_16": dict(
        n=16, wrong=BYZ5, answers=[i not in BYZ5 for i in range(16)],
        checks=21, failed=1 + 2 + 3 + 4 + 5, leaves=5,
        rows=16 + 2 * 8 + 4 * 4 + 6 * 2 + 8 * 1,
        padded=2 * (21 * BUCKET - 68),
        depths=[0] + [1] * 2 + [2] * 4 + [3] * 6 + [4] * 8, ahead=16,
    ),
}


@pytest.fixture(scope="module")
def requests():
    suite = BLSSuite()
    sks = SecretKeySet.random(1, random.Random(5), suite)
    pks = sks.public_keys()
    return suite, [
        VerifyRequest.sig_share(
            pks.public_key_share(i), b"doc", sks.secret_key_share(i).sign(b"doc")
        )
        for i in range(16)
    ]


def _index(req):
    """Which signer's request this is (the RPC server decodes new objects)."""
    return req.payload[0].to_bytes()


def stub_kernels(monkeypatch, verdict, launched=None):
    """Replace the two programs; ``verdict(reqs)`` answers the pair stage
    for the requests of the most recent ``_scan_prep`` before its dispatch.
    Returns the list that every prepared group is appended to; ``launched``
    takes the program each call asks for: ``("scan", n1, n2, legs)``,
    ``("pair", pairs)``."""
    prepared = []
    launched = [] if launched is None else launched
    honest_prep = B.TpuBackend._scan_prep

    def scan_prep(self, reqs):
        prepared.append(list(reqs))
        return honest_prep(self, reqs)

    def fake_pair_kernel(n_pairs):
        launched.append(("pair", n_pairs))
        return lambda lhs, rhs: jnp.asarray(verdict(prepared[-1]))

    def fake_scan_kernel(n1, n2, nl):
        launched.append(("scan", n1, n2, nl))
        return lambda *args: (
            jnp.asarray(True),
            dc.identity(dc.G1_OPS, (1 + nl,)),
            dc.identity(dc.G2_OPS, (1 + nl,)),
        )

    monkeypatch.setattr(B.TpuBackend, "_scan_prep", scan_prep)
    monkeypatch.setattr(B, "_pair_kernel", fake_pair_kernel)
    monkeypatch.setattr(B, "_scan_kernel", fake_scan_kernel)
    monkeypatch.setattr(B, "_compile_pair_kernel_early", lambda n_pairs: None)
    return prepared


def stubbed_backend(suite, metrics=None):
    """A ``TpuBackend`` whose oracle raises: no verdict may come from it."""
    backend = B.TpuBackend(suite, metrics=metrics)

    def no_oracle(reqs):
        raise AssertionError("the oracle was asked for a verdict")

    backend._eager.verify_batch = no_oracle
    return backend


@pytest.fixture(params=sorted(SCRIPTS))
def flushed(request, requests, monkeypatch):
    """One RPC through server, service and a ``TpuBackend`` on stubbed
    kernels, under a profiler session: (script, metrics, spans)."""
    script = SCRIPTS[request.param]
    suite, reqs = requests
    reqs = reqs[: script["n"]]
    verdicts = list(script.get("verdicts", ()))
    wrong = {_index(reqs[i]) for i in script.get("wrong", ())}

    def verdict(group):
        if "wrong" in script:
            return not wrong & {_index(r) for r in group}
        return verdicts.pop(0)

    stub_kernels(monkeypatch, verdict)
    metrics = Metrics()
    service = CryptoPlaneService(
        stubbed_backend(suite, metrics), window_s=0.0, metrics=metrics
    )
    server = CryptoRpcServer(service, suite).start()
    client = RpcServiceClient(
        (server.host, server.port), suite, fallback=None, timeout_s=120.0
    )
    session = Session()
    try:
        assert client.verify_batch(reqs) == script["answers"]
        # the server's thread closes its reply and serve spans after the
        # client has the answer: wait for them, or the session lacks them
        deadline = time.monotonic() + 10.0
        while "crypto.rpc.serve" not in metrics.timers:
            assert time.monotonic() < deadline
            time.sleep(0.001)
    finally:
        spans = session.spans()
        client.close()
        server.stop()
    assert verdicts == []
    return script, metrics, spans


def test_counters_equal_the_spans_they_sit_beside(flushed):
    script, metrics, spans = flushed
    names = Counter(name for _, name, _, _, _ in spans)
    counters = metrics.counters
    assert names["crypto.flush"] == counters["crypto.flushes"] == 1
    assert names["crypto.window"] == 1
    assert names["crypto.tpu.well_formed"] == 1
    assert names["crypto.tpu.check"] == counters["crypto.tpu.checks"] == script["checks"]
    assert counters["crypto.tpu.checks_failed"] == script["failed"]
    assert names["crypto.tpu.leaf"] == counters["crypto.tpu.leaves"] == script["leaves"]
    assert counters["crypto.tpu.prepared_ahead"] == script["ahead"]
    assert counters["crypto.tpu.rows"] == script["rows"]
    assert counters["crypto.tpu.rows_padded"] == script["padded"]
    # a signature share is one G1 row (its key share) and one G2 row
    assert counters["crypto.tpu.g1_rows"] == counters["crypto.tpu.g2_rows"] == script["rows"]
    assert {k: v for k, v in counters.items() if ".requests." in k} == {
        "crypto.tpu.requests.sig_share": script["n"]
    }
    # one document a check, hashed once however many shares sign it
    assert (
        names["crypto.tpu.hash_to_g2"]
        == counters["crypto.tpu.hash_to_g2_calls"]
        == counters["crypto.tpu.rhs_hashed"]
        == script["checks"]
    )
    # every check is one of each of its stages
    for stage in ("scan_prep", "coefficients", "build_legs", "pack",
                  "scan_dispatch", "rhs_prep", "pair_dispatch", "verdict_sync"):
        assert names["crypto.tpu." + stage] == script["checks"], stage
    # and the timers the ``stats`` op exports count the same
    for name, n in names.items():
        assert metrics.timers[name].count == n, name
    checks = [a for _, name, _, _, a in spans if name == "crypto.tpu.check"]
    assert [a["depth"] for a in checks] == script["depths"]
    assert sum(a["rows"] for a in checks) == script["rows"]
    preps = [a for _, name, _, _, a in spans if name == "crypto.tpu.scan_prep"]
    assert {(a["n1"], a["n2"], a["legs"]) for a in preps} == {(BUCKET, BUCKET, 2)}


def test_a_check_holds_its_own_dispatches_and_the_next_groups_prep(flushed):
    """One scan launch, the right-hand points, one pair launch and one sync
    a check, in that order; a ``scan_prep`` before them is the check's own,
    one between the pair's dispatch and the sync is the next check's,
    prepared ahead."""
    script, _, spans = flushed
    checks = [s for s in spans if s[1] == "crypto.tpu.check"]
    ahead = 0
    for k, (_, _, start, end, args) in enumerate(checks):
        inside = {}
        for _, name, s, e, a in spans:
            if name.startswith("crypto.tpu.") and start <= s and e <= end:
                inside.setdefault(name[len("crypto.tpu."):], []).append((s, e, a))
        (scan,), (rhs,), (pair,), (sync,) = (
            inside[stage]
            for stage in ("scan_dispatch", "rhs_prep", "pair_dispatch", "verdict_sync")
        )
        assert scan[1] <= rhs[0] and rhs[1] <= pair[0] and pair[1] <= sync[0]
        (hashed,) = inside["hash_to_g2"]
        assert rhs[0] <= hashed[0] and hashed[1] <= rhs[1]
        assert rhs[2] == {"legs": 1, "hashed": 1}
        for s, e, a in inside.get("scan_prep", ()):
            if e <= scan[0]:
                assert a["rows"] == args["rows"]
            else:
                assert pair[1] <= s and e <= sync[0]
                nxt = checks[k + 1][4]
                assert a["rows"] == nxt["rows"] and nxt["depth"] == args["depth"]
                ahead += 1
    assert ahead == script["ahead"]


def test_spans_nest_on_the_flush_line_and_rpcs_carry_the_flushs_id(flushed):
    script, metrics, spans = flushed
    rows = script["n"]
    (flush,) = [s for s in spans if s[1] == "crypto.flush"]
    line, _, start, end, args = flush
    assert args["requests"] == rows and args["jobs"] == 1 and args["flush"] == 1
    for other, name, s, e, _ in spans:
        if name.startswith("crypto.tpu."):
            assert other == line and start <= s and e <= end, name
    by_name = {}
    for s in spans:
        by_name.setdefault(s[1], []).append(s)
    # a check holds its stages, scan_prep holds its three parts, the hash
    # lies in rhs_prep
    for parent, children in [
        ("crypto.tpu.check", ["scan_prep", "scan_dispatch", "rhs_prep",
                              "pair_dispatch", "verdict_sync"]),
        ("crypto.tpu.scan_prep", ["coefficients", "build_legs", "pack"]),
        ("crypto.tpu.rhs_prep", ["hash_to_g2"]),
    ]:
        for child in children:
            for _, _, s, e, _ in by_name["crypto.tpu." + child]:
                assert any(
                    ps <= s and e <= pe for _, _, ps, pe, _ in by_name[parent]
                ), (parent, child)
    # the RPC's four spans sit on another line and carry the id that the
    # flush lists
    rpc = [s for s in spans if s[1].startswith("crypto.rpc.")]
    assert sorted(s[1] for s in rpc) == [
        "crypto.rpc.decode", "crypto.rpc.reply", "crypto.rpc.serve",
        "crypto.rpc.wait",
    ]
    assert {s[0] for s in rpc} != {line} and len({s[0] for s in rpc}) == 1
    assert {s[4]["span"] for s in rpc} == set(args["spans"].split()) == {"1:1"}
    (serve,) = by_name["crypto.rpc.serve"]
    assert serve[4]["op"] == "verify" and serve[4]["requests"] == rows
    (decode,) = by_name["crypto.rpc.decode"]
    assert serve[4]["bytes"] == decode[4]["bytes"] > 0
    # a key share and a share a request; how many the memo of validated
    # bytes answered depends on what this process decoded before
    assert decode[4]["points"] == 2 * rows >= decode[4]["hits"] >= 0
    assert metrics.counters["crypto.rpc.decode_points"] == decode[4]["points"]
    assert metrics.counters["crypto.rpc.decode_point_hits"] == decode[4]["hits"]
    for s in rpc:
        assert serve[2] <= s[2] and s[3] <= serve[3]
    (wait,) = by_name["crypto.rpc.wait"]
    (window,) = by_name["crypto.window"]
    assert wait[2] <= start and end <= wait[3]
    assert window[0] == line and window[3] <= start


# -- bisection against the depth-first recursion it replaced -------------------

def depth_first_checks(idxs, wrong, depth=1):
    """The plain reference: the (depth, group) checks that the recursion
    before the level sweep made below a failed ``idxs``: a failed group's
    halves, the first half's subtree before the second half; a failed group
    of one went to the oracle."""
    if len(idxs) == 1:
        return []
    mid = len(idxs) // 2
    out = []
    for half in (idxs[:mid], idxs[mid:]):
        out.append((depth, half))
        if wrong & set(half):
            out += depth_first_checks(half, wrong, depth + 1)
    return out


@pytest.mark.parametrize(
    "n,wrong",
    [
        (16, ()), (16, (0,)), (16, (15,)), (16, (7, 8)), (16, BYZ5),
        (16, tuple(range(16))), (5, (0, 2, 4)),
    ],
    ids=["none", "first", "last", "two_adjacent", "byz5", "all", "3_of_5"],
)
def test_bisection_answers_the_wrong_set_with_the_recursions_checks(
    requests, monkeypatch, n, wrong
):
    suite, reqs = requests
    reqs = reqs[:n]
    where = {_index(r): i for i, r in enumerate(reqs)}
    prepared = stub_kernels(
        monkeypatch,
        lambda group: not any(where[_index(r)] in wrong for r in group),
    )
    backend = stubbed_backend(suite)
    assert backend.verify_batch(reqs) == [i not in wrong for i in range(n)]
    everyone = list(range(n))
    want = [(0, everyone)]
    if wrong:
        want += depth_first_checks(everyone, set(wrong))
    # the same checks, a level at a time instead of a subtree at a time (the
    # sort is stable: within a level, left to right as the recursion went)
    by_level = sorted(want, key=lambda check: check[0])
    got = [[where[_index(r)] for r in group] for group in prepared]
    assert got == [group for _, group in by_level]
    counters = backend.metrics.counters
    assert counters["crypto.tpu.checks"] == len(want)
    assert counters["crypto.tpu.leaves"] == len(wrong)
    levels = {depth for depth, _ in want[1:]}
    assert counters["crypto.tpu.prepared_ahead"] == len(want) - 1 - len(levels)


# -- the shapes a flush and its groups ask for ---------------------------------

@pytest.fixture(scope="module")
def wan_requests(requests):
    """One ciphertext's decrypt phase at N = 104: the ciphertext check, then
    the 103 other validators' decryption shares on it."""
    suite, _ = requests
    rng = random.Random(30)
    sks = SecretKeySet.random(1, rng, suite)
    pks = sks.public_keys()
    ct = pks.public_key().encrypt(bytes(range(250)), rng)
    return [VerifyRequest.ciphertext(ct)] + [
        VerifyRequest.dec_share(
            pks.public_key_share(i), ct, sks.secret_key_share(i).decryption_share(ct)
        )
        for i in range(103)
    ]


@pytest.fixture(scope="module")
def decrypt_requests(wan_requests):
    """The same at N = 16: the check, then the 15 other validators' shares."""
    return wan_requests[:16]


def _seeded(n, k, seed=30):
    return tuple(sorted(random.Random(seed).sample(range(n), k)))


# name -> (kind, requests, wrong positions).  ``dec``: that many shares on one
# ciphertext; ``check+dec``: the ciphertext check, then that many shares.
SHAPE_CASES = {
    "dec_1": ("dec", 1, ()),
    "dec_8": ("dec", 8, ()),
    "dec_15": ("dec", 15, ()),
    "check_and_15": ("check+dec", 15, ()),
    "dec_1_wrong": ("dec", 1, (0,)),
    "dec_8_bisected": ("dec", 8, _seeded(8, 2)),
    "dec_15_bisected": ("dec", 15, _seeded(15, 3)),
    "check_and_15_bisected": ("check+dec", 15, (0,) + tuple(1 + i for i in _seeded(15, 2))),
    "dec_17_bisected": ("dec", 17, _seeded(17, 2)),
    "dec_103": ("dec", 103, ()),
    "dec_103_bisected": ("dec", 103, _seeded(103, 2)),
    "check_and_103_bisected": ("check+dec", 103, (0,) + tuple(1 + i for i in _seeded(103, 1))),
    "sig_2": ("sig", 2, ()),
    "sig_16": ("sig", 16, ()),
    "sig_16_byz5": ("sig", 16, BYZ5),
    "sig_40": ("sig", 40, ()),
    "sig_40_bisected": ("sig", 40, _seeded(40, 2)),
}
# ``crypto.tpu.rows_padded`` of the coin's flushes, as before the floor
# counted requests
PADDED = {
    "sig_2": SCRIPTS["whole"]["padded"], "sig_16": 0,
    "sig_16_byz5": SCRIPTS["byz5_of_16"]["padded"],
}


@pytest.mark.parametrize("case", sorted(SHAPE_CASES))
def test_a_flush_and_every_group_of_it_ask_for_one_scan_program(
    requests, wan_requests, monkeypatch, case
):
    """A decrypt burst of up to 16 requests and every group bisection makes
    of it, down to a lone share or the lone check: ``scan(32,16,2)`` and
    ``pair(3)`` and nothing else.  The coin's flushes keep the programs and
    the padding they had; a flush above the floor buckets as before, and
    its groups run in ITS program (PR 35: the shape is handed down): a
    104-node network's burst of 103 shares and every group of it in
    ``scan(256,16,2)`` and ``pair(3)``."""
    kind, n, wrong = SHAPE_CASES[case]
    suite, sig_reqs = requests
    if kind == "sig":
        reqs = [sig_reqs[i % 16] for i in range(n)]
    else:
        reqs = wan_requests[0 if kind == "check+dec" else 1:][: n + (kind == "check+dec")]
    # 40 shares are of 16 signers: an object each, to tell them by position
    reqs = [VerifyRequest(r.kind, r.payload) for r in reqs]
    bad = {id(reqs[i]) for i in wrong}
    launched = []
    prepared = stub_kernels(
        monkeypatch, lambda group: not bad & {id(r) for r in group}, launched
    )
    backend = stubbed_backend(suite)
    assert backend.verify_batch(reqs) == [i not in wrong for i in range(len(reqs))]
    scans = [shape[1:] for shape in launched if shape[0] == "scan"]
    assert len(scans) == len(prepared) == backend.metrics.counters["crypto.tpu.checks"]
    assert {shape for shape in launched if shape[0] == "pair"} == {("pair", 3)}
    if kind == "sig":
        # one G1 and one G2 row a share: the bucket with the floor of 16 rows
        own = [(flush_shapes.bucket(len(g)),) * 2 + (2,) for g in prepared]
        want = [own[0]] * len(prepared)
        if n <= 16:
            assert set(want) == set(own) == {(16, 16, 2)}
            assert backend.metrics.counters["crypto.tpu.rows_padded"] == PADDED[case]
        else:
            assert want[0] == (64, 64, 2)
    else:
        # two G1 rows a share and one for the check, which brings the G2 row
        own = [
            (flush_shapes.bucket(sum(2 - (r.kind == "ciphertext") for r in g), 32), 16, 2)
            for g in prepared
        ]
        want = [own[0]] * len(prepared)
        assert want[0] == ((32, 16, 2) if n <= 15 else (64, 16, 2) if n == 17 else (256, 16, 2))
    assert scans == want
    # the groups whose own bucket is smaller than their flush's, counted
    handed = sum(o != w for o, w in zip(own, want))
    assert backend.metrics.counters.get("crypto.tpu.groups_handed_shape", 0) == handed
    assert (handed > 0) == (len(wrong) > 0 and n > 16)
    if handed:
        # below a flush over the floor every group is smaller than its flush
        assert handed == len(prepared) - 1
    if len(wrong) and n > 1:
        # bisection went down to groups of one, the lone check among them
        lone = [g[0].kind for g in prepared if len(g) == 1]
        assert len(lone) >= len(wrong)
        assert ("ciphertext" in lone) == (kind == "check+dec")


def test_the_floor_is_read_off_the_requests_and_nothing_else(decrypt_requests, requests):
    """No option, environment variable or argument steers the shape."""
    import inspect

    assert list(inspect.signature(B.TpuBackend.__init__).parameters) == [
        "self", "suite", "shard", "metrics",
    ]
    assert list(inspect.signature(B.TpuBackend._scan_prep).parameters) == ["self", "reqs"]
    assert list(inspect.signature(flush_shapes.scan_shape).parameters) == [
        "reqs", "g1_rows", "g2_rows", "legs",
    ]
    assert B._scan_shape is flush_shapes.scan_shape
    assert B._pairs_bucket is flush_shapes.pairs_bucket
    assert not hasattr(B, "_bucket")  # the buckets are the shape module's alone
    _, sig_reqs = requests
    check, share = decrypt_requests[:2]
    g1_floor = flush_shapes.g1_floor
    assert g1_floor(sig_reqs) == g1_floor(sig_reqs[:1]) == 16
    assert g1_floor([share]) == g1_floor([check]) == 32
    assert g1_floor(decrypt_requests) == g1_floor(sig_reqs + [share]) == 32
    # the same rows land in another program by the requests' kinds alone
    assert flush_shapes.scan_shape([share], 2, 0, 2) == (32, 16, 2)
    assert flush_shapes.scan_shape(sig_reqs[:2], 2, 2, 1) == (16, 16, 2)
    # and importing the rule brings no jax with it
    code = "import sys, hbbft_tpu.crypto.flush_shapes; sys.exit('jax' in sys.modules)"
    assert subprocess.run([sys.executable, "-c", code], cwd=ROOT).returncode == 0


def test_the_row_and_request_counters_of_a_decrypt_flush(decrypt_requests, requests, monkeypatch):
    """``g1_rows``, ``g2_rows`` and ``rows_padded`` are what the groups'
    requests bring by kind, ``requests.<kind>`` what entered the flush:
    once, not again in its groups, and not what the filter refused."""
    suite, _ = requests
    share = decrypt_requests[1]
    malformed = VerifyRequest.dec_share(share.payload[0], share.payload[1], "junk")
    reqs = decrypt_requests + [malformed]
    wrong = (0, 5, 11)
    bad = {id(reqs[i]) for i in wrong}
    prepared = stub_kernels(monkeypatch, lambda group: not bad & {id(r) for r in group})
    metrics = Metrics()
    backend = stubbed_backend(suite, metrics)
    session = Session()
    try:
        got = backend.verify_batch(reqs)
    finally:
        spans = session.spans()
    assert got == [i not in wrong for i in range(16)] + [False]
    counters = metrics.counters
    assert {k: v for k, v in counters.items() if ".requests." in k} == {
        "crypto.tpu.requests.ciphertext": 1, "crypto.tpu.requests.dec_share": 15,
    }
    by_kind = [Counter(r.kind for r in group) for group in prepared]
    g1 = [2 * c["dec_share"] + c["ciphertext"] for c in by_kind]
    g2 = [c["ciphertext"] for c in by_kind]
    assert counters["crypto.tpu.g1_rows"] == sum(g1)
    assert counters["crypto.tpu.g2_rows"] == sum(g2) > 1
    assert counters["crypto.tpu.rows_padded"] == sum(32 - a + 16 - b for a, b in zip(g1, g2))
    preps = [a for _, name, _, _, a in spans if name == "crypto.tpu.scan_prep"]
    assert [a["rows"] for a in preps] == [len(group) for group in prepared]
    assert {(a["n1"], a["n2"], a["legs"]) for a in preps} == {(32, 16, 2)}
    assert counters["crypto.tpu.rows"] == sum(a["rows"] for a in preps)
    legs = [(s, e) for _, name, s, e, _ in spans if name == "crypto.tpu.build_legs"]
    assert len(legs) == len(preps) == counters["crypto.tpu.checks"]
    # every group is on the one ciphertext: its hash input hashed once a
    # group under the group's scan; ``W`` comes ready wherever a share does
    rhs = [(s, e, a) for _, name, s, e, a in spans if name == "crypto.tpu.rhs_prep"]
    assert [a for _, _, a in rhs] == [
        {"legs": 1 + bool(c["dec_share"]), "hashed": 1} for c in by_kind
    ]
    hashes = [(s, e) for _, name, s, e, _ in spans if name == "crypto.tpu.hash_to_g2"]
    assert (
        len(hashes) == counters["crypto.tpu.hash_to_g2_calls"]
        == counters["crypto.tpu.rhs_hashed"] == len(prepared)
    )
    assert all(any(rs <= s and e <= re for rs, re, _ in rhs) for s, e in hashes)
    assert not any(ls <= s and e <= le for ls, le in legs for s, e in hashes)


# -- the scan program's G2 stage: which groups let it be skipped ----------------

# name -> (kind, requests, wrong positions)
G2_STAGE_CASES = {
    "dec_15": ("dec", 15, ()),
    "sig_16": ("sig", 16, ()),
    "check_and_15": ("check+dec", 15, ()),
    "sig_2_and_dec_8": ("sig+dec", 10, ()),
    "dec_15_bisected": ("dec", 15, _seeded(15, 3)),
    "sig_16_byz5": ("sig", 16, BYZ5),
    "check_and_15_bisected": ("check+dec", 15, (0,) + tuple(1 + i for i in _seeded(15, 2))),
}


@pytest.mark.parametrize("case", sorted(G2_STAGE_CASES))
def test_the_g2_stage_is_counted_skipped_where_a_group_brings_no_g2_row(
    requests, decrypt_requests, monkeypatch, case
):
    """The scan program's predicate for its G2 stage (``_any_g2_row``, on
    the arguments each group's scan is dispatched with): a flush of
    decryption shares alone and every group of it skip the stage, a coin
    flush and its groups never do, and of a decrypt phase's bisection
    exactly the groups that hold the ciphertext check (its ``W`` is the G2
    row) run it.  The host counts no skip: the stage's device time
    (``scan_g2_ms``) is its witness."""
    kind, n, wrong = G2_STAGE_CASES[case]
    suite, sig_reqs = requests
    if kind == "sig":
        reqs = sig_reqs[:n]
    elif kind == "sig+dec":
        reqs = sig_reqs[:2] + decrypt_requests[1 : n - 1]
    else:
        reqs = decrypt_requests[0 if kind == "check+dec" else 1:][: n + (kind == "check+dec")]
    reqs = [VerifyRequest(r.kind, r.payload) for r in reqs]
    bad = {id(reqs[i]) for i in wrong}
    prepared = stub_kernels(monkeypatch, lambda group: not bad & {id(r) for r in group})
    stage = []
    stub = B._scan_kernel

    def scan_kernel(n1, n2, nl):
        run = stub(n1, n2, nl)

        def call(*args):
            stage.append(int(B._any_g2_row(args[4][3], args[7])))
            return run(*args)

        return call

    monkeypatch.setattr(B, "_scan_kernel", scan_kernel)
    metrics = Metrics()
    backend = stubbed_backend(suite, metrics)
    session = Session()
    try:
        got = backend.verify_batch(reqs)
    finally:
        spans = session.spans()
    assert got == [i not in wrong for i in range(len(reqs))]
    # a sig_share's share and a ciphertext's W are the G2 rows there are
    want = [int(any(r.kind != "dec_share" for r in group)) for group in prepared]
    assert stage == want
    notes = [a for _, name, _, _, a in spans if name == "crypto.tpu.scan_prep"]
    assert len(notes) == len(want) and not any("g2" in a for a in notes)
    counters = metrics.counters
    assert "crypto.tpu.g2_stage_skipped" not in counters
    assert len(want) == counters["crypto.tpu.checks"]
    assert (counters["crypto.tpu.g2_rows"] == 0) == (not any(want))
    if kind == "dec":
        assert not any(want)
    elif kind == "check+dec" and wrong:
        # the decrypt cells' probe: some groups of it took each branch
        assert 0 < sum(want) < len(want)
    else:
        assert all(want)


# -- the hash to G2 runs under the dispatched scan ------------------------------

# name -> (kind, requests, wrong positions, CHUNK or None)
ORDER_CASES = {
    "sig_16": ("sig", 16, (), None),
    "dec_15": ("dec", 15, (), None),
    "check_and_15": ("check+dec", 15, (), None),
    "sig_16_byz5": ("sig", 16, BYZ5, None),
    "dec_15_bisected": ("dec", 15, _seeded(15, 3), None),
    "sig_16_in_4_chunks": ("sig", 16, (), 4),
    # the wrong share lies in the last chunk: see ``verdict`` below
    "sig_16_in_4_chunks_bisected": ("sig", 16, (13,), 4),
    "dec_15_in_2_chunks": ("dec", 15, (), 8),
}


@pytest.mark.parametrize("case", sorted(ORDER_CASES))
def test_the_hash_runs_after_the_scans_dispatch_and_before_the_pairs(
    requests, decrypt_requests, monkeypatch, case
):
    """A check is ``scan_prep``, ``scan_dispatch``, ``rhs_prep`` (the
    ``hash_to_g2`` spans inside it, one a distinct leg that brings no point:
    16 shares on one document hash once, 15 decryption shares on one
    ciphertext once, their ``W`` ready), ``pair_dispatch``, ``verdict_sync``:
    on the flush's check, on every group of a bisection level (the next
    group still prepared between ``pair_dispatch`` and ``verdict_sync``) and,
    where a flush is several chunks, with every chunk's scan dispatched
    before the first hash."""
    kind, n, wrong, chunk = ORDER_CASES[case]
    suite, sig_reqs = requests
    if kind == "sig":
        reqs = sig_reqs[:n]
    else:
        reqs = decrypt_requests[0 if kind == "check+dec" else 1:][: n + (kind == "check+dec")]
    reqs = [VerifyRequest(r.kind, r.payload) for r in reqs]
    bad = {id(reqs[i]) for i in wrong}
    chunks = -(-len(reqs) // chunk) if chunk else 0
    pair_calls = []

    def verdict(group):
        # the stub hands over the group prepared last, which is not what a
        # flush of several chunks checks first: all of it, then (after a
        # failure) chunk by chunk, before bisection prepares anything more
        pair_calls.append(group)
        if 1 < len(pair_calls) <= 1 + chunks:
            group = reqs[(len(pair_calls) - 2) * chunk :][:chunk]
        elif chunks and len(pair_calls) == 1:
            group = reqs
        return not bad & {id(r) for r in group}

    prepared = stub_kernels(monkeypatch, verdict)
    metrics = Metrics()
    backend = stubbed_backend(suite, metrics)
    if chunk:
        backend.CHUNK = chunk
    session = Session()
    try:
        got = backend.verify_batch(reqs)
    finally:
        spans = session.spans()
    assert got == [i not in wrong for i in range(len(reqs))]

    def named(stage):
        return sorted(
            (s, e, a) for _, name, s, e, a in spans if name == "crypto.tpu." + stage
        )

    def within(inner, outer):
        return [x for x in inner if any(o[0] <= x[0] and x[1] <= o[1] for o in outer)]

    hashes, rhs_preps = named("hash_to_g2"), named("rhs_prep")
    # one rhs_prep a scan that was dispatched; a group's legs by its kinds
    assert len(rhs_preps) == len(named("scan_dispatch")) == len(prepared)
    want = []
    for group in prepared:
        kinds = {r.kind for r in group}
        want.append({"legs": 1 + ("dec_share" in kinds), "hashed": 1})
    assert [a for _, _, a in rhs_preps] == want
    counters = metrics.counters
    assert (
        len(hashes) == counters["crypto.tpu.hash_to_g2_calls"]
        == counters["crypto.tpu.rhs_hashed"] == len(prepared)
    )
    assert within(hashes, rhs_preps) == hashes
    for stage in ("scan_prep", "build_legs", "scan_dispatch", "pair_dispatch"):
        assert within(hashes, named(stage)) == [], stage
    checks = named("check")
    if chunk is None or wrong:
        # the one-chunk check, and every group of bisection
        one_chunk = [c for c in checks if within(named("scan_dispatch"), [c])]
        assert len(one_chunk) == len(checks) if chunk is None else one_chunk
        ahead = 0
        for check in one_chunk:
            (scan,), (rhs,), (pair,), (sync,) = (
                within(named(stage), [check])
                for stage in ("scan_dispatch", "rhs_prep", "pair_dispatch", "verdict_sync")
            )
            assert scan[1] <= rhs[0] and rhs[1] <= pair[0] and pair[1] <= sync[0]
            (hashed,) = within(hashes, [check])
            assert scan[1] <= hashed[0] and hashed[1] <= pair[0]
            for prep in within(named("scan_prep"), [check]):
                if prep[1] > scan[0]:
                    assert pair[1] <= prep[0] and prep[1] <= sync[0]
                    ahead += 1
        assert ahead == counters["crypto.tpu.prepared_ahead"]
        assert (ahead > 0) == (len(wrong) > 0)
    if chunk:
        # the flush's chunks: every scan dispatched, then every chunk's
        # hash, then the one pair stage; all of it beside the checks
        assert [len(g) for g in prepared[:chunks]] == [
            len(reqs[i : i + chunk]) for i in range(0, len(reqs), chunk)
        ]
        scans, rhs = named("scan_dispatch")[:chunks], rhs_preps[:chunks]
        assert max(e for _, e, _ in scans) <= min(s for s, _, _ in hashes)
        assert max(e for _, e, _ in scans) <= rhs[0][0]
        assert rhs[-1][1] <= named("pair_dispatch")[0][0] and rhs[-1][1] <= checks[0][0]
        assert within(scans + rhs + named("scan_prep")[:chunks], checks) == []
        # a failed combined check re-checks chunk by chunk on the points it
        # has: no second hash before bisection prepares its groups
        assert len(within(named("pair_dispatch"), checks[: 1 + chunks * bool(wrong)])) == (
            1 + chunks * bool(wrong)
        )
        assert within(hashes, checks[: 1 + chunks * bool(wrong)]) == []


def test_stats_op_of_an_eager_worker_keeps_its_shape():
    """The service's and the server's spans come back as timers; a backend
    that counts nothing adds nothing."""
    suite = ScalarSuite()
    sks = SecretKeySet.random(1, random.Random(5), suite)
    pks = sks.public_keys()
    reqs = [
        VerifyRequest.sig_share(
            pks.public_key_share(i), b"doc", sks.secret_key_share(i).sign(b"doc")
        )
        for i in range(2)
    ]
    with ServiceProcess(suite="scalar", backend="eager") as svc:
        client = RpcServiceClient(svc.addr, suite, fallback=None)
        try:
            assert client.verify_batch(reqs) == [True, True]
        finally:
            client.close()
        # the worker closes the reply and serve spans of that request after
        # its answer is out; only a ``stats`` request, which would count
        # itself, could ask whether it has
        time.sleep(0.5)
        stats = fetch_stats(svc.addr, suite)
    assert set(stats) == {"counters", "gauges", "timers", "summaries"}
    assert not [k for group in stats.values() for k in group if ".tpu." in k]
    assert stats["counters"]["crypto.flushes"] == 1
    timers = {k: v["count"] for k, v in stats["timers"].items()}
    # the ``stats`` request itself was decoded by then; its serve and its
    # reply were still to end
    assert timers == {
        "crypto.window": 1, "crypto.flush": 1, "crypto.rpc.serve": 1,
        "crypto.rpc.decode": 2, "crypto.rpc.wait": 1, "crypto.rpc.reply": 1,
    }
    assert set(stats["timers"]["crypto.flush"]) == {
        "count", "total_s", "mean_s", "max_s"
    }


@pytest.mark.parametrize(
    "kernel,shape,name",
    [
        (B._scan_kernel, (16, 16, 2), "hbbft_scan_16_16_2"),
        (B._pair_kernel, (3,), "hbbft_pair_3"),
        (B._join_kernel, (3,), "hbbft_join_3"),
    ],
)
def test_the_two_programs_have_names_of_their_own(kernel, shape, name):
    """``jax.jit`` names a module ``jit_<function name>``: read off the
    jitted object, nothing is lowered.  The join between them is neither a
    ``jit_hbbft_scan_`` nor a ``jit_hbbft_pair_`` module."""
    jitted = kernel(*shape)
    assert jitted.__name__ == jitted.__wrapped__.__name__ == name


# -- the dispatch spans carry their programs' op-to-scope maps ---------------

def _scoped_programs(monkeypatch):
    """Small jitted programs in the two kernels' places, with the kernels'
    signatures and a loop under a named scope each; the pair program passes
    every check."""
    programs = {}

    def scan_kernel(n1, n2, nl):
        def hbbft_scan(*args):
            with jax.named_scope("scan_g1"):
                bits = jax.lax.fori_loop(0, 7, lambda i, b: b * 3 + 1, args[1])
            return (
                jnp.all(bits != 7),
                dc.identity(dc.G1_OPS, (1 + nl,)),
                dc.identity(dc.G2_OPS, (1,)),
            )

        return programs.setdefault(("scan", n1, n2, nl), jax.jit(hbbft_scan))

    def pair_kernel(n_pairs):
        def hbbft_pair(lhs, rhs):
            with jax.named_scope("miller_loop"):
                flags = jax.lax.fori_loop(0, 7, lambda i, f: f * 3 + 1, lhs[3])
            return jnp.all(flags != 7)

        return programs.setdefault(("pair", n_pairs), jax.jit(hbbft_pair))

    monkeypatch.setattr(B, "_scan_kernel", scan_kernel)
    monkeypatch.setattr(B, "_pair_kernel", pair_kernel)
    monkeypatch.setattr(B, "_compile_pair_kernel_early", lambda n_pairs: None)


def test_the_dispatch_spans_carry_their_programs_scopes(requests, monkeypatch):
    """From a program's first call on, each dispatch span names, in its arg
    ``scopes``, the scope of every op of the executable it runs: the first
    dispatch of a shape notes it once the program has run, the next ones
    carry it from the start.  A kernel with no HLO (a stub) gives none."""
    from hbbft_tpu.utils import op_scopes

    suite, reqs = requests
    _scoped_programs(monkeypatch)
    backend = stubbed_backend(suite)
    session = Session()
    try:
        assert backend.verify_batch(reqs) == [True] * 16
        assert backend.verify_batch(reqs) == [True] * 16
    finally:
        spans = session.spans()
    dispatches = {"crypto.tpu.scan_dispatch": ("jit_hbbft_scan", "scan_g1"),
                  "crypto.tpu.pair_dispatch": ("jit_hbbft_pair", "miller_loop")}
    for name, (module, scope) in dispatches.items():
        args = [a for _, n, _, _, a in spans if n == name]
        assert len(args) == 2 and args[0]["scopes"] == args[1]["scopes"]
        assert "," not in args[0]["scopes"]
        got_module, scopes = op_scopes.decode(args[0]["scopes"])
        assert got_module == module and scope in scopes.values()
    # the stubs of the benchmark's traced tests have no HLO: no map, no arg
    stub_kernels(monkeypatch, lambda group: True)
    backend = stubbed_backend(suite)
    session = Session()
    try:
        assert backend.verify_batch(reqs) == [True] * 16
    finally:
        spans = session.spans()
    assert [a.get("scopes") for _, n, _, _, a in spans if n in dispatches] == [None] * 2
