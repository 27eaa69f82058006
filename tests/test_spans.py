"""Spans inside the flush: ``Metrics.span``, the worker's span tree under a
profiler session on the CPU backend, the counters held to the spans, the
``stats`` op, the two programs' names.

No flush program is compiled: the two kernels are stubs whose verdicts are
scripted, so that one flush passes whole and one bisects down to a leaf.
"""

import json
import random
import subprocess
import sys
from collections import Counter

import pytest

import jax
import jax.numpy as jnp

from hbbft_tpu.crypto.backend import VerifyRequest
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

ROWS = 2          # requests a flush
BUCKET = 16       # the smallest G1 and G2 bucket

# verdicts of the pair stub, in order -> what the flush must count
SCRIPTS = {
    # one check, passes whole
    "whole": dict(
        verdicts=[True], checks=1, failed=0, leaves=0, rows=2,
        padded=2 * (BUCKET - 2), depths=[0],
    ),
    # the flush's check fails, the first half's too (its one request goes
    # to the oracle: a leaf), the second half passes
    "bisects_to_a_leaf": dict(
        verdicts=[False, False, True], checks=3, failed=2, leaves=1,
        rows=2 + 1 + 1, padded=2 * (BUCKET - 2) + 4 * (BUCKET - 1),
        depths=[0, 1, 1],
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
        for i in range(ROWS)
    ]


@pytest.fixture(params=sorted(SCRIPTS))
def flushed(request, requests, monkeypatch):
    """One RPC through server, service and a ``TpuBackend`` on stubbed
    kernels, under a profiler session: (script, metrics, spans)."""
    script = SCRIPTS[request.param]
    verdicts = list(script["verdicts"])

    def fake_pair_kernel(n_pairs):
        return lambda lhs, rhs: jnp.asarray(verdicts.pop(0))

    def fake_scan_kernel(n1, n2, nl):
        return lambda *args: (
            jnp.asarray(True),
            dc.identity(dc.G1_OPS, (1 + nl,)),
            dc.identity(dc.G2_OPS, (1 + nl,)),
        )

    monkeypatch.setattr(B, "_pair_kernel", fake_pair_kernel)
    monkeypatch.setattr(B, "_scan_kernel", fake_scan_kernel)
    monkeypatch.setattr(B, "_compile_pair_kernel_early", lambda n_pairs: None)

    suite, reqs = requests
    metrics = Metrics()
    service = CryptoPlaneService(
        B.TpuBackend(suite, metrics=metrics), window_s=0.0, metrics=metrics
    )
    server = CryptoRpcServer(service, suite).start()
    client = RpcServiceClient(
        (server.host, server.port), suite, fallback=None, timeout_s=120.0
    )
    session = Session()
    try:
        assert client.verify_batch(reqs) == [True] * ROWS
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
    assert counters["crypto.tpu.rows"] == script["rows"]
    assert counters["crypto.tpu.rows_padded"] == script["padded"]
    assert (
        names["crypto.tpu.hash_to_g2"]
        == counters["crypto.tpu.hash_to_g2_calls"]
        == script["rows"]
    )
    # every check is one of each of its stages
    for stage in ("scan_prep", "coefficients", "pack", "scan_dispatch",
                  "pair_dispatch", "verdict_sync"):
        assert names["crypto.tpu." + stage] == script["checks"], stage
    # and the timers the ``stats`` op exports count the same
    for name, n in names.items():
        assert metrics.timers[name].count == n, name
    checks = [a for _, name, _, _, a in spans if name == "crypto.tpu.check"]
    assert [a["depth"] for a in checks] == script["depths"]
    assert sum(a["rows"] for a in checks) == script["rows"]
    preps = [a for _, name, _, _, a in spans if name == "crypto.tpu.scan_prep"]
    assert {(a["n1"], a["n2"], a["legs"]) for a in preps} == {(BUCKET, BUCKET, 2)}


def test_spans_nest_on_the_flush_line_and_rpcs_carry_the_flushs_id(flushed):
    _, _, spans = flushed
    (flush,) = [s for s in spans if s[1] == "crypto.flush"]
    line, _, start, end, args = flush
    assert args["requests"] == ROWS and args["jobs"] == 1 and args["flush"] == 1
    for other, name, s, e, _ in spans:
        if name.startswith("crypto.tpu."):
            assert other == line and start <= s and e <= end, name
    by_name = {}
    for s in spans:
        by_name.setdefault(s[1], []).append(s)
    # a check holds its stages, scan_prep holds its three parts
    for parent, children in [
        ("crypto.tpu.check", ["scan_prep", "scan_dispatch", "pair_dispatch",
                              "verdict_sync"]),
        ("crypto.tpu.scan_prep", ["coefficients", "hash_to_g2", "pack"]),
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
    assert serve[4]["op"] == "verify" and serve[4]["requests"] == ROWS
    assert serve[4]["bytes"] == by_name["crypto.rpc.decode"][0][4]["bytes"] > 0
    for s in rpc:
        assert serve[2] <= s[2] and s[3] <= serve[3]
    (wait,) = by_name["crypto.rpc.wait"]
    (window,) = by_name["crypto.window"]
    assert wait[2] <= start and end <= wait[3]
    assert window[0] == line and window[3] <= start


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
        for i in range(ROWS)
    ]
    with ServiceProcess(suite="scalar", backend="eager") as svc:
        client = RpcServiceClient(svc.addr, suite, fallback=None)
        try:
            assert client.verify_batch(reqs) == [True] * ROWS
        finally:
            client.close()
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
    ],
)
def test_the_two_programs_have_names_of_their_own(kernel, shape, name):
    """``jax.jit`` names a module ``jit_<function name>``: read off the
    jitted object, nothing is lowered."""
    jitted = kernel(*shape)
    assert jitted.__name__ == jitted.__wrapped__.__name__ == name
