"""Crypto plane as a process (round 18): the RPC boundary tier.

What this file pins, mirroring the round-13 in-thread tier one level
out:

* **Verdict identity through the socket**: an :class:`RpcServiceClient`
  returns exactly the local backend's verdicts — good, bad, and
  unserializable-junk requests included (the deferred-verification
  invariant survives the serialization boundary).
* **Framing fuzz parity** (the transport corrupt-frame tier's rules on
  the crypto kind set): corrupted/truncated/oversized/wrong-plane
  frames kill only the offending CONNECTION — the server keeps serving
  fresh dials, and a client fed garbage falls back locally instead of
  wedging its flush.
* **batches_sha identity** of the rpc-service vs in-thread-service vs
  inline arms at N=4 seed 0 (both node impls for the RPC arm).
* **SIGKILL-mid-flush drill**: clients fall back with no lost or
  duplicated fault attributions and re-attach when a new service
  process comes up on the old port (both impls, plus the
  process-per-node runtime via ``ProcCluster.kill_service``).
* **Fault-multiset parity at the RPC boundary**: the seeded
  TamperingAdversary sim commits identical batches AND identical fault
  logs whether shares verify in scalar C or through the service
  process.

Batched CPU backend only — no jax/XLA, safe during crypto-cache cold
states; native halves skip cleanly without g++.  ``make
cryptoplane-smoke`` runs this with the round-13 tier.
"""

from __future__ import annotations

import random
import socket
import threading
import time

import pytest

import hbbft_tpu.wire  # noqa: F401  (vreq struct registration)
from hbbft_tpu.chaos.oracle import batch_keys, batches_sha, fault_entries
from hbbft_tpu.crypto.backend import BatchedBackend, VerifyRequest
from hbbft_tpu.crypto.keys import SecretKeySet
from hbbft_tpu.crypto.suite import ScalarSuite
from hbbft_tpu.cryptoplane import CryptoPlaneService
from hbbft_tpu.cryptoplane.proc_service import (
    CryptoRpcServer,
    RpcServiceClient,
    ServiceProcess,
    fetch_stats,
    parse_addr,
)
from hbbft_tpu.protocols.queueing_honey_badger import Input
from hbbft_tpu.transport import LocalCluster
from hbbft_tpu.transport.framing import (
    CRYPTO_KINDS,
    KIND_CRYPTO_HELLO,
    KIND_CRYPTO_REQ,
    KIND_MSG,
    FrameDecoder,
    FrameError,
    encode_frame,
)
from hbbft_tpu.transport.proc_cluster import ProcCluster
from hbbft_tpu.utils import serde
from hbbft_tpu.utils.metrics import Metrics

EPOCH_TIMEOUT_S = 45  # wall cap per driven phase; typical is < 3 s


def _lib_or_skip():
    from hbbft_tpu import native_engine

    lib = native_engine.get_lib()
    if lib is None:
        pytest.skip("native engine unavailable (no compiler?)")
    return lib


def _impl_or_skip(impl: str) -> str:
    if impl == "native":
        _lib_or_skip()
    return impl


def _scalar_fixture():
    suite = ScalarSuite()
    rng = random.Random(5)
    sks = SecretKeySet.random(1, rng, suite)
    pks = sks.public_keys()
    good = VerifyRequest.sig_share(
        pks.public_key_share(0), b"doc", sks.secret_key_share(0).sign(b"doc")
    )
    bad = VerifyRequest.sig_share(
        pks.public_key_share(1), b"doc", sks.secret_key_share(0).sign(b"doc")
    )
    return suite, good, bad


def _server(suite, **kw):
    svc = CryptoPlaneService(BatchedBackend(suite), window_s=0.0, **kw)
    return CryptoRpcServer(svc, suite).start()


# ---------------------------------------------------------------------------
# verdict identity + protocol basics (in-process server, no subprocess)
# ---------------------------------------------------------------------------


def test_rpc_verdicts_identical_to_local_backend():
    suite, good, bad = _scalar_fixture()
    junk = VerifyRequest("sig_share", (object(), b"m", object()))
    batch = [good, bad, good, junk, bad]
    server = _server(suite)
    try:
        cli = RpcServiceClient(
            (server.host, server.port), suite, BatchedBackend(suite),
            metrics=Metrics(),
        )
        want = BatchedBackend(suite).verify_batch(batch)
        assert cli.verify_batch(batch) == want == [True, False, True,
                                                  False, False]
        assert cli.metrics.counters["crypto.rpc.calls"] == 1
        assert cli.metrics.counters.get("crypto.rpc.fallbacks", 0) == 0
        assert cli.verify_batch([]) == []
        # the response reported the merged flush size (the client's
        # amortization observable)
        assert cli.metrics.counters["crypto.rpc.merged_requests"] >= 4
    finally:
        server.stop()


def test_rpc_concurrent_clients_merge_into_one_flush():
    """Three clients on three sockets land in ONE backend flush when
    the window holds — the cross-PROCESS version of the round-13
    cross-thread merge test (here cross-connection; the process drill
    is the ProcCluster test below)."""
    suite, good, bad = _scalar_fixture()
    svc = CryptoPlaneService(BatchedBackend(suite), window_s=0.1)
    server = CryptoRpcServer(svc, suite).start()
    try:
        out = {}
        barrier = threading.Barrier(3)

        def worker(i):
            cli = RpcServiceClient(
                (server.host, server.port), suite, BatchedBackend(suite),
                client_id=f"c{i}",
            )
            barrier.wait()
            out[i] = (cli.verify_batch([good, bad, good]),
                      cli.metrics.counters.get("crypto.rpc.merged_requests",
                                               0))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(out[i][0] == [True, False, True] for i in range(3)), out
        # at least one client's flush rode a merged batch (all three
        # released together, well inside the 100 ms window; full 9-way
        # merging is scheduling-dependent on the 1-core box)
        assert max(out[i][1] for i in range(3)) >= 6, out
    finally:
        server.stop()


def test_stats_rpc_and_parse_addr():
    suite, good, _ = _scalar_fixture()
    server = _server(suite)
    try:
        cli = RpcServiceClient(
            (server.host, server.port), suite, BatchedBackend(suite)
        )
        assert cli.verify_batch([good]) == [True]
        stats = fetch_stats((server.host, server.port), suite)
        assert stats["counters"]["crypto.rpc.served_requests"] == 1
        assert stats["counters"]["crypto.flushes"] == 1
    finally:
        server.stop()
    assert parse_addr("127.0.0.1:9999") == ("127.0.0.1", 9999)
    for bad_spec in ("nohost", ":123", "host:", "host:abc"):
        with pytest.raises(ValueError):
            parse_addr(bad_spec)


def _bls_fixture():
    from hbbft_tpu.crypto.bls import BLSSuite

    suite = BLSSuite()
    rng = random.Random(6)
    sks = SecretKeySet.random(1, rng, suite)
    pks = sks.public_keys()
    reqs = [
        VerifyRequest.sig_share(
            pks.public_key_share(i), b"doc", sks.secret_key_share(i).sign(b"doc")
        )
        for i in range(2)
    ]
    return suite, reqs


@pytest.mark.parametrize("suite_name", ["scalar", "bls"])
def test_stats_op_carries_decode_point_counters(suite_name):
    """``crypto.rpc.decode_points`` / ``decode_point_hits``: the frames'
    group elements and how many the suite's memo of validated bytes
    answered.  The scalar suite keeps no memo and counts none."""
    if suite_name == "scalar":
        suite, good, _ = _scalar_fixture()
        reqs, want = [good, good], (0, 0)
    else:
        from hbbft_tpu.crypto.bls import suite as bls_suite

        bls_suite._decode_validated.cache_clear()
        suite, reqs = _bls_fixture()
        # two calls of 2 keys + 2 shares: all new, then all seen
        want = (8, 4)
    server = _server(suite)
    try:
        addr = (server.host, server.port)
        cli = RpcServiceClient(addr, suite, BatchedBackend(suite))
        assert cli.verify_batch(reqs) == [True, True]
        assert cli.verify_batch(reqs) == [True, True]
        assert cli.metrics.counters.get("crypto.rpc.fallbacks", 0) == 0
        counters = fetch_stats(addr, suite)["counters"]
        assert (
            counters["crypto.rpc.decode_points"],
            counters["crypto.rpc.decode_point_hits"],
        ) == want
        assert counters["crypto.rpc.served_requests"] == 4
        assert server.metrics.timers["crypto.rpc.decode"].count == 3
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# framing fuzz: garbage must kill connections, never the plane
# ---------------------------------------------------------------------------


def _dial_raw(server) -> socket.socket:
    s = socket.create_connection((server.host, server.port), timeout=5)
    s.settimeout(5)
    return s


def _poisoned(sock: socket.socket) -> bool:
    """True when the server dropped the connection (EOF / RST)."""
    try:
        return sock.recv(4096) == b""
    except OSError:
        return True


def _dial_past_hello(server, suite) -> socket.socket:
    """A raw connection on which the HELLO exchange is done."""
    s = _dial_raw(server)
    s.sendall(
        encode_frame(
            KIND_CRYPTO_HELLO, serde.dumps((1, suite.name)), kinds=CRYPTO_KINDS
        )
    )
    dec = FrameDecoder(kinds=CRYPTO_KINDS)
    while dec.next_frame() is None:
        dec.feed(s.recv(4096))
    return s


def _await_bad_frames(server, n: int) -> int:
    deadline = time.monotonic() + 5
    while (
        server.metrics.counters.get("crypto.rpc.bad_frames", 0) < n
        and time.monotonic() < deadline
    ):
        time.sleep(0.01)
    return server.metrics.counters["crypto.rpc.bad_frames"]


def test_server_survives_corrupt_frames():
    """Each corruption mode kills ITS connection; the listener and the
    service live on, and a well-behaved client still verifies."""
    suite, good, _ = _scalar_fixture()
    server = _server(suite)
    try:
        attacks = []

        # raw garbage (fails CRC / length slicing)
        s = _dial_raw(server)
        s.sendall(b"\xff" * 64)
        attacks.append(s)
        # a consensus-plane frame on the crypto port (disjoint kind set)
        s = _dial_raw(server)
        s.sendall(encode_frame(KIND_MSG, b"x" * 10))
        attacks.append(s)
        # oversized declared length (rejected from the prefix alone)
        s = _dial_raw(server)
        s.sendall((1 << 30).to_bytes(4, "big") + b"\x00" * 16)
        attacks.append(s)
        # valid HELLO then a REQ whose payload is not serde
        s = _dial_past_hello(server, suite)
        s.sendall(
            encode_frame(KIND_CRYPTO_REQ, b"\x99not-serde",
                         kinds=CRYPTO_KINDS)
        )
        attacks.append(s)
        # wrong-suite HELLO
        s = _dial_raw(server)
        s.sendall(
            encode_frame(
                KIND_CRYPTO_HELLO, serde.dumps((1, "bls12-381")),
                kinds=CRYPTO_KINDS,
            )
        )
        attacks.append(s)
        # truncated frame then close (half a header)
        s = _dial_raw(server)
        s.sendall(b"\x00\x00")
        s.close()

        for s in attacks:
            assert _poisoned(s)
            s.close()
        assert _await_bad_frames(server, 4) >= 4

        cli = RpcServiceClient(
            (server.host, server.port), suite, BatchedBackend(suite)
        )
        assert cli.verify_batch([good]) == [True]
        assert cli.metrics.counters.get("crypto.rpc.fallbacks", 0) == 0
    finally:
        server.stop()


def test_corrupt_point_drops_connection_beside_cached_neighbours():
    """A frame with one bad point is refused whole, every time, although
    the memo holds its valid neighbours (and would answer them): the
    connection goes, ``crypto.rpc.bad_frames`` counts it, the memo does
    not grow, and the plane serves on."""
    from hbbft_tpu.crypto.bls import suite as bls_suite

    bls_suite._decode_validated.cache_clear()
    suite, reqs = _bls_fixture()
    server = _server(suite)
    try:
        addr = (server.host, server.port)
        cli = RpcServiceClient(addr, suite, BatchedBackend(suite))
        assert cli.verify_batch(reqs) == [True, True]
        size = bls_suite._decode_validated.cache_info().currsize
        assert size == 4
        body = bytearray(serde.dumps((7, "verify", tuple(reqs))))
        share = reqs[1].payload[2].g2.to_bytes()
        body[body.index(share) + len(share) - 1] ^= 1  # off the curve
        for attempt in (1, 2):
            s = _dial_past_hello(server, suite)
            s.sendall(
                encode_frame(KIND_CRYPTO_REQ, bytes(body), kinds=CRYPTO_KINDS)
            )
            assert _poisoned(s)
            s.close()
            assert _await_bad_frames(server, attempt) == attempt
            assert bls_suite._decode_validated.cache_info().currsize == size
        counters = fetch_stats(addr, suite)["counters"]
        # the refused frames' points are not counted: no decode finished
        assert counters["crypto.rpc.decode_points"] == 4
        assert cli.verify_batch(reqs) == [True, True]
        assert cli.metrics.counters.get("crypto.rpc.fallbacks", 0) == 0
    finally:
        server.stop()


class _EvilService:
    """A fake service that handshakes correctly, then answers every REQ
    with attacker-chosen bytes — the client-side fuzz half."""

    def __init__(self, suite, responses):
        self.suite = suite
        self.responses = list(responses)
        self._listener = socket.socket()
        self._listener.bind(("127.0.0.1", 0))
        self._listener.listen(8)
        self.addr = self._listener.getsockname()[:2]
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while self.responses:
            try:
                sock, _ = self._listener.accept()
            except OSError:
                return
            try:
                sock.settimeout(5)
                dec = FrameDecoder(kinds=CRYPTO_KINDS)
                while True:
                    f = dec.next_frame()
                    if f is not None:
                        kind, payload = f
                        if kind == KIND_CRYPTO_HELLO:
                            sock.sendall(
                                encode_frame(
                                    KIND_CRYPTO_HELLO,
                                    serde.dumps((1, self.suite.name)),
                                    kinds=CRYPTO_KINDS,
                                )
                            )
                        else:
                            sock.sendall(self.responses.pop(0))
                            break
                        continue
                    data = sock.recv(1 << 16)
                    if not data:
                        break
                    dec.feed(data)
            except (OSError, FrameError):
                pass
            finally:
                sock.close()

    def close(self):
        self._listener.close()


def test_client_falls_back_on_malformed_responses():
    """Garbage, wrong-plane, wrong-req-id, and short responses each
    make the client re-verify locally (correct verdicts, counted
    fallback) instead of wedging the flush — and a later good service
    gets re-dialed."""
    suite, good, bad = _scalar_fixture()
    evil_responses = [
        b"\xff" * 32,                                     # not a frame
        encode_frame(KIND_MSG, b"zzz"),                   # wrong plane
        encode_frame(                                     # wrong req id
            0x23, serde.dumps((999, "verify", True, b"\x01", 1, 1)),
            kinds=CRYPTO_KINDS,
        ),
        encode_frame(                                     # short tuple
            0x23, serde.dumps((1, "verify")), kinds=CRYPTO_KINDS
        ),
    ]
    evil = _EvilService(suite, evil_responses)
    try:
        cli = RpcServiceClient(
            evil.addr, suite, BatchedBackend(suite),
            timeout_s=5.0, reconnect_backoff_s=0.0,
        )
        for k in range(4):
            assert cli.verify_batch([good, bad]) == [True, False], k
        assert cli.metrics.counters["crypto.rpc.fallbacks"] == 4
    finally:
        evil.close()


def test_client_times_out_on_silent_service_and_recovers():
    """A service that accepts and never answers: the flush falls back
    after timeout_s (bounded, no wedge)."""
    suite, good, _ = _scalar_fixture()
    listener = socket.socket()
    listener.bind(("127.0.0.1", 0))
    listener.listen(8)
    addr = listener.getsockname()[:2]
    conns = []

    def accept_and_hold():
        try:
            while True:
                sock, _ = listener.accept()
                sock.settimeout(5)
                dec = FrameDecoder(kinds=CRYPTO_KINDS)
                while dec.next_frame() is None:
                    dec.feed(sock.recv(1 << 16))
                sock.sendall(
                    encode_frame(
                        KIND_CRYPTO_HELLO, serde.dumps((1, suite.name)),
                        kinds=CRYPTO_KINDS,
                    )
                )
                conns.append(sock)  # then go silent
        except OSError:
            return

    t = threading.Thread(target=accept_and_hold, daemon=True)
    t.start()
    try:
        cli = RpcServiceClient(
            addr, suite, BatchedBackend(suite), timeout_s=0.5
        )
        t0 = time.monotonic()
        assert cli.verify_batch([good]) == [True]
        assert 0.4 < time.monotonic() - t0 < 10.0
        assert cli.metrics.counters["crypto.rpc.fallbacks"] == 1
    finally:
        listener.close()
        for s in conns:
            s.close()


# ---------------------------------------------------------------------------
# batches_sha identity: rpc-service vs in-thread-service vs inline
# ---------------------------------------------------------------------------


def _run_cluster_arm(impl: str, crypto: str, *, seed: int = 0,
                     target: int = 4, rounds: int = 6, **cluster_kw):
    c = LocalCluster(4, seed=seed, node_impl=impl, crypto=crypto,
                     **cluster_kw)
    for k in range(rounds):
        for i in range(4):
            c.submit(i, Input.user(f"tx-{k}-{i}"))
    c.start()
    try:
        ok = c.wait(
            lambda cl: all(len(cl.batches(i)) >= target for i in range(4)),
            EPOCH_TIMEOUT_S,
        )
        assert ok, {i: len(c.batches(i)) for i in range(4)}
        m = c.merged_metrics(fresh=True)
        assert m.counters.get("cluster.handler_errors", 0) == 0
        keys = {i: batch_keys(c, i, upto=target) for i in range(4)}
        sha = batches_sha(c, 0, upto=target)
        return keys, sha, dict(m.counters)
    finally:
        c.stop()


def test_rpc_arm_output_identical_three_crypto_arms():
    """THE round-18 acceptance pin: inline, in-thread service, and
    rpc-service arms commit identical batches at N=4 seed 0 — python
    impl for all three crypto arms, native for the RPC arm.  Same
    majority-retry stance as the round-13 pin (live-socket epoch
    composition is scheduling-sensitive; a real verdict bug diverges
    deterministically and no retry masks it)."""
    _lib_or_skip()
    arms = [
        ("python", "inline"),
        ("python", "service"),
        ("python", "service-proc"),
        ("native", "service-proc"),
    ]
    runs = {arm: _run_cluster_arm(*arm) for arm in arms}
    for _retry in range(2):
        by_sha: dict = {}
        for arm, (_, sha, _) in runs.items():
            by_sha.setdefault(sha, []).append(arm)
        if len(by_sha) == 1:
            break
        majority = max(by_sha.values(), key=len)
        for sha, arm_list in by_sha.items():
            if arm_list is majority:
                continue
            for arm in arm_list:
                runs[arm] = _run_cluster_arm(*arm)
    shas = {arm: sha for arm, (_, sha, _) in runs.items()}
    assert len(set(shas.values())) == 1, shas
    ref = runs[("python", "inline")][0]
    for arm, (keys, _, _) in runs.items():
        assert keys == ref, f"batch divergence in arm {arm}"
    for arm in (("python", "service-proc"), ("native", "service-proc")):
        counters = runs[arm][2]
        assert counters.get("crypto.rpc.calls", 0) > 0, (arm, counters)
        assert counters.get("crypto.rpc.fallbacks", 0) == 0, (arm, counters)


def test_fault_multiset_parity_through_rpc():
    """The deterministic attribution pin at the RPC boundary: a seeded
    TamperingAdversary sim commits the same batches AND the same fault
    logs (order included) whether shares verify in scalar C or through
    a service PROCESS — serialization changes where verdicts compute,
    never what gets attributed."""
    from hbbft_tpu import native_engine
    from hbbft_tpu.net.adversary import TamperingAdversary

    _lib_or_skip()
    suite = ScalarSuite()

    def drive(**kw):
        nat = native_engine.NativeQhbNet(
            7, seed=9, batch_size=8, num_faulty=2, session_id=b"qhb-test",
            adversary=TamperingAdversary(tamper_p=0.5), **kw,
        )
        for nid in sorted(nat.correct_ids) + sorted(nat.faulty_ids):
            nat.send_input(nid, Input.user(f"x{nid}"))
        nat.run_until(
            lambda e: all(
                len(e.nodes[i].outputs) >= 1 for i in e.correct_ids
            ),
            chunk=1,
        )
        out = (
            {
                i: [
                    (b.era, b.epoch, b.contributions)
                    for b in nat.nodes[i].outputs
                ]
                for i in nat.correct_ids
            },
            {i: nat.faults(i) for i in range(7)},
        )
        nat.close()
        return out

    with ServiceProcess(suite="scalar", backend="batched") as svc:
        base = drive()
        cli = RpcServiceClient(svc.addr, suite, BatchedBackend(suite))
        via_rpc = drive(
            suite=suite, external_crypto=True, flush_every=1, backend=cli,
        )
        assert base == via_rpc
        share_faults = [
            (subj, kind)
            for faults in base[1].values()
            for subj, kind in faults
            if "invalid-share" in kind
        ]
        assert share_faults, "tampering never produced a share fault"
        assert cli.metrics.counters["crypto.rpc.calls"] > 0
        assert cli.metrics.counters.get("crypto.rpc.fallbacks", 0) == 0


# ---------------------------------------------------------------------------
# SIGKILL-mid-flush drill + re-attach (both impls)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["python", "native"])
def test_service_process_sigkill_fallback_and_reattach(impl):
    """The round-13 service-death drill at the process boundary: a REAL
    SIGKILL mid-run flips every client to its local fallback (commits
    continue, no handler errors, no spurious fault attributions), and
    a restarted service on the old port gets re-attached."""
    _impl_or_skip(impl)
    with LocalCluster(
        4, seed=3, node_impl=impl, crypto="service-proc",
        service_kwargs=dict(timeout_s=2.0),
    ) as c:
        c.drive_to([0, 1, 2, 3], 2, timeout_s=EPOCH_TIMEOUT_S)
        pre = dict(c.merged_metrics(fresh=True).counters)
        assert pre.get("crypto.rpc.calls", 0) > 0  # the service WAS serving
        c.crypto_service.kill()
        c.drive_to([0, 1, 2, 3], 4, timeout_s=EPOCH_TIMEOUT_S, tag="post")
        m = c.merged_metrics(fresh=True)
        assert m.counters.get("cluster.handler_errors", 0) == 0
        assert m.counters.get("crypto.rpc.fallbacks", 0) > 0
        # no lost/dup attributions: an honest-only cluster logs NO
        # protocol faults through the flip (a dropped or doubled
        # verdict would surface as one)
        for i in range(4):
            assert not [e for e in fault_entries(c.nodes[i])], i
        want = batch_keys(c, 0, upto=4)
        for i in (1, 2, 3):
            assert batch_keys(c, i, upto=4) == want

        c.crypto_service.restart()
        # scalar epochs commit in well under the client dial backoff
        # (0.5 s), so keep driving until a flush lands PAST the backoff
        # window and re-dials the reborn service
        target, deadline = 6, time.monotonic() + 30
        while True:
            c.drive_to(
                [0, 1, 2, 3], target, timeout_s=EPOCH_TIMEOUT_S,
                tag=f"reborn{target}",
            )
            m = c.merged_metrics(fresh=True)
            if m.counters.get("crypto.rpc.reconnects", 0) > 0:
                break
            assert time.monotonic() < deadline, dict(m.counters)
            target += 1
            time.sleep(0.3)
        assert m.counters.get("cluster.handler_errors", 0) == 0
        want = batch_keys(c, 0, upto=target)
        for i in (1, 2, 3):
            assert batch_keys(c, i, upto=target) == want


# ---------------------------------------------------------------------------
# process-per-node runtime: one service process serving N node processes
# ---------------------------------------------------------------------------


def _presubmit_arm(crypto: str):
    """One N=4 presubmit run of three epochs.  Returns the workers'
    summaries, the service's counters (None inline) and the committed
    window as ``[{proposer: contribution digest}]``, after the claims that
    hold in every run: all four processes committed one stream, and every
    epoch's subset holds at least n - f = 3 proposers."""
    with ProcCluster(
        n=4, seed=0, impl="native", epochs=3, drive="presubmit",
        timeout_s=90.0, crypto=crypto,
    ) as pc:
        sums = pc.join(timeout_s=120.0)
        assert all(s is not None for s in sums.values()), sums
        shas = pc.shas()
        assert len(set(shas.values())) == 1, shas
        window = sums[0]["epoch_contrib_shas"]
        assert all(s["epoch_contrib_shas"] == window for s in sums.values()), sums
        assert len(window) == 3 and all(len(e) >= 3 for e in window), window
        stats = (
            pc.crypto_service.stats()["counters"]
            if crypto == "service-proc" else None
        )
        return sums, stats, window


def test_proc_cluster_service_arm_identity_and_amortization():
    """ProcCluster's service arm commits what its inline arm commits,
    every worker's flushes rode the ONE service process, and the
    service's flush counters show cross-node merging.

    Across two runs the digest of the whole stream is the network's
    timing (a third of the runs alone, one epoch's subset misses one
    proposer's broadcast, and that proposer then draws its later
    proposals from a queue that still holds the missed ones), so the
    arms are compared on what no schedule moves: a proposer's
    contribution is a function of its seed and its own queue, hence the
    same bytes in both arms in every epoch up to the first whose subset
    lacks it in either arm.  Each arm's epoch 0 holds three proposers of
    four or more, so it always compares two or more."""
    _lib_or_skip()
    sums, stats, served = _presubmit_arm("service-proc")
    for i, s in sums.items():
        rpc = s.get("crypto_rpc")
        assert rpc and rpc["calls"] > 0, (i, s)
        assert rpc["fallbacks"] == 0, (i, s)
        # every flush response carries the merged size; with 4
        # clients the merged total can only exceed this node's own
        assert rpc["merged_requests"] >= rpc["requests"], (i, s)
    assert stats["crypto.flushes"] > 0
    assert stats["crypto.requests"] > stats["crypto.flushes"], stats

    _, _, inline = _presubmit_arm("inline")
    compared = 0
    for proposer in map(str, range(4)):
        for a, b in zip(served, inline):
            if proposer not in a or proposer not in b:
                break
            assert a[proposer] == b[proposer], (proposer, served, inline)
            compared += 1
    assert compared >= 2, (served, inline)


def test_proc_cluster_service_kill_drill():
    """kill_service mid-run: worker processes keep committing via their
    local fallbacks; summaries record the fallback flip."""
    _lib_or_skip()
    with ProcCluster(
        n=4, seed=1, impl="native", epochs=0, drive="self",
        timeout_s=90.0, crypto="service-proc",
        service_kwargs=dict(timeout_s=2.0),
    ) as pc:
        assert pc.wait(
            lambda c: all(c.batch_count(i) >= 2 for i in range(4)),
            EPOCH_TIMEOUT_S,
        ), {i: pc.batch_count(i) for i in range(4)}
        pc.kill_service()
        base = {i: pc.batch_count(i) for i in range(4)}
        assert pc.wait(
            lambda c: all(
                c.batch_count(i) >= base[i] + 2 for i in range(4)
            ),
            EPOCH_TIMEOUT_S,
        ), ({i: pc.batch_count(i) for i in range(4)}, base)
        pc.stop()
        sums = pc.summaries()
        for i, s in sums.items():
            assert s is not None, (i, sums)
            rpc = s.get("crypto_rpc")
            assert rpc and rpc["calls"] > 0, (i, s)
            assert rpc["fallbacks"] > 0, (i, s)


# ---------------------------------------------------------------------------
# observability: spans on the cryptoplane track, paired by id
# ---------------------------------------------------------------------------


def test_flush_spans_on_cryptoplane_track_pair_by_id():
    """RPC flushes show up as crypto.flush.open/done pairs on the
    shared ``cryptoplane`` track, carry a span id (concurrent clients
    interleave), and the analyzer pairs them by that id."""
    from hbbft_tpu.obs.analyze import _flush_spans

    with LocalCluster(4, seed=0, crypto="service-proc") as c:
        c.drive_to([0, 1, 2, 3], 2, timeout_s=EPOCH_TIMEOUT_S)
        tracks = c.trace_events()
    evs = tracks.get("cryptoplane")
    assert evs, sorted(tracks)
    opens = [e for e in evs if e.name == "crypto.flush.open"]
    dones = [e for e in evs if e.name == "crypto.flush.done"]
    assert opens and dones, [e.name for e in evs[:8]]
    assert all(e.args.get("span") for e in opens + dones)
    assert all(e.args.get("backend") == "rpc" for e in opens)
    assert all(e.args.get("requests", 0) > 0 for e in opens)
    spans = _flush_spans(tracks)
    assert spans, "analyzer paired no flush spans"
    assert all(t1 >= t0 for t0, t1 in spans)
    # one span per completed open/done pair, id-matched
    done_ids = {e.args["span"] for e in dones}
    assert len(spans) == sum(
        1 for e in opens if e.args["span"] in done_ids
    )


# ---------------------------------------------------------------------------
# construction validation pins
# ---------------------------------------------------------------------------


def test_cluster_construction_validation():
    with pytest.raises(ValueError, match="unknown crypto arm"):
        LocalCluster(4, crypto="service-rpc")
    with pytest.raises(ValueError, match="service_kwargs"):
        LocalCluster(
            4, crypto="service-proc",
            crypto_service=("127.0.0.1", 1), service_kwargs=dict(backend="x"),
        )
    with pytest.raises(ValueError, match="crypto must be"):
        ProcCluster(4, crypto="service")
    with pytest.raises(ValueError, match="crypto_service requires"):
        ProcCluster(4, crypto_service=("127.0.0.1", 1))


def test_cluster_attach_does_not_own_external_service():
    """A cluster attached to an externally-run service process must not
    stop it on teardown (the config9 TpuBackend-arm contract: one warm
    service outlives many runs)."""
    suite = ScalarSuite()
    with ServiceProcess(suite="scalar", backend="batched") as svc:
        with LocalCluster(
            4, seed=0, crypto="service-proc", crypto_service=svc.addr,
        ) as c:
            c.drive_to([0, 1, 2, 3], 2, timeout_s=EPOCH_TIMEOUT_S)
        assert svc.alive  # survived the cluster teardown
        stats = fetch_stats(svc.addr, suite)
        assert stats["counters"]["crypto.flushes"] > 0
