"""Warm the JAX compilation cache for the heavy crypto kernels.

The pairing / flush kernels are large XLA graphs: cold compiles cost
minutes each (round-3 audit on the virtual-CPU platform: pairing
product ~80 s, flush kernel ~7 min).  This script compiles the
canonical shape buckets ONCE, serially, with progress lines — run it
before a cold-cache `pytest tests/test_tpu_crypto.py` (or let any
prior full run populate `.jax_cache/`) and the heavy tier becomes
minutes-fast.

Usage (CPU tests):
    env JAX_PLATFORMS=cpu python benchmarks/warm_crypto_cache.py
The cache goes where JAX_COMPILATION_CACHE_DIR says (default .jax_cache/).

``WARM_SERVICE_LEGS=1`` warms the crypto-plane WORKER's cache instead:
the worker process owns the device, so this parent then never imports
jax and the in-process legs do not run (one process per chip).
"""

from __future__ import annotations

import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(msg: str) -> None:
    print(f"[warm {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def main() -> None:
    from hbbft_tpu.crypto.backend import VerifyRequest
    from hbbft_tpu.crypto.bls.suite import BLSSuite
    from hbbft_tpu.crypto.keys import SecretKeySet

    suite = BLSSuite()
    rng = random.Random(7)
    sks = SecretKeySet.random(1, rng, suite)
    pks = sks.public_keys()
    msg = b"warmup"

    # Warm each legs bucket the heavy tier touches (floor=2: buckets
    # 2/4/8 — the test-tier mixed batches land in nl=8, bisection
    # sub-batches in nl=4 and nl=2).  One distinct-leg batch per bucket.
    from hbbft_tpu.crypto.keys import SignatureShare

    def sig(i: int, m: bytes) -> VerifyRequest:
        return VerifyRequest.sig_share(
            pks.public_key_share(i % 2), m, sks.secret_key_share(i % 2).sign(m)
        )

    ct = pks.public_key().encrypt(b"warm-ct", rng)
    batches = {
        # nl=2: generator leg + one message-hash leg
        2: [sig(0, msg), sig(1, msg)],
        # nl=4 (3 legs): + a second distinct message
        4: [sig(0, msg), sig(1, b"warm-doc-2")],
        # nl=8 (5 legs): + ciphertext check + decryption share
        8: [
            sig(0, msg),
            sig(1, b"warm-doc-2"),
            VerifyRequest.ciphertext(ct),
            VerifyRequest.dec_share(
                pks.public_key_share(0),
                ct,
                sks.secret_key_share(0).decryption_share(ct),
            ),
        ],
    }
    if os.environ.get("WARM_SERVICE_LEGS"):
        _warm_service_process(suite, batches[8])
        log("done")
        return

    from hbbft_tpu.utils.jaxcache import enable_cache

    enable_cache()

    from hbbft_tpu.crypto.tpu.backend import TpuBackend

    backend = TpuBackend(suite)
    for nl, reqs in sorted(batches.items()):
        t0 = time.time()
        ok = backend.verify_batch(reqs)
        assert all(ok), (nl, ok)
        log(f"flush kernel legs-bucket nl={nl} warmed in {time.time() - t0:.0f}s")

    # Bisection fallback: a bad share forces the aggregate to split; the
    # sub-batches reuse the buckets warmed above.
    t0 = time.time()
    bad = VerifyRequest.sig_share(
        pks.public_key_share(0), msg, SignatureShare(suite.g2_generator(), suite)
    )
    reqs8 = batches[8]
    res = backend.verify_batch(reqs8 + [bad])
    assert res[:-1] == [True] * len(reqs8) and res[-1] is False
    log(f"bisection path exercised in {time.time() - t0:.0f}s")

    # Single-chunk PAIR buckets (1 + nl = 3/5/9 pairs), compiled
    # directly: after a failed cross-chunk combine, the per-chunk
    # recheck in TpuBackend.verify_batch invokes _pair_kernel at
    # exactly these counts — a cache warmed only through combined
    # production buckets (WARM_SHARES) would eat a multi-minute cold
    # XLA compile on the FAILURE path, the worst possible moment on
    # this platform (ADVICE round 5).  Identity pairs compile the same
    # (n_pairs,)-shaped kernel the recheck uses and their product is 1.
    from hbbft_tpu.crypto.tpu import backend as tbackend
    from hbbft_tpu.crypto.tpu import curve as dcurve

    for b in (3, 5, 9):
        t0 = time.time()
        lhs = dcurve.identity(dcurve.G1_OPS, (b,))
        rhs = dcurve.identity(dcurve.G2_OPS, (b,))
        assert bool(tbackend._pair_kernel(b)(lhs, rhs)), b
        log(f"single-chunk pair bucket {b} pairs warmed in {time.time() - t0:.0f}s")

    # Production-size buckets (deployment prewarm, round-4 VERDICT #9):
    # WARM_SHARES=2048,10240 compiles the firehose-scale scan buckets +
    # the cross-chunk pair bucket so first real traffic never eats the
    # ~10-min-per-bucket compile wave.  NOTE the pair-stage bucket is
    # keyed by TOTAL pair count (chunks x (1+legs), padded to a multiple
    # of 8), so WARM_SHARES must list the flush sizes the deployment
    # actually issues — warming 10240 does NOT cover a 4096 flush's
    # 2-chunk pair bucket.  Signing n shares host-side costs ~12 ms
    # each, so reuse a handful of signatures across rows.
    shares_env = os.environ.get("WARM_SHARES", "")
    if shares_env:
        shares8 = [sks.secret_key_share(k % 2).sign(msg) for k in range(8)]
        for n_shares in [int(s) for s in shares_env.split(",") if s]:
            reqs = [
                VerifyRequest.sig_share(
                    pks.public_key_share(i % 2), msg, shares8[i % 8]
                )
                for i in range(n_shares)
            ]
            t0 = time.time()
            ok = backend.verify_batch(reqs)
            assert all(ok), n_shares
            log(
                f"production bucket {n_shares} shares "
                f"(CHUNK={backend.CHUNK}) warmed in {time.time() - t0:.0f}s"
            )

    # Crypto-plane service bucket (round 13): a cluster's shared
    # CryptoPlaneService merges several nodes' sig/dec/ct checks into
    # one device flush — the mixed-kind legs land in the SAME nl=8
    # bucket warmed above, but route here through the service worker
    # (config9's service-tpu arm) so the end-to-end path is exercised
    # once while the cache is being built.
    from hbbft_tpu.crypto.backend import BatchedBackend
    from hbbft_tpu.cryptoplane import CryptoPlaneService

    svc = CryptoPlaneService(backend, window_s=0.05)
    # Distinct CPU fallback (the worker owns the TpuBackend — a timed-
    # out client must never re-enter it concurrently) and a compile-
    # scale timeout: this flush COLD is a multi-minute XLA build.
    client = svc.client(BatchedBackend(suite), timeout_s=3600.0)
    t0 = time.time()
    ok = client.verify_batch(batches[8])
    assert all(ok)
    assert svc.metrics.counters.get("crypto.flushes", 0) == 1, (
        svc.metrics.counters
    )
    svc.stop()
    log(f"cryptoplane service flush warmed in {time.time() - t0:.0f}s")

    log("done")


def _warm_service_process(suite, reqs) -> None:
    """Service-PROCESS arm (round 18): spawn the RPC worker with the
    TpuBackend and push the mixed-kind batch through the socket, so the
    WORKER's own compile-cache entries (config9's service-proc-bls
    BLS/TPU arm) get built now instead of on first cluster traffic.
    The worker inherits this process's environment — run this under the
    one the deployment will use."""
    from hbbft_tpu.crypto.backend import BatchedBackend
    from hbbft_tpu.cryptoplane.proc_service import (
        COLD_COMPILE_TIMEOUT_S,
        RpcServiceClient,
        ServiceProcess,
    )

    t0 = time.time()
    with ServiceProcess(
        suite="bls", backend="tpu", ready_timeout_s=600.0
    ) as proc:
        rpc = RpcServiceClient(
            proc.addr, suite, BatchedBackend(suite),
            timeout_s=COLD_COMPILE_TIMEOUT_S,
        )
        ok = rpc.verify_batch(reqs)
        assert all(ok)
        assert rpc.metrics.counters.get("crypto.rpc.fallbacks", 0) == 0, (
            rpc.metrics.counters
        )
        stats = proc.stats()["counters"]
        assert stats.get("crypto.flushes", 0) == 1, stats
        rpc.close()
    log(f"service-process (rpc) flush warmed in {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
