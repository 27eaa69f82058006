"""BASELINE config 3 on the FUSED stack: native C++ message loop + real
BLS12-381 crypto plane + deferred-verify flush (round-3 VERDICT item #1).

N=16 QueueingHoneyBadger, 256 transactions, real threshold crypto: the
engine runs the whole network's message loop natively; signing /
combining / serde gates call back per instance; verifications accumulate
in the engine pools and flush through the configured CryptoBackend when
the delivery queue runs dry (``flush_every=0`` — maximal amortization).

Prints one JSON line per epoch batch committed plus a summary line.

Env knobs: BENCH_NODES (16), BENCH_TXNS (256), BENCH_BATCH (256),
BENCH_BACKEND (batched|eager|tpu|hybrid), BENCH_FLUSH (0).
"""

from __future__ import annotations

import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from hbbft_tpu import native_engine
from hbbft_tpu.crypto.backend import BatchedBackend, EagerBackend
from hbbft_tpu.crypto.bls import BLSSuite
from hbbft_tpu.protocols.queueing_honey_badger import Input


def make_backend(name: str, suite):
    if name == "eager":
        return EagerBackend(suite)
    if name == "tpu":
        from hbbft_tpu.crypto.tpu import TpuBackend

        return TpuBackend(suite)
    if name == "hybrid":
        # The deployment-shaped choice: flushes below min_device_batch
        # ride the host (this config's mean flush is ~4 requests — a
        # device round-trip per tiny flush, plus a fresh ~10-min compile
        # per small shape bucket, would swamp the epoch); the big deduped
        # flushes ride the chip.  Failover scope: HybridBackend handles a
        # device that is absent at CONSTRUCTION or dies MID-RUN.
        from hbbft_tpu.crypto.tpu import HybridBackend

        return HybridBackend(suite, min_device_batch=64)
    return BatchedBackend(suite)


def main() -> None:
    n = int(os.environ.get("BENCH_NODES", "16"))
    n_txns = int(os.environ.get("BENCH_TXNS", "256"))
    batch_size = int(os.environ.get("BENCH_BATCH", "256"))
    backend_name = os.environ.get("BENCH_BACKEND", "batched")
    flush_every = int(os.environ.get("BENCH_FLUSH", "0"))
    suite = BLSSuite()

    t0 = time.perf_counter()
    nat = native_engine.NativeQhbNet(
        n,
        seed=0,
        batch_size=batch_size,
        num_faulty=0,  # all-correct: every node proposes (sim config-3 shape)
        session_id=b"config3-bls",
        suite=suite,
        backend=make_backend(backend_name, suite),
        flush_every=flush_every,
    )
    setup_s = time.perf_counter() - t0

    rng = random.Random(7)
    txns = [rng.randbytes(16) for _ in range(n_txns)]
    t0 = time.perf_counter()
    for i, txn in enumerate(txns):
        nat.send_input(i % n, Input.user(txn))
    want = set(txns)

    def committed(nid: int) -> set:
        return {
            t
            for b in nat.nodes[nid].outputs
            for _, c in b.contributions
            if isinstance(c, (list, tuple))
            for t in c
        }

    epoch_walls = []
    last = time.perf_counter()
    while not all(want <= committed(i) for i in nat.correct_ids):
        prev_batches = len(nat.nodes[0].outputs)
        nat.run_until(
            lambda e, w=prev_batches + 1: all(
                len(e.nodes[i].outputs) >= w for i in e.correct_ids
            ),
            chunk=5000,
        )
        now = time.perf_counter()
        epoch_walls.append(now - last)
        last = now
        b = nat.nodes[0].outputs[-1]
        print(
            json.dumps(
                {
                    "epoch": b.epoch,
                    "wall_s": round(epoch_walls[-1], 2),
                    "txs_committed": len(committed(0) & want),
                    "delivered": nat.delivered,
                }
            ),
            flush=True,
        )
    total = time.perf_counter() - t0

    st = nat.flush_stats
    print(
        json.dumps(
            {
                "config": "config3_native_bls",
                "nodes": n,
                "suite": "bls12-381",
                "backend": backend_name,
                "flush_every": flush_every,
                "txns": n_txns,
                "epochs": len(epoch_walls),
                "epoch_latency_s": round(total / max(1, len(epoch_walls)), 2),
                "total_wall_s": round(total, 2),
                "setup_s": round(setup_s, 2),
                "delivered": nat.delivered,
                "msgs_per_s": round(nat.delivered / total, 1),
                "verify_flushes": st["flushes"],
                "verify_requests": st["requests"],
                "backend_requests": st["backend_requests"],
                "max_flush_batch": st["max_batch"],
                "reqs_per_flush": round(
                    st["backend_requests"] / max(1, st["flushes"]), 2
                ),
            }
        )
    )
    nat.close()


if __name__ == "__main__":
    main()
