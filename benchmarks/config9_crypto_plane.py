"""Config 9: crypto plane A/B — inline scalar vs the shared service.

The first benchmark where the TPU crypto work can serve a LIVE
cluster: per (N, crypto arm) it runs the traffic plane's open-loop
client fleet over a TCP cluster and prices the share-verification
path — epochs/s plus the submit→commit txn p50/p99 — so the
decrypt-after-order latency cost of threshold cryptography (PAPERS.md
arxiv 2407.12172) is a measured column, not an estimate.

One JSON line per (N, arm, impl):

    BENCH_CP_NS="4,8" BENCH_CP_ARMS="scalar,service-cpu" \
        python benchmarks/config9_crypto_plane.py

Arms:

* ``scalar`` — ``crypto="inline"``: native nodes verify in scalar C,
  Python nodes on their per-node BatchedBackend.  The baseline.
* ``service-cpu`` — ``crypto="service"``: every node's COIN/DECRYPT
  share checks flow through ONE shared CryptoPlaneService over a
  BatchedBackend (RLC pairing collapse amortized across nodes).  Runs
  on this box with no XLA involvement.
* ``service-tpu`` — the same service over ``TpuBackend`` with the
  BLS12-381 suite (python node impl: the native wire grammar pins the
  scalar suite).  Gated behind ``BENCH_TPU=1``: needs the chip
  (or a long-suffering CPU XLA compile — see CLAUDE.md cold-start
  budgets) and is NOT part of the mandatory matrix.
* ``service-proc`` — ``crypto="service-proc"`` (round 18): the same
  shared plane as ``service-cpu`` but in its OWN PROCESS behind the
  socket RPC boundary, so the column prices serialization + RPC on
  top of the amortization.  Both impls.
* ``inline-bls`` — ``crypto="inline"`` with the BLS12-381 suite
  (python impl: the native wire grammar pins the scalar suite).  The
  round-18 acceptance BASELINE: every node pays its own pairings.
* ``service-proc-bls`` — the BLS suite with every node's share checks
  routed to ONE service process (python impl).  Worker backend is
  ``batched`` by default; ``BENCH_TPU=1`` switches it to ``tpu``
  (worker spawned with a compile-scale RPC timeout) — the live-TPU-amortization headline arm.

Drive modes (BENCH_CP_DRIVE): ``open`` (default; honest latency
percentiles) or ``presubmit`` (deterministic workload — the line
carries ``batches_sha``, comparable across arms/impls at one seed; do
not quote presubmit latency).  ``BENCH_CP_KILL=1`` arms the mid-run
service-kill drill on the ``service-proc*`` arms: once every node has
committed a batch the service process takes a SIGKILL, and the line's
``kill_drill`` block records the fallback flip (the scripted version
of the tests/test_cryptoplane_proc.py drill — quote it only when
``complete`` is true and ``fallbacks`` > 0).

Env: BENCH_CP_NS (default "4"), BENCH_CP_ARMS (default
"scalar,service-cpu"; the round-18 acceptance pair is
"inline-bls,service-proc-bls" at N>=16 presubmit),
BENCH_CP_IMPLS (python|native list, default
"python,native"), BENCH_CP_DRIVE (open|presubmit, default open),
BENCH_CP_DURATION_S (default 2.0), BENCH_CP_TXNS (presubmit workload,
default 32), BENCH_CP_CLIENTS_PER_NODE (default 2), BENCH_CP_TPS (per
client; default 80/N^2 — config7's capacity-scaled rate),
BENCH_CP_WINDOW_S (service batching window, default 0.002),
BENCH_CP_SEED (default 0), BENCH_CP_DEADLINE_S (default 120),
BENCH_CP_METRICS=1 to embed the merged metrics snapshot.  BENCH_TRACE
/ BENCH_OBS_PORT work as in config6/7.

Round 16: every line carries the analyzer's ``critical_path`` summary
— on the service arms its ``flush`` block folds the ``cryptoplane``
track's per-epoch flush latency into the same object (the
decrypt-after-order latency price, arxiv 2407.12172) — plus
``trace_dropped`` (ring-overflow honesty), via ``obs_extras``.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from hbbft_tpu.cryptoplane.proc_service import COLD_COMPILE_TIMEOUT_S  # noqa: E402
from hbbft_tpu.traffic import ClientFleet, TrafficDriver  # noqa: E402
from hbbft_tpu.transport import LocalCluster  # noqa: E402
from hbbft_tpu.utils import serde  # noqa: E402

from config6_tcp_cluster import obs_extras, preload_engine_serde  # noqa: E402


def build_cluster(n: int, arm: str, impl: str, seed: int, window_s: float):
    if arm == "scalar":
        return LocalCluster(n, seed=seed, node_impl=impl, crypto="inline")
    if arm == "service-cpu":
        return LocalCluster(
            n, seed=seed, node_impl=impl, crypto="service",
            service_kwargs=dict(window_s=window_s),
        )
    if arm == "service-tpu":
        # BLS suite + the TPU flush kernel behind the shared service;
        # python impl only (the native cluster wire grammar is pinned
        # to the scalar suite's share encoding).
        from hbbft_tpu.crypto.bls import BLSSuite
        from hbbft_tpu.crypto.tpu.backend import TpuBackend
        from hbbft_tpu.cryptoplane import CryptoPlaneService
        from hbbft_tpu.obs.trace import TraceBuffer

        suite = BLSSuite()
        service = CryptoPlaneService(
            TpuBackend(suite),
            window_s=window_s,
            trace=TraceBuffer("cryptoplane"),
        )
        return LocalCluster(
            n, seed=seed, node_impl="python", suite=suite,
            crypto="service", crypto_service=service,
            # compile-scale client timeout: a cold flush bucket is a
            # multi-minute XLA build — the 30 s default would silently
            # benchmark the CPU fallback under a service-tpu label
            service_kwargs=dict(timeout_s=3600.0),
        )
    if arm == "service-proc":
        return LocalCluster(
            n, seed=seed, node_impl=impl, crypto="service-proc",
            service_kwargs=dict(window_s=window_s),
        )
    if arm in ("inline-bls", "service-proc-bls"):
        from hbbft_tpu.crypto.bls import BLSSuite

        suite = BLSSuite()
        if arm == "inline-bls":
            return LocalCluster(
                n, seed=seed, node_impl="python", suite=suite,
                crypto="inline",
            )
        kw: dict = dict(window_s=window_s, backend="batched")
        if os.environ.get("BENCH_TPU") == "1":
            # compile-scale RPC timeout: a cold flush bucket is a
            # multi-minute XLA build, and the 30 s default would
            # silently benchmark the CPU fallback under a service label
            kw = dict(
                window_s=window_s, backend="tpu",
                timeout_s=COLD_COMPILE_TIMEOUT_S,
            )
        return LocalCluster(
            n, seed=seed, node_impl="python", suite=suite,
            crypto="service-proc", service_kwargs=kw,
        )
    raise ValueError(f"unknown arm {arm!r}")


def arm_kill_drill(cluster, kill_info: dict, deadline_s: float) -> None:
    """BENCH_CP_KILL=1: SIGKILL the service process once every node has
    committed a batch; the run keeps going on the clients' local
    fallbacks and the JSON line records the flip."""

    def _watch():
        deadline = time.monotonic() + deadline_s
        while time.monotonic() < deadline:
            svc = cluster.crypto_service
            if svc is None or not getattr(svc, "alive", False):
                return
            counts = [cluster.batch_count(i) for i in cluster.nodes]
            if counts and min(counts) >= 1:
                try:
                    kill_info["stats_at_kill"] = {
                        k: v
                        for k, v in svc.stats()["counters"].items()
                        if k.startswith("crypto.")
                    }
                except Exception:
                    pass
                svc.kill()
                kill_info["killed"] = True
                kill_info["killed_at_epoch"] = min(counts)
                return
            time.sleep(0.05)

    threading.Thread(target=_watch, daemon=True).start()


def run_one(
    n: int, arm: str, impl: str, *, drive: str, duration_s: float,
    txns: int, clients_per_node: int, tps: float, window_s: float,
    seed: int, deadline_s: float,
) -> dict:
    fleet = ClientFleet(clients_per_node * n, tps, seed=seed)
    rec = {
        "config": "config9_crypto_plane",
        "nodes": n,
        "crypto_arm": arm,
        "node_impl": "python" if arm == "service-tpu" else impl,
        "drive": drive,
        "seed": seed,
        "clients": clients_per_node * n,
        "offered_tps": round(fleet.offered_tps, 3),
        "service_window_s": window_s if arm.startswith("service") else None,
        "serde_native": serde._native_scan(serde.dumps(0)) is not None,
    }
    cluster = build_cluster(n, arm, impl, seed, window_s)
    d = TrafficDriver(cluster, fleet)
    kill_info: dict = {}
    kill_armed = (
        os.environ.get("BENCH_CP_KILL") == "1"
        and arm.startswith("service-proc")
    )
    try:
        obs_port = os.environ.get("BENCH_OBS_PORT")
        if obs_port is not None:
            rec["obs_port"] = cluster.serve_obs(port=int(obs_port)).port
        if drive == "presubmit":
            ids = d.run_presubmit(txns)
            rec["presubmitted"] = len(ids)
            t0 = time.perf_counter()
            cluster.start()
            if kill_armed:
                arm_kill_drill(cluster, kill_info, deadline_s)
            drained = d.drain(deadline_s)
            wall = time.perf_counter() - t0
            res = {
                "arrived": d.arrived,
                "admitted": d.admitted,
                "committed": d.recorder.committed,
                "outstanding": d.outstanding(),
            }
            digest = hashlib.sha256()
            for b in cluster.batches(0):
                if not any(c for _, c in b.contributions):
                    continue  # trailing empty epochs differ across arms
                digest.update(serde.dumps((b.era, b.epoch, b.contributions)))
            rec["batches_sha"] = digest.hexdigest()[:16]
            rec["drained"] = drained
        else:
            cluster.start()
            if kill_armed:
                arm_kill_drill(cluster, kill_info, deadline_s)
            res = d.run_open_loop(duration_s, drain_timeout_s=deadline_s)
            wall = res["wall_s"]
        epochs = min(cluster.batch_count(i) for i in cluster.nodes)
        hist = d.recorder.hist
        m = cluster.merged_metrics(fresh=True)
        rec.update(
            {
                "wall_s": round(wall, 2),
                "epochs_committed": epochs,
                "epochs_per_s": round(epochs / wall, 5) if wall else None,
                "committed_txns": res["committed"],
                "txns_per_s": round(res["committed"] / wall, 1)
                if wall
                else None,
                "outstanding": res["outstanding"],
                "lat_p50_s": round(hist.quantile(0.5), 4),
                "lat_p99_s": round(hist.quantile(0.99), 4),
                "protocol_faults": m.counters.get("cluster.protocol_faults", 0),
                "handler_errors": m.counters.get("cluster.handler_errors", 0),
                "complete": res["outstanding"] == 0,
            }
        )
        # the crypto-plane columns: how the share checks were served
        rec["crypto"] = {
            "flushes": m.counters.get("crypto.flushes", 0),
            "requests": m.counters.get("crypto.requests", 0),
            "fallbacks": m.counters.get("crypto.fallbacks", 0),
        }
        sm = m.summaries.get("crypto.batch_size")
        if sm is not None:
            rec["crypto"]["batch_p50"] = round(sm.quantiles.get(0.5, 0.0), 1)
            rec["crypto"]["batch_p99"] = round(sm.quantiles.get(0.99, 0.0), 1)
        t = m.timers.get("crypto.flush")
        if t is not None:
            rec["crypto"]["flush_mean_s"] = round(t.mean_s, 5)
            rec["crypto"]["flush_max_s"] = round(t.max_s, 5)
        if arm.startswith("service-proc"):
            # RPC-boundary columns: client side from the merged node
            # metrics, service side from the worker's stats RPC (its
            # counters die with the process, so a killed service only
            # reports what the drill snapshotted)
            rec["crypto"]["rpc"] = {
                k: m.counters.get(f"crypto.rpc.{k}", 0)
                for k in (
                    "calls", "requests", "merged_requests", "merged_jobs",
                    "fallbacks", "fallback_requests", "connects",
                    "reconnects",
                )
            }
            rt = m.timers.get("crypto.rpc.round_trip")
            if rt is not None:
                rec["crypto"]["rpc"]["round_trip_mean_s"] = round(
                    rt.mean_s, 5
                )
            svc = cluster.crypto_service
            if svc is not None and getattr(svc, "alive", False):
                try:
                    rec["crypto"]["service"] = {
                        k: v
                        for k, v in svc.stats()["counters"].items()
                        if k.startswith("crypto.")
                    }
                except Exception:
                    pass
            if kill_armed:
                rec["kill_drill"] = {
                    "killed": kill_info.get("killed", False),
                    "killed_at_epoch": kill_info.get("killed_at_epoch"),
                    "epochs_after_kill": (
                        epochs - kill_info["killed_at_epoch"]
                        if "killed_at_epoch" in kill_info
                        else None
                    ),
                    "fallbacks": m.counters.get("crypto.rpc.fallbacks", 0),
                    "stats_at_kill": kill_info.get("stats_at_kill"),
                }
        if os.environ.get("BENCH_CP_METRICS"):
            rec["metrics"] = m.to_json()
        obs_extras(rec, cluster, f"config9_n{n}_{arm}_{impl}", m=m)
    finally:
        cluster.stop()
        # the service-tpu arm hands the cluster a pre-built service,
        # which the cluster does not own; stop it here (idempotent)
        if cluster.crypto_service is not None:
            cluster.crypto_service.stop()
    return rec


def main() -> None:
    ns = [int(x) for x in os.environ.get("BENCH_CP_NS", "4").split(",")]
    arms = os.environ.get("BENCH_CP_ARMS", "scalar,service-cpu").split(",")
    impls = os.environ.get("BENCH_CP_IMPLS", "python,native").split(",")
    drive = os.environ.get("BENCH_CP_DRIVE", "open")
    duration = float(os.environ.get("BENCH_CP_DURATION_S", "2.0"))
    txns = int(os.environ.get("BENCH_CP_TXNS", "32"))
    cpn = int(os.environ.get("BENCH_CP_CLIENTS_PER_NODE", "2"))
    tps_env = os.environ.get("BENCH_CP_TPS")
    window_s = float(os.environ.get("BENCH_CP_WINDOW_S", "0.002"))
    seed = int(os.environ.get("BENCH_CP_SEED", "0"))
    deadline = float(os.environ.get("BENCH_CP_DEADLINE_S", "120"))
    if "service-tpu" in arms and os.environ.get("BENCH_TPU") != "1":
        print(
            "# service-tpu arm skipped (set BENCH_TPU=1; needs the chip "
            "or a very warm .jax_cache)",
            file=sys.stderr,
        )
        arms = [a for a in arms if a != "service-tpu"]
    preload_engine_serde()
    for n in ns:
        tps = float(tps_env) if tps_env else 80.0 / (n * n)
        for arm in arms:
            if arm in ("service-tpu", "inline-bls", "service-proc-bls"):
                arm_impls = ["python"]  # BLS suite: python nodes only
            else:
                arm_impls = impls
            for impl in arm_impls:
                rec = run_one(
                    n, arm, impl, drive=drive, duration_s=duration,
                    txns=txns, clients_per_node=cpn, tps=tps,
                    window_s=window_s, seed=seed, deadline_s=deadline,
                )
                print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
