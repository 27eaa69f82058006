"""Config 6: N-node TCP cluster epoch throughput over localhost.

The first benchmark that pays real socket costs: serde encode/decode of
every protocol message, frame plumbing, kernel round-trips, the ACK
resume layer, and thread scheduling of 2N threads on this 1-core box —
against the VirtualNet configs, the delta IS the transport tax.

One JSON line per N (like config1..5):

    BENCH_TCP_NS="4,8,16" BENCH_TCP_EPOCHS=5 python \
        benchmarks/config6_tcp_cluster.py

Round 9 A/B: ``BENCH_TCP_IMPL=native`` runs one C++ engine per node
(LocalCluster ``node_impl`` — the message-boundary wire API) against
the default ``python`` protocol-thread oracle.  Both arms pre-submit a
deterministic workload before start, so a native arm at seed s commits
byte-identical batches to the Python arm at seed s and the JSON's
``batches_sha`` can be compared across arms directly (the docs/
TRANSPORT.md oracle-mode recipe).  ``BENCH_TCP_DRIVE=paced`` restores
the round-8 wall-clock-paced feeder (throughput-trajectory continuity;
cross-arm digests are NOT comparable in that mode — pacing races).

Env: BENCH_TCP_NS (comma list, default "4,8,16"), BENCH_TCP_EPOCHS
(target epochs per N, default 5), BENCH_TCP_DEADLINE_S per N (default
300), BENCH_TCP_IMPL (python|native|mixed, default python; "mixed"
alternates arms per node id — one flight-recorder trace then carries
tracks from BOTH impls), BENCH_TCP_DRIVE (presubmit|paced, default
presubmit), BENCH_TCP_SEED (default 0), BENCH_TCP_METRICS=1 to embed
the merged metrics snapshot.

Flight recorder (round 12): BENCH_TRACE=<dir> writes the merged Chrome
trace (one file per line, path echoed in the JSON) — load it in
Perfetto / chrome://tracing; BENCH_OBS_PORT=<port> serves /metrics,
/trace.json and /healthz live during the run (port echoed too; 0 picks
a free one).  Native arms always carry their engine.cyc.<type> cycle
splits in the JSON line.

Round 14 — process-per-node arm: ``BENCH_PROC=1`` (or
``BENCH_TCP_IMPL=native_proc``) runs one cluster_worker OS process per
node (:class:`~hbbft_tpu.transport.proc_cluster.ProcCluster`, ephemeral
port-0 ready-line handshake, presubmit drive) instead of 2N threads in
this interpreter — the N=104 scale runs go through this arm.
``BENCH_PROC=1 BENCH_TCP_IMPL=python`` selects Python-oracle workers
(``python_proc``); ``BENCH_PROC_OBS=1`` gives every worker its own
scrape endpoints.  The JSON
line gains ``workers``/``ready_s``/``sha_identical`` (asserted across
ALL worker summaries, not just node 0) and ``min_epoch_contribs`` (the
non-empty-epochs check); ``batches_sha`` stays directly comparable with
the thread arms at one seed.  BENCH_TRACE also works here: each worker
dumps its trace file at exit and the parent merges them on the shared
wall clock.  The vectored-egress A/B for any arm is
``HBBFT_TPU_SENDMSG=0`` (buffered round-9 path) vs unset (sendmsg
gather egress) on the same build; every line records the live setting.

Round 20 — message coalescing: every line records the live ``coalesce``
arm (``HBBFT_TPU_COALESCE``; see docs/TRANSPORT.md "Message
coalescing") plus ``msgs_sent`` and ``msgs_per_frame`` (the coalescing
ratio — 1.0 on the per-message arm).  ``BENCH_TCP_COALESCE_AB=1`` runs
BOTH arms back to back per N on one build (thread arms only), printing
one line each and asserting the two ``batches_sha`` digests are
identical in presubmit drive — the batching-never-changes-semantics
pin, benchmarked.

Round 16: every line carries the analyzer's ``critical_path`` summary
(per-epoch critical path to commit, straggler attribution, phase share
of wall, cross-node skew, BA rounds — docs/OBSERVABILITY.md "Critical
path & diagnosis") and ``trace_dropped`` (ring-overflow honesty).  The
proc arm derives its ``critical_path`` from the parent-side trace
merge, so it needs BENCH_TRACE set.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The cluster is jax-free (scalar suite, CPU protocol stack): the
# imports below must not drag jax in.

from hbbft_tpu.protocols.queueing_honey_badger import Input  # noqa: E402
from hbbft_tpu.transport import LocalCluster  # noqa: E402
from hbbft_tpu.transport.transport import (  # noqa: E402
    _coalesce_default,
    _sendmsg_default,
)
from hbbft_tpu.utils import serde  # noqa: E402


def preload_engine_serde() -> bool:
    """Load the engine lib (build if needed) so ``serde.loads`` takes
    the C token-scan fast path even with Python nodes — round 8 ran
    this bench engine-free, paying the recursive decoder on every
    frame.  Returns whether the native scan is actually live."""
    try:
        from hbbft_tpu import native_engine

        if native_engine.get_lib() is None:
            return False
    except Exception:
        return False
    return serde._native_scan(serde.dumps(0)) is not None


def _engine_build_fields(n: int) -> dict:
    """Engine-build self-description for the JSON lines (round 15):
    SIMD dispatch arm + NodeSet width, so A/B rows name their arms per
    the CLAUDE.md clock-drift rules.  Uses the width THIS n selects
    (native nodes — in-process or proc-mode workers, which run the same
    loader — pick the -DHBE_WORDS build via _words_for), not the
    default build.  Empty when no engine lib loads (pure-Python arms
    still decode via it when present).  Round 17 adds the epoch-arena
    recycle knob (mirrors the engine's hbe_create env read — workers
    inherit the environment, so this names the arm for proc mode too)."""
    try:
        from hbbft_tpu import native_engine

        lib = native_engine.get_lib(native_engine._words_for(n))
        if lib is None:
            return {}
        return {
            "simd": native_engine.simd_mode(lib),
            "hbe_words": int(lib.hbe_words()),
            "arena_recycle": os.environ.get("HBBFT_TPU_ARENA", "1") != "0",
        }
    except Exception:
        return {}


def _sha3_plane_fields(n: int) -> dict:
    """Post-run sha3-plane counters (round 17).  Library-global since
    process start, so only the in-process (thread-mode) arms stamp
    them — the proc-mode parent never hashes, its counters would read
    ~0 while the workers did the work.  One cluster per benchmark
    process keeps them per-run in practice."""
    try:
        from hbbft_tpu import native_engine

        lib = native_engine.get_lib(native_engine._words_for(n))
        if lib is None:
            return {}
        st = native_engine.sha3_plane_stats(lib)
        return {"sha3": st} if st else {}
    except Exception:
        return {}


def resolve_impl(impl: str, n: int):
    """"mixed" = alternate node arms (even ids python, odd native), so
    one cluster/trace carries both impls."""
    if impl == "mixed":
        return {i: "native" if i % 2 else "python" for i in range(n)}
    return impl


def obs_extras(rec: dict, cluster, name: str, m=None) -> None:
    """Shared round-12 benchmark plumbing: engine cycle splits on every
    line, BENCH_TRACE=<dir> Chrome-trace dump, BENCH_OBS_PORT scrape
    endpoints (started by the caller right after cluster.start()).
    Pass the caller's merged-metrics snapshot via ``m`` so the JSON
    line's fields all come from ONE instant (and the merge+ring walk
    runs once per line).

    Round 16: every line also carries ``critical_path`` (the analyzer's
    per-run summary — straggler histograms, phase share of wall, skew,
    BA rounds, crypto-plane flush totals) and ``trace_dropped`` (total
    ring-overflow count; nonzero means the trace-derived numbers on
    this line are silently partial), with the per-node split when any
    ring actually dropped."""
    if m is None:
        m = cluster.merged_metrics(fresh=True)
    cyc = {
        k.split(".", 2)[2]: v
        for k, v in sorted(m.counters.items())
        if k.startswith("engine.cyc.")
    }
    if cyc:
        rec["engine_cyc"] = cyc
    sm = m.summaries.get("epoch.latency")
    if sm is not None:
        rec["epoch_lat_p50_s"] = round(sm.quantiles.get(0.5, 0.0), 4)
        rec["epoch_lat_p99_s"] = round(sm.quantiles.get(0.99, 0.0), 4)
    from hbbft_tpu.obs.analyze import critical_path, summarize_critical_paths

    rec["critical_path"] = summarize_critical_paths(
        critical_path(cluster.trace_events())
    )
    rec["trace_dropped"] = int(m.gauges.get("trace.dropped", 0))
    if rec["trace_dropped"]:
        rec["trace_dropped_by_node"] = {
            k.split(".")[1]: int(v)
            for k, v in sorted(m.gauges.items())
            if k.startswith("trace.") and k.endswith(".dropped")
            and k != "trace.dropped"
        }
    trace_dir = os.environ.get("BENCH_TRACE")
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
        path = os.path.join(trace_dir, f"{name}.trace.json")
        rec["trace_file"] = cluster.write_trace(path)


def run_n_proc(
    n: int, epochs: int, deadline_s: float, seed: int, impl: str = "native"
) -> dict:
    """One process-per-node measurement (``native_proc`` /
    ``python_proc``): spawn the fleet, deliver the address map, let the
    workers run the presubmit workload to ``epochs`` commits, and
    aggregate their summaries."""
    from hbbft_tpu.transport.proc_cluster import ProcCluster

    trace_dir = os.environ.get("BENCH_TRACE")
    t0 = time.perf_counter()
    cluster = ProcCluster(
        n,
        seed=seed,
        batch_size=8,
        impl=impl,
        epochs=epochs,
        drive="presubmit",
        timeout_s=deadline_s,
        obs=os.environ.get("BENCH_PROC_OBS") == "1",
        trace_dir=(
            os.path.join(trace_dir, f"config6_n{n}_proc") if trace_dir else None
        ),
    )
    rec = {
        "config": "config6_tcp_cluster",
        "nodes": n,
        "suite": "scalar",
        "transport": "tcp-localhost",
        "node_impl": f"{impl}_proc",
        "drive": "presubmit",
        "seed": seed,
        "workers": n,
        "threads_per_node": 3,  # selector loop + engine sweep + driver
        "vectored": _sendmsg_default(),
        # workers inherit the environment, so the env default names
        # the proc arm too (HBBFT_TPU_COALESCE)
        "coalesce": _coalesce_default(),
        "target_epochs": epochs,
    }
    rec.update(_engine_build_fields(n))
    try:
        cluster.start()
        rec["ready_s"] = round(time.perf_counter() - t0, 2)
        t0 = time.perf_counter()
        sums = cluster.join(timeout_s=deadline_s + 60.0)
        wall = time.perf_counter() - t0
        live = [s for s in sums.values() if s is not None]
        shas = sorted({s["batches_sha"] for s in live})
        committed = min((s["batches"] for s in live), default=0)
        msgs = sum(s["msgs_handled"] for s in live)
        rec.update(
            {
                "epochs_committed": committed,
                "wall_s": round(wall, 2),
                "epochs_per_s": round(committed / wall, 3) if wall else None,
                "msgs_handled": msgs,
                "msgs_per_s": round(msgs / wall, 1) if wall else None,
                "batches_sha": shas[0] if len(shas) == 1 else None,
                "sha_identical": len(shas) == 1 and len(live) == n,
                "min_epoch_contribs": min(
                    (min(s["epoch_contribs"], default=0) for s in live),
                    default=0,
                ),
                "handler_errors": sum(s["handler_errors"] for s in live),
                "protocol_faults": sum(s["faults"] for s in live),
                # ring-overflow honesty (round 16): summed from the
                # worker summaries — nonzero means the workers' trace
                # dumps (and the critical_path below) are partial
                "trace_dropped": sum(
                    s.get("trace_dropped", 0) for s in live
                ),
                "complete": all(
                    s is not None and s["done"] for s in sums.values()
                ),
            }
        )
    finally:
        cluster.stop()
    if trace_dir:
        merged = cluster.merged_chrome_trace()
        path = os.path.join(trace_dir, f"config6_n{n}_native_proc.trace.json")
        with open(path, "w") as fh:
            json.dump(merged, fh)
        rec["trace_file"] = path
        # critical_path over the parent-side merge: the same analyzer
        # the thread arms run over live rings (tools/analyze.py reads
        # the dumped file identically).
        from hbbft_tpu.obs.analyze import (
            critical_path,
            summarize_critical_paths,
            tracks_from_chrome,
        )

        rec["critical_path"] = summarize_critical_paths(
            critical_path(tracks_from_chrome(merged))
        )
    return rec


def run_n(
    n: int,
    epochs: int,
    deadline_s: float,
    impl: str,
    drive: str,
    seed: int,
    coalesce: bool = None,
) -> dict:
    t0 = time.perf_counter()
    kwargs = {}
    if coalesce is not None:  # the BENCH_TCP_COALESCE_AB dual-arm driver
        kwargs["transport_kwargs"] = {"coalesce": coalesce}
    cluster = LocalCluster(
        n, seed=seed, batch_size=8, node_impl=resolve_impl(impl, n), **kwargs
    )
    setup_s = time.perf_counter() - t0
    rec = {
        "config": "config6_tcp_cluster",
        "nodes": n,
        "suite": "scalar",
        "transport": "tcp-localhost",
        "node_impl": impl,
        "drive": drive,
        "seed": seed,
        "serde_native": serde._native_scan(serde.dumps(0)) is not None,
        "threads_per_node": 2,
        "vectored": _sendmsg_default(),
        "coalesce": _coalesce_default() if coalesce is None else coalesce,
        "target_epochs": epochs,
        "setup_s": round(setup_s, 3),
    }
    rec.update(_engine_build_fields(n))
    if drive == "presubmit":
        # Deterministic workload BEFORE start: every node sees the
        # identical txn queue in every arm, so the first `epochs`
        # batches are byte-identical across node_impls at one seed.
        for k in range(epochs + 4):
            for i in range(n):
                cluster.submit(i, Input.user(f"b-{k}-{i}"))
    t0 = time.perf_counter()
    try:
        cluster.start()
        obs_port = os.environ.get("BENCH_OBS_PORT")
        if obs_port is not None:
            rec["obs_port"] = cluster.serve_obs(port=int(obs_port)).port
        try:
            if drive == "presubmit":
                ok = cluster.wait(
                    lambda c: all(
                        len(c.batches(i)) >= epochs for i in range(n)
                    ),
                    deadline_s,
                )
                if not ok:
                    raise TimeoutError
            else:
                cluster.drive_to(range(n), epochs, timeout_s=deadline_s)
        except TimeoutError:
            pass  # report whatever committed within the deadline
        wall = time.perf_counter() - t0
        committed = min(len(cluster.batches(i)) for i in range(n))
        digest = hashlib.sha256()
        for b in cluster.batches(0)[:epochs]:
            digest.update(serde.dumps((b.era, b.epoch, b.contributions)))
        m = cluster.merged_metrics(fresh=True)
        frames = sum(
            st["frames_out"]
            for node in cluster.nodes.values()
            for st in node.transport.stats().values()
        )
        msgs_sent = sum(
            st["msgs_out"]
            for node in cluster.nodes.values()
            for st in node.transport.stats().values()
        )
        wire_bytes = sum(
            st["bytes_out"]
            for node in cluster.nodes.values()
            for st in node.transport.stats().values()
        )
        rec.update(
            {
                "epochs_committed": committed,
                "wall_s": round(wall, 2),
                "epochs_per_s": round(committed / wall, 3) if wall else None,
                "msgs_handled": m.counters.get("cluster.msgs_handled", 0),
                "msgs_per_s": round(
                    m.counters.get("cluster.msgs_handled", 0) / wall, 1
                ),
                "frames_sent": frames,
                "msgs_sent": msgs_sent,
                # the coalescing ratio: protocol messages per wire
                # frame (1.0 = the per-message arm, > 1 = batching)
                "msgs_per_frame": (
                    round(msgs_sent / frames, 2) if frames else None
                ),
                "wire_mb": round(wire_bytes / 1e6, 2),
                "batches_sha": digest.hexdigest()[:16],
                "protocol_faults": m.counters.get("cluster.protocol_faults", 0),
                "handler_errors": m.counters.get("cluster.handler_errors", 0),
                "complete": committed >= epochs,
            }
        )
        if os.environ.get("BENCH_TCP_METRICS"):
            rec["metrics"] = m.to_json()
        obs_extras(rec, cluster, f"config6_n{n}_{impl}", m=m)
        # Arena high-water marks ride the merged metrics already
        # (engine.cyc.arena via the slot-15 counter sync); the sha3
        # plane is library-global, so stamp it post-run here (thread
        # arms only — see _sha3_plane_fields).
        rec.update(_sha3_plane_fields(n))
    finally:
        cluster.stop()
    return rec


def main() -> None:
    ns = [int(x) for x in os.environ.get("BENCH_TCP_NS", "4,8,16").split(",")]
    epochs = int(os.environ.get("BENCH_TCP_EPOCHS", "5"))
    deadline = float(os.environ.get("BENCH_TCP_DEADLINE_S", "300"))
    impl = os.environ.get("BENCH_TCP_IMPL", "python")
    drive = os.environ.get("BENCH_TCP_DRIVE", "presubmit")
    seed = int(os.environ.get("BENCH_TCP_SEED", "0"))
    proc = (
        os.environ.get("BENCH_PROC") == "1" or impl.endswith("_proc")
    )
    coalesce_ab = os.environ.get("BENCH_TCP_COALESCE_AB") == "1" and not proc
    preload_engine_serde()
    for n in ns:
        if proc:
            # BENCH_TCP_IMPL still selects the worker implementation in
            # the proc arm: python → python_proc, anything else (the
            # default, native, native_proc) → native_proc.
            worker_impl = "python" if impl.startswith("python") else "native"
            rec = run_n_proc(n, epochs, deadline, seed, impl=worker_impl)
            print(json.dumps(rec), flush=True)
        elif coalesce_ab:
            # Dual-arm mode (round 20): both coalescing arms back to
            # back on one build, one line each.  Presubmit drive makes
            # batches_sha cross-arm comparable — a digest mismatch
            # means the coalescing layer changed protocol semantics,
            # so it is a hard failure, not a footnote.
            arms = []
            for arm in (False, True):
                rec = run_n(n, epochs, deadline, impl, drive, seed,
                            coalesce=arm)
                arms.append(rec)
                print(json.dumps(rec), flush=True)
            if drive == "presubmit" and all(a["complete"] for a in arms):
                assert arms[0]["batches_sha"] == arms[1]["batches_sha"], (
                    "coalescing arms committed different batches: "
                    f"{arms[0]['batches_sha']} vs {arms[1]['batches_sha']}"
                )
        else:
            rec = run_n(n, epochs, deadline, impl, drive, seed)
            print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
