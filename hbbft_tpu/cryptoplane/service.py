"""Cluster crypto plane: a shared batched share-verification service.

The two halves of this repo meet here (ROADMAP item 2): cluster nodes
verify COIN/DECRYPT/sig shares either inline (scalar C for native
nodes, a per-node :class:`~hbbft_tpu.crypto.backend.CryptoBackend` for
Python nodes) or — with ``LocalCluster(crypto="service")`` — through
ONE shared :class:`CryptoPlaneService` that merges the share-check
requests of ALL nodes into single ``CryptoBackend.verify_batch``
flushes.  This is the "threshold cryptography as a distributed
service" architecture of Thetacrypt (PAPERS.md, arxiv 2502.03247):
with ``TpuBackend`` attached, the device flush kernel serves an actual
running network; with ``BatchedBackend`` (CI / no device) the RLC
pairing collapse still amortizes across nodes.

Correctness stance — the standing deferred-verification invariant:
verification verdicts are PURE functions of request content, so
merging requests across nodes, reordering flushes, or falling back to
a local backend can never change a verdict, only its timing.  The
service arm therefore commits byte-identical batches to the inline
arm, and per-sender fault attribution is preserved exactly
(``BatchedBackend`` bisects aggregate failures down to the offending
request — the RLC bisection contract, docs/INVARIANTS.md).  Pinned by
tests/test_cryptoplane.py (``batches_sha`` across arms, fault-multiset
parity under a corrupt-share adversary).

Failure stance: the service is an OPTIMIZATION plane, never a
liveness dependency.  Every :class:`ServiceClient` carries a local
fallback backend; a flush that times out, a killed service, or a
worker crash routes the same requests through the fallback (counted:
``crypto.fallbacks``) and the cluster keeps committing on the scalar
path — the lost-device story for ``TpuBackend``.

Threading: ``submit`` may be called from any number of node protocol
threads; the single worker thread owns the backend (JAX dispatch is
not assumed thread-safe).  Callers block on their job's event — a
node cannot progress past a share check anyway, and the window is the
measured "latency price of threshold cryptography" (arxiv 2407.12172)
that benchmarks/config9_crypto_plane.py prices against epochs/s.
"""

from __future__ import annotations

import threading
import time
import traceback
from typing import Any, List, Optional, Sequence

from hbbft_tpu.crypto.backend import CryptoBackend, VerifyRequest
from hbbft_tpu.traffic.latency import LatencyHistogram
from hbbft_tpu.utils.metrics import Metrics


class _Job:
    """One client's submitted batch: requests in, verdicts out."""

    __slots__ = ("reqs", "span", "results", "done", "cancelled",
                 "flush_requests", "flush_jobs")

    def __init__(self, reqs: List[VerifyRequest], span: str = "") -> None:
        self.reqs = reqs
        # The submitter's id for this batch (an RPC's "<conn>:<req_id>"):
        # the crypto.flush span lists the ids of the jobs it merged, so a
        # trace ties each RPC's spans to the flush that answered it.
        self.span = span
        self.results: Optional[List[bool]] = None  # None = failed/killed
        self.done = threading.Event()
        # Stamped by _flush: the size of the MERGED batch this job rode
        # in (requests / jobs across all clients) — the cross-node
        # amortization observable the RPC server reports back to its
        # clients (proc_service.py).
        self.flush_requests = 0
        self.flush_jobs = 0
        # Set by a client that timed out and re-verified locally: the
        # worker drops still-queued cancelled jobs instead of paying a
        # backend flush nobody is waiting for (best-effort — a job the
        # worker already collected still flushes).
        self.cancelled = False


class CryptoPlaneService:
    """The shared verification service: one worker, one backend.

    * ``window_s`` — how long the worker holds the first pending job
      open for more arrivals before flushing (the cross-node batching
      window; 0 flushes immediately).
    * ``max_batch`` — pending-request count that triggers an immediate
      flush regardless of the window.
    * ``trace`` — optional :class:`~hbbft_tpu.obs.trace.TraceBuffer`;
      every flush emits ``crypto.flush.open`` / ``crypto.flush.done``
      milestone events (requests/jobs/backend args) onto it, so the
      flight recorder's merged timeline shows device flushes next to
      the per-node epoch phases.

    Metrics (exported via :meth:`export_metrics` into
    ``LocalCluster.merged_metrics``): ``crypto.flushes`` /
    ``crypto.requests`` counters, ``crypto.flush`` span (the backend's
    ``verify_batch``; args ``flush`` = its sequence number, ``requests``,
    ``jobs``, ``spans`` = the merged jobs' ids, space-joined) and
    ``crypto.window`` span (first pending job seen to the flush's start),
    both :meth:`Metrics.span`: a timer each and, under an open profiler
    session, an event each on that session's clock;
    ``crypto.batch_size`` summary (log-bucket histogram),
    ``crypto.queue_depth`` gauge, ``crypto.fallbacks`` (client-side,
    counted here so the cluster sees one total), ``crypto.flush_errors``.
    Hand the backend the same ``metrics`` and its own spans and counters
    (``crypto.tpu.*``) are exported with these.
    """

    def __init__(
        self,
        backend: CryptoBackend,
        *,
        window_s: float = 0.002,
        max_batch: int = 512,
        metrics: Optional[Metrics] = None,
        trace: Any = None,
    ) -> None:
        self.backend = backend
        self.window_s = float(window_s)
        self.max_batch = int(max_batch)
        self.metrics = metrics if metrics is not None else Metrics()
        self.trace = trace
        self._cv = threading.Condition()
        self._jobs: List[_Job] = []
        self._pending_reqs = 0
        self._thread: Optional[threading.Thread] = None
        self._stop = False
        self._killed = False
        self._flush_seq = 0  # worker thread only
        # batch-size distribution (requests per backend flush): the
        # log-bucket estimator bounds memory like the traffic plane's
        # latency clocks; re-published as the crypto.batch_size summary.
        self._batch_hist = LatencyHistogram(lo=1.0, hi=65536.0, growth=1.25)

    # -- lifecycle ------------------------------------------------------
    def start(self) -> "CryptoPlaneService":
        """Start the worker.  stop()/kill() are TERMINAL: a stopped
        service never restarts (clients fall back locally forever) —
        restartability would make LocalCluster.stop() racy against
        late in-flight submits."""
        with self._cv:
            if self._thread is None and not self._killed and not self._stop:
                self._start_locked()
        return self

    def _start_locked(self) -> None:
        self._thread = threading.Thread(
            target=self._run, name="cryptoplane", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        """Graceful shutdown: drains nothing — outstanding jobs fail
        over to their clients' fallbacks (same path as kill)."""
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout=10)
        self._fail_pending()
        self._thread = None

    def kill(self) -> None:
        """Simulated crash (the fallback drill): the service goes dead
        NOW and stays dead — outstanding and future submissions fail
        immediately, clients fall back to their local backend."""
        with self._cv:
            self._killed = True
            self._stop = True
            self._cv.notify_all()
        self._fail_pending()

    @property
    def alive(self) -> bool:
        return not self._killed and not self._stop

    def _fail_pending(self) -> None:
        with self._cv:
            jobs, self._jobs = self._jobs, []
            self._pending_reqs = 0
            # scrapes of the surviving cluster must not show a stale
            # nonzero queue on a dead service
            self.metrics.gauge("crypto.queue_depth", 0)
        for j in jobs:
            j.done.set()  # results stay None -> client falls back

    # -- submission (any thread) ---------------------------------------
    def submit(
        self, reqs: Sequence[VerifyRequest], span: str = ""
    ) -> Optional[_Job]:
        """Enqueue one batch; returns the job to wait on, or None when
        the service is dead (caller falls back immediately).  Lazily
        starts the worker so a cluster built before ``start()`` still
        gets service semantics.  ``span`` is the caller's id for the
        batch; the flush that carries it lists it."""
        job = _Job(list(reqs), span)
        with self._cv:
            if self._killed or self._stop:
                return None
            if self._thread is None:
                # Lazy start UNDER the lock: a submit racing stop()
                # must never resurrect a worker after shutdown.
                self._start_locked()
            self._jobs.append(job)
            self._pending_reqs += len(job.reqs)
            self.metrics.gauge("crypto.queue_depth", self._pending_reqs)
            self._cv.notify_all()
        return job

    # -- worker ---------------------------------------------------------
    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._jobs and not self._stop:
                    self._cv.wait(timeout=0.2)
                if self._stop:
                    return
                with self.metrics.span("crypto.window"):
                    # Hold the window open from the FIRST pending arrival:
                    # more nodes' flushes pile into the same device batch.
                    deadline = time.monotonic() + self.window_s
                    while (
                        not self._stop
                        and self._pending_reqs < self.max_batch
                        and (remain := deadline - time.monotonic()) > 0
                    ):
                        self._cv.wait(timeout=remain)
                    if self._stop:
                        return
                    # Timed-out clients already re-verified locally; drop
                    # their abandoned jobs rather than flushing for nobody.
                    jobs = [j for j in self._jobs if not j.cancelled]
                    self._jobs = []
                    self._pending_reqs = 0
                    self.metrics.gauge("crypto.queue_depth", 0)
            if jobs:
                self._flush(jobs)

    def _flush(self, jobs: List[_Job]) -> None:
        reqs = [r for j in jobs for r in j.reqs]
        backend = type(self.backend).__name__
        if self.trace is not None:
            self.trace.emit(
                "crypto.flush.open",
                requests=len(reqs), jobs=len(jobs), backend=backend,
            )
        ok = False
        self._flush_seq += 1
        try:
            with self.metrics.span(
                "crypto.flush", flush=self._flush_seq, requests=len(reqs),
                jobs=len(jobs), spans=" ".join(j.span for j in jobs if j.span),
            ):
                results = self.backend.verify_batch(reqs)
            if len(results) != len(reqs):  # a broken backend is a crash
                raise RuntimeError(
                    f"backend returned {len(results)} verdicts "
                    f"for {len(reqs)} requests"
                )
            pos = 0
            for j in jobs:
                j.results = [bool(v) for v in results[pos:pos + len(j.reqs)]]
                pos += len(j.reqs)
            ok = True
            self.metrics.count("crypto.flushes")
            self.metrics.count("crypto.requests", len(reqs))
            self._batch_hist.observe(float(len(reqs)))
            self._publish_batch_summary()
        except Exception:
            # One bad flush must not take the plane down: these jobs
            # fail over to their clients' fallbacks, the worker lives.
            # The traceback goes to stderr: the counter alone cannot say
            # whether it was a compile error, an OOM or a bad dtype.
            traceback.print_exc()
            self.metrics.count("crypto.flush_errors")
        finally:
            if self.trace is not None:
                self.trace.emit(
                    "crypto.flush.done",
                    requests=len(reqs), jobs=len(jobs), backend=backend,
                    ok=ok,
                )
            for j in jobs:
                j.flush_requests = len(reqs)
                j.flush_jobs = len(jobs)
                j.done.set()

    def _publish_batch_summary(self) -> None:
        h = self._batch_hist
        self.metrics.summary(
            "crypto.batch_size",
            {q: h.quantile(q) for q in (0.5, 0.9, 0.99)},
            h.count,
            h.total,
        )

    # -- clients --------------------------------------------------------
    def client(
        self,
        fallback: CryptoBackend,
        *,
        timeout_s: float = 30.0,
    ) -> "ServiceClient":
        return ServiceClient(self, fallback, timeout_s=timeout_s)

    def export_metrics(self, into: Metrics) -> None:
        into.merge(self.metrics)


class ServiceClient(CryptoBackend):
    """Per-node facade: a drop-in :class:`CryptoBackend` whose
    ``verify_batch`` routes through the shared service and falls back
    to ``fallback`` (a local CPU backend) when the service is dead,
    killed mid-wait, or slower than ``timeout_s``.  Verdicts are pure,
    so the two paths are interchangeable per request."""

    def __init__(
        self,
        service: CryptoPlaneService,
        fallback: CryptoBackend,
        *,
        timeout_s: float = 30.0,
    ) -> None:
        self.service = service
        self.fallback = fallback
        self.timeout_s = float(timeout_s)

    def verify_batch(self, reqs: Sequence[VerifyRequest]) -> List[bool]:
        reqs = list(reqs)
        if not reqs:
            return []
        job = self.service.submit(reqs)
        if job is not None:
            if job.done.wait(self.timeout_s):
                results = job.results
                if results is not None:
                    return results
            else:
                job.cancelled = True  # worker drops it if still queued
        m = self.service.metrics
        m.count("crypto.fallbacks")
        m.count("crypto.fallback_requests", len(reqs))
        return self.fallback.verify_batch(reqs)
