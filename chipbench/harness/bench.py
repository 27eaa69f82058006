"""One run of one cell: worker up, warm-up, measured window, check, one line.

The path the window drives is the product's: ``RpcServiceClient.verify_batch``
against one worker process (``--suite bls --backend tpu``) that owns the
chip.  Closed loop, one client, one flush in flight.  This process never
imports jax.

Set-up (``setup_s``: process start to the first timed flush) is the worker's
start, one warm-up flush of the cell's own traffic (the compile, or the
cache read), the traffic's probe flush (a round with one wrong share of each
kind, so that a path that accepts everything shows in every cell) and,
overlapped with the warm-up in helper processes, the building of the seeded
traffic pool and the plain reference's verdicts on it (:func:`judged_positions`).

The pieces (:class:`Session`, :class:`Prepared`, :func:`judge`) are apart so
that a builder can drive many seeds through one worker.
"""

from __future__ import annotations

import concurrent.futures
import importlib
import json
import multiprocessing
import os
import random
import shutil
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from chipbench.harness import stats as hstats
from chipbench.harness import work
from chipbench.harness.reduce_trace import find_trace
from chipbench.harness.worker import DEFAULT_ENTRY, REPO_ROOT, Worker

#: Seconds a worker has to stop after a window (it takes 13-15 on the chip),
#: and after a run that did not end (a cut, no chip, a worker that died):
#: nothing it could still say is wanted, and a cut must end before the
#: killer's second signal.
WORKER_STOP_S = 30.0
WORKER_CUT_S = 2.0

#: Exit codes of a run that prints no result.
EXIT_USAGE = 2
EXIT_NO_CHIP = 3
EXIT_WORKER = 4


class NoResult(Exception):
    """The run cannot print a result; ``code`` is its exit code."""

    def __init__(self, code: int, why: str) -> None:
        super().__init__(why)
        self.code = code


class Cell:
    """One entry of ``workloads`` with its configuration and traffic files."""

    def __init__(self, bench: Dict[str, Any], name: str, root: str) -> None:
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
        self.bench = bench
        self.root = root
        self.cell = cells[name]
        configs = {c["name"]: c for c in bench["configs"]}
        with open(os.path.join(root, configs[self.cell["config"]]["file"])) as f:
            self.config = json.load(f)
        traffic_dir = os.path.join(root, bench["paths"][0], "traffic")
        with open(os.path.join(traffic_dir, self.cell["traffic"] + ".json")) as f:
            self.traffic = json.load(f)
        self.generator = importlib.import_module(
            "chipbench.generators." + self.traffic["generator"]
        )

    @property
    def name(self) -> str:
        return self.cell["name"]

    @property
    def requests_per_flush(self) -> int:
        return int(self.traffic["params"]["requests"])

    def metrics(self, group: str) -> List[Dict[str, Any]]:
        """The metrics of ``end_to_end`` or ``per_layer`` that this cell
        reports: those without a ``workloads`` key, and those that list it."""
        return [
            m for m in self.bench[group]
            if "workloads" not in m or self.name in m["workloads"]
        ]


#: Helper processes that build the pool and run the plain reference while
#: the worker warms up: plain Python, no jax, ended before the window.
HELPERS = max(2, min(6, (os.cpu_count() or 2) // 2))

#: Requests of a warm-up or pool flush that the plain reference judges where
#: the flush holds more, at positions drawn from ``(seed, index)``.  16 is the
#: largest flush of the 16-node cells, so they stay judged whole.  The rest
#: of a large flush is held to the verdict its construction expects, which a
#: generator knows for free; the reference is there to catch a generator
#: whose expectations are wrong, and it still judges every request the
#: construction expects to fail and the whole probe (:func:`judged_positions`).
JUDGED_PER_FLUSH = 16

#: Requests a helper judges in one task, so that a large flush is judged by
#: every helper and not by one.
JUDGE_SLICE = 32

WARM_UP = 0    # index of the warm-up flush; the window's are 1..pool_flushes
PROBE = -1     # index of the probe flush


def _build_one(args: Tuple[str, Dict, Dict, int, int, Any]):
    """Flush ``index`` of the run with ``seed``, from the generator."""
    generator, config, params, seed, index, keys = args
    mod = importlib.import_module("chipbench.generators." + generator)
    return mod.make_flush(config, params, seed, index, keys)


def _judge_slice(args: Tuple[List[str], List[Tuple[bytes, ...]]]):
    """The plain reference's verdict on each request of a slice, from its
    wire bytes, by the verifier of the request's own kind
    (``chipbench/kinds/<kind>.py``), and the seconds it took."""
    from chipbench import kinds
    from chipbench.reference.verify import Reference

    request_kinds, wires = args
    t = time.perf_counter()
    reference = Reference()
    verdicts = [
        kinds.load(kind).verify(reference, *wire)
        for kind, wire in zip(request_kinds, wires)
    ]
    return verdicts, time.perf_counter() - t


def judged_positions(seed: int, index: int, expected: Sequence[bool]) -> List[int]:
    """The positions of flush ``index`` that the plain reference judges:
    all of the probe and of a flush of up to ``JUDGED_PER_FLUSH``; else
    ``JUDGED_PER_FLUSH`` drawn from a stream of ``(seed, index)`` alone (not
    the construction's, so parent and change judge the same positions) and
    every request whose expected verdict is false."""
    n = len(expected)
    if index == PROBE or n <= JUDGED_PER_FLUSH:
        return list(range(n))
    drawn = random.Random(f"chipbench judged {seed} {index}").sample(
        range(n), JUDGED_PER_FLUSH
    )
    return sorted(set(drawn).union(i for i, ok in enumerate(expected) if not ok))


class Prepared:
    """One seed's traffic and the plain reference's verdicts on it.

    Flush ``WARM_UP`` is the warm-up, ``PROBE`` the traffic's probe round
    (absent where the traffic file has no ``probe``), 1.. the window's pool.
    ``HELPERS`` processes build each flush from the seed while the worker
    warms up, and :meth:`get` returns a flush as soon as it is built.  A
    thread hands each built flush's judged positions to the same helpers in
    slices of ``JUDGE_SLICE``; :meth:`finish` waits for every verdict and ends
    the helpers, so nothing of this runs inside the window."""

    def __init__(self, cell: Cell, seed: int, pool_flushes: Optional[int] = None) -> None:
        traffic = cell.traffic
        self.seed = seed
        self.pool_flushes = int(pool_flushes or traffic["pool_flushes"])
        self.keys = cell.generator.make_keys(cell.config, traffic["params"], seed)
        self.flushes: List[Any] = []
        self.verdicts: Dict[int, List[Optional[bool]]] = {}
        self.reference_s = 0.0
        self.judged_requests = 0
        self._judging: Dict[int, List[Tuple[List[int], concurrent.futures.Future]]] = {}
        self._feeder = threading.Thread(target=self._feed, name="chipbench-judge", daemon=True)
        self._executor = concurrent.futures.ProcessPoolExecutor(
            max_workers=HELPERS, mp_context=multiprocessing.get_context("spawn"),
        )
        order = [(WARM_UP, traffic["params"])]
        if traffic.get("probe"):
            order.append((PROBE, traffic["probe"]))
        order += [(i, traffic["params"]) for i in range(1, 1 + self.pool_flushes)]
        self._built: Dict[int, concurrent.futures.Future] = {}
        try:
            for index, params in order:
                self._built[index] = self._executor.submit(
                    _build_one,
                    (traffic["generator"], cell.config, params, seed, index, self.keys),
                )
            self._feeder.start()
        except BaseException:  # a cut (SIGTERM) among the submissions
            self.abandon()
            raise
        self.has_probe = PROBE in self._built

    def _feed(self) -> None:
        """Send each flush's judged requests to the helpers once it is built,
        in the order the flushes were asked for."""
        for index, built in self._built.items():
            try:
                flush = built.result()
            except Exception:  # abandoned, or a build failed: get() and finish() raise it
                return
            part_of = judged_positions(self.seed, index, flush.expected)
            slices = []
            for start in range(0, len(part_of), JUDGE_SLICE):
                part = part_of[start:start + JUDGE_SLICE]
                try:
                    judging = self._executor.submit(
                        _judge_slice,
                        ([flush.kinds[p] for p in part], [flush.wire[p] for p in part]),
                    )
                except RuntimeError:  # shut down: the run was abandoned
                    return
                slices.append((part, judging))
            self._judging[index] = slices

    def get(self, index: int):
        """Flush ``index``, once built; its verdicts may not exist yet."""
        return self._built[index].result()

    def reference(self, index: int) -> List[Optional[bool]]:
        """The plain reference's verdict on each request of flush ``index``,
        None where it was not judged; after :meth:`finish`."""
        return self.verdicts[index]

    def finish(self) -> None:
        """Wait for every flush and every verdict, then end the helpers."""
        for built in self._built.values():
            built.result()
        self.flushes = [self.get(i) for i in range(1 + self.pool_flushes)]
        self._feeder.join()
        for index, slices in self._judging.items():
            verdicts: List[Optional[bool]] = [None] * len(self.get(index).expected)
            for part, judging in slices:
                got, seconds = judging.result()
                for p, verdict in zip(part, got):
                    verdicts[p] = verdict
                self.reference_s += seconds
                self.judged_requests += len(part)
            self.verdicts[index] = verdicts
        self._executor.shutdown(wait=True)

    def abandon(self) -> None:
        """End the helpers at once, built, judged or not: a run that is cut
        does not wait for a task to end.  (``ProcessPoolExecutor`` has no
        public way to end its processes before Python 3.14.)"""
        helpers = list((self._executor._processes or {}).values())
        self._executor.shutdown(wait=False, cancel_futures=True)
        for p in helpers:
            p.terminate()
        self._executor.shutdown(wait=True)
        if self._feeder.is_alive():
            self._feeder.join(timeout=10)


class _NoFallback:
    """The client's local fallback, made to answer nothing: a flush that
    the chip path did not answer must show, not be verified on the host."""

    def verify_batch(self, reqs: Sequence[Any]) -> List[bool]:
        return [False] * len(reqs)


class Session:
    """One worker that owns the chip and one RPC client to it."""

    def __init__(
        self,
        cell: Cell,
        *,
        require_tpu: bool = True,
        worker_entry: str = DEFAULT_ENTRY,
        worker_entry_args: Sequence[str] = (),
        worker_overrides: Optional[Dict[str, Any]] = None,
    ) -> None:
        self.cell = cell
        self.require_tpu = require_tpu
        settings = dict(cell.config["worker"])
        settings.update(worker_overrides or {})
        self.worker = Worker(settings, entry=worker_entry, entry_args=worker_entry_args)
        self.ready: Dict[str, Any] = {}
        self.device: Dict[str, Any] = {}
        self.client: Any = None
        self.client_metrics: Any = None
        self.suite: Any = None
        self.worker_rc: Optional[int] = None
        self.trace_dir = os.path.join(cell.root, cell.bench["paths"][0], ".trace")

    def start(self) -> None:
        self.worker.start()

    def connect(self) -> None:
        """Wait for the worker's ready line, check its device, dial it."""
        # The program under test; a checkout without it cannot be measured.
        from hbbft_tpu.crypto.bls.suite import BLSSuite
        from hbbft_tpu.cryptoplane.proc_service import (
            COLD_COMPILE_TIMEOUT_S,
            RpcServiceClient,
        )
        from hbbft_tpu.utils.metrics import Metrics

        try:
            self.ready = self.worker.wait_ready()
        except RuntimeError as e:
            raise NoResult(
                EXIT_NO_CHIP if self.require_tpu else EXIT_WORKER, str(e)
            ) from None
        self.device = self.ready.get("device") or {}
        chips = int(self.cell.cell["chips"])
        if self.require_tpu and (
            self.device.get("platform") != "tpu"
            or int(self.device.get("count", 0)) < chips
        ):
            raise NoResult(
                EXIT_NO_CHIP,
                f"the worker holds {self.device or 'no jax device'}; the cell "
                f"asks for {chips} TPU chip(s)",
            )
        self.suite = BLSSuite()
        self.client_metrics = Metrics()
        self.client = RpcServiceClient(
            self.worker.addr, self.suite, _NoFallback(),
            timeout_s=COLD_COMPILE_TIMEOUT_S, metrics=self.client_metrics,
        )

    def stats(self) -> Dict[str, Any]:
        from hbbft_tpu.cryptoplane.proc_service import fetch_stats

        return fetch_stats(self.worker.addr, self.suite)

    def _fallback_requests(self) -> int:
        return int(
            self.client_metrics.counters.get("crypto.rpc.fallback_requests", 0)
        )

    def _cache_entries(self) -> Optional[List[str]]:
        path = self.ready.get("compile_cache_dir")
        if not path or not os.path.isdir(path):
            return None
        return sorted(os.listdir(path))

    def setup_flush(self, index: int, flush: Any) -> Dict[str, Any]:
        """One flush of the set-up (the warm-up, the probe): its answers and
        how long it took."""
        t = time.perf_counter()
        got = self.client.verify_batch(flush.requests)
        seconds = time.perf_counter() - t
        if not self.worker.alive:
            raise NoResult(EXIT_WORKER, f"the worker died during set-up flush {index}")
        return {"index": index, "answers": got, "seconds": seconds}

    def window(
        self,
        flushes: Sequence[Any],
        seconds: float,
        trace: Optional[Dict[str, Any]] = None,
        on_start: Optional[Callable[[], None]] = None,
    ) -> Dict[str, Any]:
        """Drive flushes 1.. in a closed loop.  Untraced: until ``seconds``
        have passed, the flush in flight then is finished, and the window is
        from the first send to the last answer.  Traced (``trace`` is the
        traffic's plan): one flush in steady state, then ``host_flushes``
        whole flushes under a profiler window that records the host's
        runtime events alone (its stop takes 0.3 s and it holds every program
        launch of a flush however long), then the device's window around
        ``flushes`` whole flushes, and no more, however long ``seconds`` is.
        Where the plan sets ``seconds`` the device's window is closed that
        long after it opened, inside the flush: the device's trace buffer
        holds about 5 million op events, 1.3 s of these programs (PERF.md)."""
        if trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)  # one run's on disk
        stats0 = self.stats()
        cache0 = self._cache_entries()
        fell_seen = self._fallback_requests()
        fell_calls = wraps = 0
        nxt = 1
        t_end = 0.0
        lat: List[float] = []
        stamps: List[Tuple[int, int]] = []
        answers: List[Tuple[int, List[bool]]] = []

        def call() -> None:
            nonlocal fell_seen, fell_calls, wraps, nxt, t_end
            w0 = time.time_ns()
            c0 = time.perf_counter()
            got = self.client.verify_batch(flushes[nxt].requests)
            t_end = time.perf_counter()
            stamps.append((w0, time.time_ns()))
            lat.append(t_end - c0)
            answers.append((nxt, got))
            fell = self._fallback_requests()
            if fell != fell_seen:
                fell_seen = fell
                fell_calls += 1
            nxt += 1
            if nxt >= len(flushes):
                nxt = 1
                wraps += 1

        def opened(which: str, host_only: bool) -> int:
            """Open a profiler window in the worker; its start on the wall
            clock.  A worker without jax (the tests') traces nothing."""
            if not self.device:
                return time.time_ns()
            return int(self.worker.control(
                op="trace_start", dir=os.path.join(self.trace_dir, which),
                host_only=host_only,
            )["wall_ns"])

        traced: Dict[str, Any] = {}
        if on_start:
            on_start()
        t_start = time.perf_counter()
        if not trace:
            while True:
                call()
                if t_end - t_start >= seconds:
                    break
        else:
            call()
            host_flushes = int(trace.get("host_flushes", 0))
            if host_flushes:
                h0 = self.stats()
                start = opened("host", host_only=True)
                first = len(lat)
                for _ in range(host_flushes):
                    call()
                stop = time.time_ns()
                if self.device:
                    self.worker.control(op="trace_stop")
                traced["host"] = {
                    "window": (start, stop), "from": first, "to": len(lat),
                    "stats0": h0, "stats1": self.stats(),
                }
            cut: Dict[str, Any] = {}

            def close_device() -> None:
                cut["stop_ns"] = time.time_ns()
                cut.update(self.worker.control(op="trace_stop"))

            stats_a = self.stats()
            start = opened("device", host_only=False)
            first = len(lat)
            timer = None
            if trace.get("seconds") and self.device:
                timer = threading.Timer(float(trace["seconds"]), close_device)
                timer.start()
            for _ in range(int(trace["flushes"])):
                call()
            stop = time.time_ns()
            if timer is not None:
                timer.cancel()  # a flush shorter than the cap: close it here
                timer.join()
            reply = cut
            if self.device and not cut:
                reply = self.worker.control(op="trace_stop")
            traced["device"] = {
                "window": (start, cut.get("stop_ns", stop)), "from": first,
                "to": len(lat), "stats0": stats_a, "cut": bool(cut),
                "stop_s": reply.get("stop_s"),
            }
        alive = self.worker.alive
        stats1 = self.stats() if alive else None
        cache1 = self._cache_entries()
        if "device" in traced:
            traced["device"]["stats1"] = stats1
        return {
            "lat": lat, "stamps": stamps, "answers": answers, "wraps": wraps,
            "fell_calls": fell_calls, "window_s": t_end - t_start,
            "stats0": stats0, "stats1": stats1,
            "new_cache_entries": (
                len(set(cache1) - set(cache0))
                if cache0 is not None and cache1 is not None else 0
            ),
            "traced": traced,
        }

    def memory_peak(self) -> Optional[int]:
        if not self.worker.alive or self.worker.control_port is None:
            return None
        return self.worker.control(op="memory")["memory_peak_bytes"]

    def close(self, grace_s: float) -> Optional[int]:
        """Close the client, stop the worker, kill it and its process group
        after ``grace_s``, and wait until it has ended."""
        if self.client is not None:
            self.client.close()
            self.client = None
        self.worker_rc = self.worker.stop(grace_s)
        return self.worker_rc


def judge(
    cell: Cell, prep: Prepared, obs: Dict[str, Any],
    setup_answers: Sequence[Dict[str, Any]] = (),
    worker_rc: Optional[int] = 0,
) -> Tuple[bool, Dict[str, Dict[str, int]], int, List[str]]:
    """``correct``, the numbers compared each beside its limit, ``failed``
    and what went wrong on the way, for one window.

    Every answer of the window and of the set-up's flushes is held against
    the verdict the construction expects, and, where the plain reference
    judged that request (:func:`judged_positions`), against the reference's
    verdict; the construction is held against the reference on every request
    judged.  All limits are 0: the comparison is exact."""
    n_req = cell.requests_per_flush
    calls = len(obs["lat"])
    c0 = obs["stats0"].get("counters", {})
    c1 = (obs["stats1"] or {}).get("counters", {})
    worker_flushes = c1.get("crypto.flushes", 0) - c0.get("crypto.flushes", 0)
    flush_errors = c1.get("crypto.flush_errors", 0) - c0.get("crypto.flush_errors", 0)
    failed = obs["fell_calls"] * n_req
    problems: List[str] = []
    if obs["stats1"] is None or worker_rc != 0:
        problems.append(f"worker died or exited with code {worker_rc}")
        failed = max(failed, n_req)
    if flush_errors:
        problems.append(f"worker counted {flush_errors} flush errors")
    if obs["stats1"] is not None and worker_flushes != calls:
        problems.append(f"worker counted {worker_flushes} flushes for {calls} calls")

    vs_reference = vs_construction = by_reference = by_construction = 0
    judged = due = 0
    every = [(s["index"], s["answers"]) for s in setup_answers] + list(obs["answers"])
    for index, got in every:
        expected = prep.get(index).expected
        verdicts = prep.reference(index)
        vs_construction += sum(g != e for g, e in zip(got, expected))
        by_construction += min(len(got), len(expected))
        due += len(expected)
        positions = [p for p, v in enumerate(verdicts) if v is not None]
        answered = [p for p in positions if p < len(got)]
        vs_reference += sum(got[p] != verdicts[p] for p in answered)
        by_reference += len(answered)
        judged += len(positions)
    built = [WARM_UP] + ([PROBE] if prep.has_probe else [])
    built += range(1, 1 + prep.pool_flushes)
    construction_vs_reference = sum(
        e != v
        for i in built
        for e, v in zip(prep.get(i).expected, prep.reference(i))
        if v is not None
    )
    compared = {
        "answers_differing_from_reference": {"value": vs_reference, "limit": 0},
        "answers_differing_from_construction": {"value": vs_construction, "limit": 0},
        "construction_differing_from_reference": {
            "value": construction_vs_reference, "limit": 0,
        },
        "answers_checked_by_reference": {"value": by_reference, "at_least": judged},
        "answers_checked_by_construction": {"value": by_construction, "at_least": due},
        "requests_not_answered_by_chip_path": {"value": failed, "limit": 0},
        "flush_count_mismatch_or_errors": {"value": len(problems), "limit": 0},
        "programs_compiled_in_window": {
            "value": obs["new_cache_entries"], "limit": 0,
        },
    }
    correct = all(
        entry["value"] >= entry["at_least"] if "at_least" in entry
        else entry["value"] <= entry["limit"]
        for entry in compared.values()
    )
    return correct, compared, failed, problems


def say_compared(say: Callable[[str], None], compared: Dict[str, Dict[str, int]], correct: bool) -> None:
    for name, entry in compared.items():
        bound = "limit" if "limit" in entry else "at_least"
        say(f"compared {name} = {entry['value']} ({bound} {entry[bound]})")
    say(f"correct = {correct}")


def reduce_trace_in_child(request: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Reduce the trace in a child of its own, so that this process never
    imports jax.  The child is held to the CPU and starts no backend."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO_ROOT
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.harness.reduce_trace"],
        input=json.dumps(request), capture_output=True, text=True,
        env=env, cwd=REPO_ROOT, timeout=1800,
    )
    if out.returncode != 0:
        raise RuntimeError(f"trace reduction failed: {out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def _flush_total_s(stats: Optional[Dict[str, Any]]) -> float:
    return (stats or {}).get("timers", {}).get("crypto.flush", {}).get("total_s", 0.0)


def _flushes(stats: Optional[Dict[str, Any]]) -> int:
    return (stats or {}).get("counters", {}).get("crypto.flushes", 0)


def layer_metrics(
    cell: Cell, session: Session, prep: Prepared, obs: Dict[str, Any],
    notes: Dict[str, Any],
) -> Tuple[Dict[str, Dict[str, Any]], Optional[Dict[str, Any]]]:
    """The per-layer metrics of a traced window, each from its own reader
    under ``chipbench/layer_metrics``, and the device trace's reduction."""
    dev = obs["traced"]["device"]
    host = obs["traced"].get("host")
    reduced = host_seen = None
    if session.device:
        request: Dict[str, Any] = {}
        path = find_trace(os.path.join(session.trace_dir, "device"))
        if path:
            request.update(
                path=path, window_wall_ns=list(dev["window"]),
                flushes_wall_ns=[list(s) for s in obs["stamps"][dev["from"]:dev["to"]]],
            )
            notes["trace_bytes"] = os.path.getsize(path)
            notes["trace_stop_s"] = dev["stop_s"]
            notes["trace_cut_inside_flush"] = dev["cut"]
        host_path = host and find_trace(os.path.join(session.trace_dir, "host"))
        if host_path:
            request.update(host_path=host_path, host_window_wall_ns=list(host["window"]))
        if request:
            out = reduce_trace_in_child(request)
            reduced = out.get("device")
            if host_path and out.get("host_launches"):
                host_seen = {
                    "launches": out["host_launches"],
                    "flushes": host["to"] - host["from"],
                    "worker_flush_s": _flush_total_s(host["stats1"])
                    - _flush_total_s(host["stats0"]),
                    "worker_flushes": _flushes(host["stats1"]) - _flushes(host["stats0"]),
                }
                notes["host_window_launches"] = host_seen["launches"]
    seen = {
        "cell": cell.cell,
        "config": cell.config,
        "traffic": cell.traffic,
        "device_kind": session.device.get("kind"),
        "flushes": dev["to"] - dev["from"],
        "client_s": sum(obs["lat"][dev["from"]:dev["to"]]),
        "worker_flush_s": _flush_total_s(dev["stats1"]) - _flush_total_s(dev["stats0"]),
        "worker_flushes": _flushes(dev["stats1"]) - _flushes(dev["stats0"]),
        "trace": reduced,
        "trace_cut": dev["cut"],
        "host": host_seen,
        "work": work.compose(prep.flushes[1].kinds, prep.flushes[1].wire),
        "notes": notes,
    }
    metrics: Dict[str, Dict[str, Any]] = {}
    for m in cell.metrics("per_layer"):
        reader = importlib.import_module("chipbench.layer_metrics." + m["name"])
        value = reader.read(seen)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, reduced


def run_cell(
    bench: Dict[str, Any],
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t0: float,
    root: str = REPO_ROOT,
    require_tpu: bool = True,
    worker_entry: str = DEFAULT_ENTRY,
    worker_entry_args: Sequence[str] = (),
    worker_overrides: Optional[Dict[str, Any]] = None,
    out: Any = None,
    err: Any = None,
) -> int:
    """Run one cell and print the contract's line on ``out``.  Returns the
    process exit code; with any code but 0 nothing was printed on ``out``.

    ``require_tpu=False``, ``worker_entry``, ``worker_entry_args`` and
    ``worker_overrides`` are for the benchmark's own tests: the command line
    sets none of them."""
    out = out or sys.stdout
    err = err or sys.stderr

    def say(msg: str) -> None:
        print(f"chipbench: {msg}", file=err, flush=True)

    try:
        cell = Cell(bench, workload, root)
    except (KeyError, OSError, ValueError) as e:
        say(f"cannot load workload {workload!r}: {e}")
        return EXIT_USAGE

    session = Session(
        cell, require_tpu=require_tpu, worker_entry=worker_entry,
        worker_entry_args=worker_entry_args, worker_overrides=worker_overrides,
    )
    prep: Optional[Prepared] = None
    setup: Dict[str, float] = {}
    ended = False
    try:
        session.start()
        prep = Prepared(cell, seed)
        session.connect()
        set_up = [session.setup_flush(WARM_UP, prep.get(WARM_UP))]
        if prep.has_probe:
            set_up.append(session.setup_flush(PROBE, prep.get(PROBE)))
        waited = time.perf_counter()
        prep.finish()
        parent_behind_s = time.perf_counter() - waited
        obs = session.window(
            prep.flushes, seconds, cell.traffic["trace"] if trace else None,
            on_start=lambda: setup.update(s=time.perf_counter() - t0),
        )
        memory = session.memory_peak()
        ended = True
    except NoResult as e:
        say(str(e))
        return e.code
    finally:
        # also where a cut (SIGTERM, chipbench/run.py) unwinds through here:
        # the helpers and the worker end before this process does
        if prep is not None:
            prep.abandon()
        session.close(grace_s=WORKER_STOP_S if ended else WORKER_CUT_S)

    correct, compared, failed, problems = judge(
        cell, prep, obs, set_up, worker_rc=session.worker_rc
    )
    calls = len(obs["lat"])
    attempted = calls * cell.requests_per_flush
    device = session.device
    device_out: Dict[str, Any] = {
        "platform": device.get("platform", "none"),
        "kind": device.get("kind", "none"),
        "count": int(device.get("count", 0)),
        "memory_peak_bytes": memory if memory is not None else 0,
    }
    notes: Dict[str, Any] = {}
    breakdown = None
    if trace:
        metrics, reduced = layer_metrics(cell, session, prep, obs, notes)
        if reduced is not None and device.get("platform") == "tpu":
            device_out["busy_s"] = reduced["busy_s"]
            device_out["window_s"] = reduced["window_s"]
            breakdown = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"],
            }
            notes["idle_by_label_s"] = reduced["idle_by_label_s"]
            notes["trace_anchored"] = reduced["anchored"]
            notes["device_launches"] = reduced["launches"]
            notes["host_launch_events_in_device_window"] = reduced["host_launch_events"]
            notes["module_s_per_launch"] = reduced["module_s_per_launch"]
    else:
        lat_ms = [x * 1e3 for x in obs["lat"]]
        values = {
            "verifies_per_s": (attempted - failed) / obs["window_s"],
            "flush_ms.p50": hstats.quantile(lat_ms, 0.5),
            "flush_ms.p95": hstats.quantile(lat_ms, 0.95),
            "setup_s": setup["s"],
        }
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.metrics("end_to_end")
        }

    line: Dict[str, Any] = {
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device_out,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    ready = session.ready
    line["run"] = {
        "workload": cell.name, "seed": seed, "trace": bool(trace),
        "flushes": calls, "window_s": obs["window_s"], "setup_s": setup["s"],
        "warmup_flush_s": set_up[0]["seconds"],
        "probe_flush_s": set_up[-1]["seconds"] if prep.has_probe else None,
        "reference_s": prep.reference_s,
        "judged_requests": prep.judged_requests,
        "parent_behind_worker_s": parent_behind_s,
        "pool_wraps": obs["wraps"],
        "compile_cache_dir": ready.get("compile_cache_dir"),
        "compile_cache_empty_at_start": ready.get("compile_cache_empty"),
        "jax": ready.get("jax"), "problems": problems, **notes,
    }
    line["compared"] = compared
    if obs["wraps"]:
        say(f"the pool of {prep.pool_flushes} flushes wrapped around "
            f"{obs['wraps']} time(s): documents were used twice; a benchmark "
            "issue enlarges the pool")
    for p in problems:
        say(p)
    say_compared(say, compared, correct)
    print(json.dumps(line), file=out, flush=True)
    return 0
