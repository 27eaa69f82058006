"""One run of one cell: worker up, warm-up, measured window, check, one line.

The path the window drives is the product's: ``RpcServiceClient.verify_batch``
against one worker process (``--suite bls --backend tpu``) that owns the
chip.  Closed loop, one client, one flush in flight.  This process never
imports jax.

Set-up (``setup_s``: process start to the first timed flush) is the worker's
start, one warm-up flush of the cell's own traffic (the compile, or the
cache read) and, overlapped with it in this process and its helpers, the
building of the seeded traffic pool and the plain reference's verdicts for
the sample that decides ``correct``.

The pieces (:class:`Session`, :class:`Prepared`, :func:`judge`) are apart so
that ``chipbench/tools/seeds.py`` can drive many seeds through one worker.
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
from chipbench.harness.reduce_trace import find_trace
from chipbench.harness.worker import DEFAULT_ENTRY, REPO_ROOT, Worker

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


def _build_one(args: Tuple[str, Dict, Dict, int, int, Any]):
    generator, config, params, seed, index, keys = args
    mod = importlib.import_module("chipbench.generators." + generator)
    return mod.make_flush(config, params, seed, index, keys)


def draw_sample(seed: int, pool_flushes: int, requests: int, n: int) -> List[Tuple[int, int]]:
    """The (flush, position) pairs whose answers the plain reference judges:
    every other one from flush 1, which every run completes, the rest from
    the first half of the pool (what a run is expected to reach)."""
    rng = random.Random(f"chipbench sample {seed}")
    reach = max(1, pool_flushes // 2)
    picked: List[Tuple[int, int]] = []
    seen = set()
    for k in range(n):
        flush = 1 if k % 2 == 0 else rng.randrange(1, reach + 1)
        pair = (flush, rng.randrange(requests))
        if pair not in seen:
            seen.add(pair)
            picked.append(pair)
    return picked


class Prepared:
    """One seed's traffic and the reference's verdicts on its sample.

    Flush 0 is the warm-up.  The flushes are built from the seed by
    ``build_processes`` helper processes (plain Python, no jax) and the sample
    is judged by the plain reference on a thread of this process, both while
    the worker warms up; :meth:`finish` waits for both and ends the helpers,
    so nothing of this runs inside the window."""

    def __init__(self, cell: Cell, seed: int, pool_flushes: Optional[int] = None) -> None:
        from chipbench.reference.verify import Reference

        traffic = cell.traffic
        self.pool_flushes = int(pool_flushes or traffic["pool_flushes"])
        self.keys = cell.generator.make_keys(cell.config, traffic["params"], seed)
        self._executor = concurrent.futures.ProcessPoolExecutor(
            max_workers=int(traffic.get("build_processes", 2)),
            mp_context=multiprocessing.get_context("spawn"),
        )
        self._futures = [
            self._executor.submit(
                _build_one,
                (traffic["generator"], cell.config, traffic["params"], seed, i,
                 self.keys),
            )
            for i in range(1 + self.pool_flushes)
        ]
        self.sample = draw_sample(
            seed, self.pool_flushes, cell.requests_per_flush,
            int(traffic["check_requests"]),
        )
        self.reference_verdicts: Dict[Tuple[int, int], bool] = {}
        self.reference_s = 0.0
        self._reference = Reference()
        self._judge = threading.Thread(
            target=self._judge_sample, name="chipbench-reference"
        )
        self._judge.start()
        self.flushes: List[Any] = []

    def _judge_sample(self) -> None:
        for flush_i, pos in self.sample:
            try:
                wire = self.get(flush_i).wire[pos]
            except concurrent.futures.CancelledError:
                return  # abandoned: the run prints no result
            t = time.perf_counter()
            self.reference_verdicts[(flush_i, pos)] = self._reference.verify(*wire)
            self.reference_s += time.perf_counter() - t

    def get(self, index: int):
        return self._futures[index].result()

    def finish(self) -> None:
        self._judge.join()
        self.flushes = [f.result() for f in self._futures]
        self._executor.shutdown(wait=True)

    def abandon(self) -> None:
        """End the helpers and the reference's thread, built or not."""
        self._executor.shutdown(wait=True, cancel_futures=True)
        self._judge.join()


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

    def warm_up(self, flush: Any) -> Dict[str, Any]:
        t = time.perf_counter()
        got = self.client.verify_batch(flush.requests)
        seconds = time.perf_counter() - t
        if not self.worker.alive:
            raise NoResult(EXIT_WORKER, "the worker died during the warm-up flush")
        return {
            "seconds": seconds,
            "wrong": sum(g != w for g, w in zip(got, flush.expected)),
        }

    def window(
        self,
        flushes: Sequence[Any],
        seconds: float,
        trace_flushes: int = 0,
        on_start: Optional[Callable[[], None]] = None,
        trace_options: Optional[Dict[str, Any]] = None,
        trace_seconds: Optional[float] = None,
    ) -> Dict[str, Any]:
        """Drive flushes 1.. in a closed loop.  Untraced: until ``seconds``
        have passed, the flush in flight then is finished, and the window is
        from the first send to the last answer.  Traced (``trace_flushes`` >
        0): one flush, then the profiler's window around that many whole
        flushes, and no more, however long ``seconds`` is.  Where the traffic
        sets ``trace_seconds`` the profiler's window is closed that long
        after it opened, inside the flush: the device's trace buffer holds
        about 5 million op events, 1.3 s of these programs (PERF.md)."""
        trace = trace_flushes > 0
        if trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)  # one trace on disk
        stats0 = self.stats()
        cache0 = self._cache_entries()
        fell0 = self._fallback_requests()
        lat: List[float] = []
        stamps: List[Tuple[int, int]] = []
        answers: List[Tuple[int, List[bool]]] = []
        wraps = fell_calls = traced_from = 0
        trace_window: Optional[Tuple[int, int]] = None
        trace_file: Dict[str, Any] = {}
        cut: Dict[str, int] = {}  # the window's end, where a timer closed it
        timer: Optional[threading.Timer] = None

        def close_trace() -> None:
            cut["stop_ns"] = time.time_ns()
            trace_file.update(self.worker.control(op="trace_stop"))
        stats_a = stats0
        nxt = 1
        if on_start:
            on_start()
        t_start = t_end = time.perf_counter()
        while True:
            if trace and len(lat) == 1 and trace_window is None:
                stats_a = self.stats()
                started = time.time_ns()
                if self.device:  # a worker without jax (the tests') has no trace
                    started = int(
                        self.worker.control(
                            op="trace_start", dir=self.trace_dir,
                            options=trace_options,
                        )["wall_ns"]
                    )
                trace_window = (started, 0)
                traced_from = len(lat)
                if trace_seconds and self.device:
                    timer = threading.Timer(trace_seconds, close_trace)
                    timer.start()
            w0 = time.time_ns()
            c0 = time.perf_counter()
            got = self.client.verify_batch(flushes[nxt].requests)
            t_end = time.perf_counter()
            stamps.append((w0, time.time_ns()))
            lat.append(t_end - c0)
            answers.append((nxt, got))
            fell = self._fallback_requests()
            if fell != fell0:
                fell_calls += 1
                fell0 = fell
            nxt += 1
            if nxt >= len(flushes):
                nxt = 1
                wraps += 1
            if trace:
                if trace_window and len(lat) - traced_from >= trace_flushes:
                    break
            elif t_end - t_start >= seconds:
                break
        if trace_window:
            stop_ns = time.time_ns()
            if timer is not None:
                timer.cancel()  # a flush shorter than the cap: close it here
                timer.join()
            if self.device and not cut:
                trace_file.update(self.worker.control(op="trace_stop"))
            trace_window = (trace_window[0], cut.get("stop_ns", stop_ns))
        alive = self.worker.alive
        stats1 = self.stats() if alive else None
        cache1 = self._cache_entries()
        return {
            "lat": lat, "stamps": stamps, "answers": answers, "wraps": wraps,
            "fell_calls": fell_calls, "window_s": t_end - t_start,
            "stats0": stats0, "stats_a": stats_a, "stats1": stats1,
            "new_cache_entries": (
                len(set(cache1) - set(cache0))
                if cache0 is not None and cache1 is not None else 0
            ),
            "trace_window": trace_window, "traced_from": traced_from,
            "trace_stop_s": trace_file.get("stop_s"),
            "trace_cut": bool(cut),
        }

    def memory_peak(self) -> Optional[int]:
        if not self.worker.alive or self.worker.control_port is None:
            return None
        return self.worker.control(op="memory")["memory_peak_bytes"]

    def close(self) -> Optional[int]:
        """Close the client, stop the worker and wait until it has ended."""
        if self.client is not None:
            self.client.close()
            self.client = None
        self.worker_rc = self.worker.stop()
        return self.worker_rc


def judge(
    cell: Cell, prep: Prepared, obs: Dict[str, Any], warm_wrong: int = 0,
    worker_rc: Optional[int] = 0,
) -> Tuple[bool, Dict[str, Dict[str, int]], int, List[str]]:
    """``correct``, the numbers compared each beside its limit, ``failed``
    and what went wrong on the way, for one window.

    Every answer is held against the verdict the construction expects, and
    the seeded sample against the plain reference, which so judges the
    construction as well.  All limits are 0: the comparison is exact."""
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

    vs_construction = warm_wrong
    first_answer: Dict[int, List[bool]] = {}
    for idx, got in obs["answers"]:
        first_answer.setdefault(idx, got)
        vs_construction += sum(
            g != w for g, w in zip(got, prep.flushes[idx].expected)
        )
    vs_reference = construction_vs_reference = checked = 0
    for (flush_i, pos), verdict in prep.reference_verdicts.items():
        if prep.flushes[flush_i].expected[pos] != verdict:
            construction_vs_reference += 1
        if flush_i in first_answer:
            checked += 1
            if first_answer[flush_i][pos] != verdict:
                vs_reference += 1
    min_checked = len(prep.sample) // 3
    compared = {
        "answers_differing_from_reference": {"value": vs_reference, "limit": 0},
        "answers_differing_from_construction": {"value": vs_construction, "limit": 0},
        "construction_differing_from_reference": {
            "value": construction_vs_reference, "limit": 0,
        },
        "answers_checked_by_reference": {"value": checked, "at_least": min_checked},
        "requests_not_answered_by_chip_path": {"value": failed, "limit": 0},
        "flush_count_mismatch_or_errors": {"value": len(problems), "limit": 0},
        "programs_compiled_in_window": {
            "value": obs["new_cache_entries"], "limit": 0,
        },
    }
    correct = checked >= min_checked and all(
        entry["value"] == 0 for entry in compared.values() if "limit" in entry
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


def layer_metrics(
    cell: Cell, session: Session, prep: Prepared, obs: Dict[str, Any],
    notes: Dict[str, Any],
) -> Tuple[Dict[str, Dict[str, Any]], Optional[Dict[str, Any]]]:
    """The per-layer metrics of a traced window, each from its own reader
    under ``chipbench/layer_metrics``, and the trace's reduction."""
    reduced = None
    path = find_trace(session.trace_dir) if session.device else None
    if path:
        reduced = reduce_trace_in_child({
            "path": path,
            "window_wall_ns": list(obs["trace_window"]),
            "flushes_wall_ns": [list(s) for s in obs["stamps"][obs["traced_from"]:]],
        })
        notes["trace_bytes"] = os.path.getsize(path)
        notes["trace_stop_s"] = obs["trace_stop_s"]
        notes["trace_cut_inside_flush"] = obs["trace_cut"]
    counters1 = (obs["stats1"] or {}).get("counters", {})
    seen = {
        "cell": cell.cell,
        "config": cell.config,
        "traffic": cell.traffic,
        "device_kind": session.device.get("kind"),
        "flushes": len(obs["lat"]) - obs["traced_from"],
        "client_s": sum(obs["lat"][obs["traced_from"]:]),
        "worker_flush_s": _flush_total_s(obs["stats1"]) - _flush_total_s(obs["stats_a"]),
        "worker_flushes": (
            counters1.get("crypto.flushes", 0)
            - obs["stats_a"].get("counters", {}).get("crypto.flushes", 0)
        ),
        "trace": reduced,
        "trace_cut": obs["trace_cut"],
        "documents_per_flush": prep.flushes[1].documents,
        "document_bytes": len(prep.flushes[1].wire[0][1]),
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
    session.start()
    prep: Optional[Prepared] = None
    setup: Dict[str, float] = {}
    try:
        prep = Prepared(cell, seed)
        session.connect()
        warm = session.warm_up(prep.get(0))
        waited = time.perf_counter()
        prep.finish()
        parent_behind_s = time.perf_counter() - waited
        obs = session.window(
            prep.flushes, seconds,
            int(cell.traffic["trace_flushes"]) if trace else 0,
            on_start=lambda: setup.update(s=time.perf_counter() - t0),
            trace_seconds=cell.traffic.get("trace_seconds"),
        )
        memory = session.memory_peak()
    except NoResult as e:
        say(str(e))
        return e.code
    finally:
        if prep is not None:
            prep.abandon()
        session.close()

    correct, compared, failed, problems = judge(
        cell, prep, obs, warm_wrong=warm["wrong"], worker_rc=session.worker_rc
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
        "warmup_flush_s": warm["seconds"], "reference_s": prep.reference_s,
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
