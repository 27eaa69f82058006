"""Chip smoke: the crypto-plane worker answers a real-BLS flush on one TPU.

The quickest proof that the system still starts on the chip.  The
parent (this process) never imports jax: it builds BLS12-381 signature
share requests with the pure-Python suite, starts ONE worker
(``python -m hbbft_tpu.cryptoplane.proc_service --suite bls --backend
tpu``) that owns the chip, and sends phase ``round`` through
``RpcServiceClient.verify_batch``, twice (the first call compiles):

* ``round`` — one N=16 coin round: 16 shares on one document, f=5 of
  them a valid share of ANOTHER key index at seeded positions.  Bucket
  (16, 16, 2); expects the exact per-share verdict vector, so the
  worker's fault isolation (bisection, re-flushing halves through the
  same two programs) runs too.

That is two jitted programs, ``_scan_kernel(16, 16, 2)`` and
``_pair_kernel(3)``, each 8-10 minutes of XLA compile for a v5e; a cold
flush compiles them side by side.  The 2048-share production chunk (a
third program, ten more minutes) does not fit a 1200 s cold run and
comes with the first benchmark (CHANGES.md, PR 25, has its chip run).

Every verdict is compared with the pure-Python oracle
(``EagerBackend``).  Any client fallback, any worker flush error, a
flush count that differs from the calls made, or a worker that dies or
exits non-zero fails the run.

Output: one JSON line per phase (``host_wall_s`` numbers are host wall
clock, compile included in ``first_call`` — not device metrics), then
``{"ok": true, "device": {...}}`` as the worker reported its device.
Without a TPU nothing is printed on stdout and the exit code is 1.

    python chip_smoke.py [--seed N]
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from hbbft_tpu.crypto.backend import (  # noqa: E402
    BatchedBackend,
    EagerBackend,
    VerifyRequest,
)
from hbbft_tpu.crypto.bls.suite import BLSSuite  # noqa: E402
from hbbft_tpu.crypto.keys import SecretKeySet  # noqa: E402
from hbbft_tpu.cryptoplane.proc_service import (  # noqa: E402
    COLD_COMPILE_TIMEOUT_S,
    RpcServiceClient,
    ServiceProcess,
)
from hbbft_tpu.utils.metrics import Metrics  # noqa: E402

N_VALIDATORS = 16
N_FAULTY = 5


class Phase(NamedTuple):
    name: str
    bucket: Tuple[int, int, int]
    reqs: List[VerifyRequest]
    expected: List[bool]


def make_phases(seed: int, suite: BLSSuite) -> List[Phase]:
    """Keys, document and fault positions from ``seed``."""
    rng = random.Random(seed)
    sks = SecretKeySet.random(N_FAULTY, rng, suite)
    pks = sks.public_keys()
    doc = b"chip-smoke coin round %d" % seed
    pk = [pks.public_key_share(i) for i in range(N_VALIDATORS)]
    sig = [sks.secret_key_share(i).sign(doc) for i in range(N_VALIDATORS)]

    bad = set(rng.sample(range(N_VALIDATORS), N_FAULTY))
    round_reqs = [
        # wrong but well-formed: a valid share of the next key index
        VerifyRequest.sig_share(
            pk[i], doc, sig[(i + 1) % N_VALIDATORS if i in bad else i]
        )
        for i in range(N_VALIDATORS)
    ]
    return [
        Phase(
            "round", (16, 16, 2), round_reqs,
            [i not in bad for i in range(N_VALIDATORS)],
        ),
    ]


def check_reference(suite: BLSSuite, phase: Phase) -> Optional[str]:
    """The construction's expected verdicts against the oracle."""
    got = EagerBackend(suite).verify_batch(phase.reqs)
    if got != phase.expected:
        return (
            f"{phase.name}: oracle says {got}, "
            f"construction says {phase.expected}"
        )
    return None


def _call_failures(
    got: List[bool],
    expected: List[bool],
    client: Metrics,
    worker: Optional[Dict[str, int]],
    calls: int,
) -> List[str]:
    """What one ``verify_batch`` call did wrong (``worker``: the worker's
    counters after it, None if the worker is dead)."""
    out = []
    wrong = [i for i, (g, w) in enumerate(zip(got, expected)) if g != w]
    if wrong:
        out.append(
            f"{len(wrong)} verdicts differ from the reference, "
            f"first at {wrong[:8]}"
        )
    fell = {
        k: v for k, v in client.counters.items()
        if k.startswith("crypto.rpc.fallback") and v
    }
    if fell:
        out.append(f"client fell back to its local backend: {fell}")
    if worker is None:
        out.append("worker died")
        return out
    if worker.get("crypto.flush_errors", 0):
        out.append(
            f"worker counted {worker['crypto.flush_errors']} flush errors "
            "(traceback on its stderr)"
        )
    if worker.get("crypto.flushes", 0) != calls:
        out.append(
            f"worker counted {worker.get('crypto.flushes', 0)} flushes "
            f"after {calls} calls"
        )
    return out


def drive(
    proc: ServiceProcess,
    suite: BLSSuite,
    phases: Sequence[Phase],
    timeout_s: float = COLD_COMPILE_TIMEOUT_S,
) -> Tuple[List[Dict[str, Any]], List[str]]:
    """Send each phase twice (the first call compiles) through one RPC
    client; returns (one row per phase, failures) and stops at the first
    call that fails."""
    metrics = Metrics()
    client = RpcServiceClient(
        proc.addr, suite, BatchedBackend(suite),
        timeout_s=timeout_s, metrics=metrics,
    )
    ready = proc.ready or {}
    rows: List[Dict[str, Any]] = []
    calls = 0
    flush_total_s = 0.0
    try:
        for ph in phases:
            walls, flush_s = [], []
            for which in ("first", "repeat"):
                t0 = time.perf_counter()
                got = client.verify_batch(ph.reqs)
                walls.append(time.perf_counter() - t0)
                calls += 1
                stats = proc.stats() if proc.alive else None
                failures = _call_failures(
                    got, ph.expected, metrics,
                    stats and stats["counters"], calls,
                )
                if failures:
                    where = f"{ph.name} ({which} call): "
                    return rows, [where + f for f in failures]
                # the worker's crypto.flush timer is cumulative
                total_s = stats["timers"]["crypto.flush"]["total_s"]
                flush_s.append(total_s - flush_total_s)
                flush_total_s = total_s
            rows.append({
                "phase": ph.name,
                "requests": len(ph.reqs),
                "bucket": list(ph.bucket),
                "verdicts_true": sum(ph.expected),
                "host_wall_s": {
                    "first_call": walls[0],
                    "repeat_call": walls[1],
                    "first_call_worker_flush": flush_s[0],
                    "repeat_call_worker_flush": flush_s[1],
                },
                "compile_cache_dir": ready.get("compile_cache_dir"),
                "compile_cache_empty_at_start": ready.get(
                    "compile_cache_empty"
                ),
                "jax": ready.get("jax"),
            })
    finally:
        client.close()
    return rows, []


def worker_exit(proc: ServiceProcess) -> Optional[str]:
    """Stop the worker; a failure string unless it exited 0."""
    proc.stop(grace_s=30.0)
    rc = proc.proc.returncode if proc.proc is not None else None
    return None if rc == 0 else f"worker exit code {rc}"


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    suite = BLSSuite()
    phases = make_phases(args.seed, suite)
    failures = [f for f in (check_reference(suite, p) for p in phases) if f]
    rows: List[Dict[str, Any]] = []
    device = None
    if not failures:
        proc = ServiceProcess(suite="bls", backend="tpu", ready_timeout_s=600.0)
        try:
            proc.start()
            device = (proc.ready or {}).get("device")
            if not device or device.get("platform") != "tpu":
                failures.append(
                    f"the worker holds no TPU: its ready line says {device}"
                )
            else:
                rows, failures = drive(proc, suite, phases)
        except (OSError, TimeoutError) as e:
            failures.append(f"worker did not start: {e}")
        finally:
            bad_exit = worker_exit(proc)
        if bad_exit:
            failures.append(bad_exit)
    for row in rows:
        print(json.dumps(row), flush=True)
    if failures:
        for f in failures:
            print(f"chip_smoke: FAIL: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
