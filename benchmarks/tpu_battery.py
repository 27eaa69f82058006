"""TPU battery: every chip measurement in one serialized pass.

Each step is a subprocess with its own timeout (this parent never
imports jax, so each child in turn holds the chip); every JSON line
each step prints is echoed AND appended to ``BATTERY_r{N}.jsonl`` at
the repo root, so a run of any length yields a durable record of
whatever completed.

Steps, in order (cheapest-signal-first so a short window still pays):

1. ``bench.py``            — the 10240-share headline flush + the
                             Pallas-Keccak single/multi-block probes
                             (per-size reruns: ``BENCH_SHARES=n``).
2. config5 firehose        — 10k-share verify batches, the BASELINE
                             config 5 scaling axis.
3. config3 native BLS,     — the fused stack on deployment routing:
   hybrid backend            HybridBackend sends the handful of big
                             deduped flushes (up to ~240 requests at
                             N=16) to the chip and the ~4-request
                             majority to the host — so the device rows
                             in the record come from the big flushes
                             only; a full-device run is
                             ``BENCH_BACKEND=tpu`` (budget one ~10-min
                             compile per flush shape bucket).

Run: ``python benchmarks/tpu_battery.py`` (optionally
``BATTERY_TAG=r03``).  Every step refuses to run without a TPU, so
without one each row records a non-zero ``rc`` and the battery exits 1.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def run_step(name: str, argv: list[str], env: dict, timeout_s: float, sink) -> int:
    t0 = time.monotonic()
    rec = {"step": name, "argv": argv}
    try:
        r = subprocess.run(
            argv, capture_output=True, text=True, timeout=timeout_s, cwd=ROOT,
            env={**os.environ, **env},
        )
        rec["rc"] = r.returncode
        rec["wall_s"] = round(time.monotonic() - t0, 1)
        lines = []
        for line in (r.stdout or "").splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                lines.append(json.loads(line))
            except json.JSONDecodeError:
                pass
        rec["results"] = lines
        if r.returncode != 0:
            rec["stderr_tail"] = (r.stderr or "")[-400:]
    except subprocess.TimeoutExpired as e:
        rec["rc"] = -1
        rec["wall_s"] = round(time.monotonic() - t0, 1)
        rec["error"] = f"timeout after {timeout_s:.0f}s"
        partial = (e.stdout or b"")
        if isinstance(partial, bytes):
            partial = partial.decode(errors="replace")
        rec["stdout_tail"] = partial[-400:]
    print(json.dumps(rec), flush=True)
    sink.write(json.dumps(rec) + "\n")
    sink.flush()
    return rec["rc"]


def main() -> int:
    tag = os.environ.get("BATTERY_TAG", "r05")
    out_path = os.path.join(ROOT, f"BATTERY_{tag}.jsonl")
    rcs = []
    with open(out_path, "a") as sink:

        def step(name: str, argv: list[str], env: dict, timeout_s: float):
            rcs.append(run_step(name, argv, env, timeout_s, sink))

        # Timeouts re-budgeted after first contact (round 3): ONE flush
        # shape bucket costs ~10 min of XLA compile on this 1-core host
        # and a cold step can need two; a warm single-size bench.py run
        # is ~8 min wall (cache deserialization).
        py = sys.executable
        # Round-4 kernels (static-endo + psi-split scans, run-length
        # Miller/final-exp) are NEW graphs: every flush bucket recompiles
        # once (~10 min/bucket on this host, persisted).  The sweep
        # sizes run smallest-first so the battery records the full
        # batch-scaling curve of the new kernel even if a later step
        # times out; 10240 reuses the 2048 + 4096 chunk buckets.
        step(
            "bench_flush_512", [py, "bench.py"],
            {"BENCH_SHARES": "512", "BENCH_DEADLINE_S": "2400"}, 2700,
        )
        step(
            "bench_flush_2048", [py, "bench.py"],
            {"BENCH_SHARES": "2048", "BENCH_DEADLINE_S": "2400"}, 2700,
        )
        step(
            "bench_flush_headline", [py, "bench.py"],
            {"BENCH_DEADLINE_S": "2400"}, 2700,
        )
        step(
            "flush_roofline_2048", [py, "benchmarks/flush_roofline.py"],
            # Warm by construction: runs after the 2048 bench step
            # compiled its buckets.  Stage walls + cost_analysis are the
            # round-5 roofline record (VERDICT #1).
            {"ROOFLINE_SHARES": "2048"}, 2700,
        )
        step(
            "config5_firehose", [py, "benchmarks/config5_firehose.py"],
            {}, 2700,
        )
        step(
            "config3_native_bls_hybrid",
            [py, "benchmarks/config3_native_bls.py"],
            # Hybrid: tiny flushes (mean ~4 requests at N=16) stay on the
            # host; only device-worthy batches ride the chip — a pure
            # TpuBackend run would pay a fresh compile per small bucket.
            # FIRST-WINDOW CAVEAT (measured end of round 3): even the
            # hybrid's big-flush buckets cost several distinct ~10-min
            # compiles on a cold cache, so this step may spend its whole
            # budget compiling and time out on the FIRST battery run —
            # the compiles persist in .jax_cache/, and a second run
            # completes.  Expect the fused number on the rerun, not the
            # first pass.
            {"BENCH_BACKEND": "hybrid", "BENCH_TXNS": "64", "BENCH_BATCH": "64"},
            2700,
        )


    return 1 if any(rcs) else 0


if __name__ == "__main__":
    sys.exit(main())
