"""Mid device tier: a ~10-minute warm-cache slice of the heavy tests.

Round-4 VERDICT missing #5 / next-round #7: the full device tier costs
~45 min warm on this 1-core box (execution-bound pairing products) and
the default tier leaves out ALL eight heavy tests (they are marked
``slow``) — so a time-boxed round could
regress the pairing/flush kernels without noticing.  This tier runs the
three heavy tests that cover exactly the graphs the kernel rounds keep
rewriting, on their smallest shape buckets:

* ``test_pairing_product_vs_oracle`` — Miller loop + final exp vs the
  pure-Python oracle (curve.py / pairing.py / fq.py changes all land
  here first),
* ``test_tpu_backend_matches_batched_backend`` — the production flush
  (RLC scans + endo subgroup checks + two-stage scan/pair split) against
  the host RLC backend,
* ``test_tpu_backend_sharded_flush_matches`` — the same flush dp-sharded
  over the virtual 8-device mesh, including a bad share (bisection).

Writes ``DEVICE_TIER_r{TAG}.json`` at the repo root: per-test pass/fail
plus wall time.  Usage (warm ``.jax_cache/`` assumed — a cold run adds
one-time compiles):

    DEVICE_TIER_TAG=05 python benchmarks/device_tier.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MID_TESTS = [
    "test_pairing_product_vs_oracle",
    "test_tpu_backend_matches_batched_backend",
    "test_tpu_backend_sharded_flush_matches",
]


def main() -> None:
    tag = os.environ.get("DEVICE_TIER_TAG", "dev")
    out_path = os.path.join(ROOT, f"DEVICE_TIER_r{tag}.json")
    results = []
    t_all = time.monotonic()
    for name in MID_TESTS:
        t0 = time.monotonic()
        try:
            proc = subprocess.run(
                [
                    sys.executable, "-m", "pytest",
                    os.path.join(ROOT, "tests", "test_tpu_crypto.py"),
                    "-q", "-k", name, "--no-header", "-p", "no:cacheprovider",
                ],
                cwd=ROOT,
                capture_output=True,
                text=True,
                timeout=int(
                    os.environ.get("DEVICE_TIER_STEP_TIMEOUT_S", "1800")
                ),
            )
            rc = proc.returncode
            tail = (proc.stdout or "").strip().splitlines()
            summary = tail[-1] if tail else ""
            if rc == 0 and ("skipped" in summary or "1 passed" not in summary):
                rc = 1
                summary = f"did not pass exactly one test: {summary}"
        except subprocess.TimeoutExpired:
            # A cold cache shows up as a compile stall blowing the step
            # timeout — that must be RECORDED in the artifact (it is the
            # very signal README's deploy step 3 looks for), not a
            # traceback with no JSON written.
            rc = -1
            summary = "timeout (cold cache? prewarm per README deployment)"
        wall = round(time.monotonic() - t0, 1)
        results.append(
            {
                "test": name,
                "passed": rc == 0,
                "wall_s": wall,
                "summary": summary,
            }
        )
        print(f"{name}: rc={rc} wall={wall}s", flush=True)
    payload = {
        "tier": "device-mid",
        "tag": tag,
        "all_passed": all(r["passed"] for r in results),
        "total_wall_s": round(time.monotonic() - t_all, 1),
        "results": results,
    }
    with open(out_path, "w") as fh:
        fh.write(json.dumps(payload, indent=1) + "\n")
    print(json.dumps(payload))
    sys.exit(0 if payload["all_passed"] else 1)


if __name__ == "__main__":
    main()
