"""Flush-kernel roofline: stage walls + XLA cost analysis (round-5 #1).

The round-3/4 verdicts asked what fraction of the chip the flush
actually uses — without it, "how much headroom remains" is a guess.
This measures, on a WARM cache:

* scan-stage wall (RLC scalar-mul scans + subgroup chains + tree sums)
  and pair-stage wall (batched Miller + final exp) separately, via the
  round-5 two-stage split,
* end-to-end ``verify_batch`` wall at the same size,
* XLA's own ``cost_analysis`` (flops / bytes accessed) for both
  compiled kernels, from which flops/s and the roofline position are
  derived in BASELINE.md.

One JSON line.  ``ROOFLINE_SHARES`` (default 2048) sets the batch; the
shapes must already be cached or this pays their one-time compile.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from hbbft_tpu.utils.jaxcache import enable_cache

enable_cache()

import random  # noqa: E402

import jax  # noqa: E402

from hbbft_tpu.crypto.backend import VerifyRequest  # noqa: E402
from hbbft_tpu.crypto.bls.suite import BLSSuite  # noqa: E402
from hbbft_tpu.crypto.keys import SecretKeySet  # noqa: E402
from hbbft_tpu.crypto.tpu import backend as tb  # noqa: E402


def _block(tree) -> None:
    jax.block_until_ready(tree)


def _skip_cost() -> Optional[str]:
    """Reason to skip the cost_analysis stage (a second lower+compile of
    both kernels), or None to run it."""
    if os.environ.get("ROOFLINE_SKIP_COST") not in (None, "", "0"):
        return "ROOFLINE_SKIP_COST=1"
    return None


def _cost(fn, *args) -> dict:
    """flops / bytes-accessed estimates from the compiled executable."""
    try:
        compiled = fn.lower(*args).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        keep = {}
        for k in ("flops", "bytes accessed", "transcendentals"):
            if k in ca:
                keep[k.replace(" ", "_")] = float(ca[k])
        return keep
    except Exception as e:  # pragma: no cover - platform-dependent API
        return {"error": f"{type(e).__name__}: {e}"[:160]}


def main() -> None:
    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"flush_roofline: no TPU (jax platform {platform!r}); not run")
    n_shares = int(os.environ.get("ROOFLINE_SHARES", "2048"))
    reps = int(os.environ.get("ROOFLINE_REPS", "3"))
    suite = BLSSuite()
    rng = random.Random(7)
    sks = SecretKeySet.random(2, rng, suite)
    pks = sks.public_keys()
    msg = b"hbbft-tpu benchmark epoch document"
    backend = tb.TpuBackend(suite)
    shares8 = [sks.secret_key_share(k).sign(msg) for k in range(8)]
    reqs = [
        VerifyRequest.sig_share(pks.public_key_share(i % 8), msg, shares8[i % 8])
        for i in range(n_shares)
    ]

    # Warm + correctness (compiles scan + pair buckets if cold).
    t0 = time.perf_counter()
    assert all(backend.verify_batch(reqs)), "warmup verification failed"
    warm_s = time.perf_counter() - t0

    # End-to-end.
    e2e = []
    for _ in range(reps):
        t0 = time.perf_counter()
        assert all(backend.verify_batch(reqs))
        e2e.append(time.perf_counter() - t0)

    # Stage split: scan (dispatch + block) vs pair (on the scan output),
    # chunked EXACTLY like verify_batch so the stage walls decompose the
    # same kernels the e2e numbers ran (an unchunked _scan_dev on
    # ROOFLINE_SHARES > CHUNK would compile and time a bucket production
    # never uses).
    chunks = [
        reqs[s : s + backend.CHUNK] for s in range(0, len(reqs), backend.CHUNK)
    ]
    scan_s, pair_s = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        parts = [backend._scan_dev(c) for c in chunks]
        _block(parts)
        scan_s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        ok = bool(backend._check_parts(parts))
        pair_s.append(time.perf_counter() - t0)
        assert ok

    # Cost analysis on the compiled kernels for these buckets, lowered
    # from the exact production inputs (_scan_prep is the same host prep
    # _scan_dev dispatches with).  ROOFLINE_SKIP_COST=1 skips it.
    costs = {}
    skip_reason = _skip_cost()
    if skip_reason is not None:
        costs["skipped"] = True
        costs["skip_reason"] = skip_reason
    else:
        try:
            buckets, args = backend._scan_prep(reqs[: backend.CHUNK])
            costs["scan_bucket"] = list(buckets)
            costs["scan"] = _cost(tb._scan_kernel(*buckets), *args)
            part = backend._scan_dev(reqs[: backend.CHUNK])
            npairs = int(part[1][3].shape[0])
            costs["pair_bucket"] = tb._pairs_bucket(npairs)
            costs["pair"] = _cost(tb._pair_kernel(npairs), part[1], part[2])
        except Exception as e:
            costs["error"] = f"{type(e).__name__}: {e}"[:200]

    out = {
        "config": "flush_roofline",
        "shares": n_shares,
        "chunk": backend.CHUNK,
        "device": jax.devices()[0].platform,
        "warm_first_call_s": round(warm_s, 2),
        "e2e_s": [round(x, 3) for x in e2e],
        "scan_stage_s": [round(x, 3) for x in scan_s],
        "pair_stage_s": [round(x, 3) for x in pair_s],
        "verifies_per_sec_best": round(n_shares / min(e2e), 1),
        "cost_analysis": costs,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
