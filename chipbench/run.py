"""The benchmark's one command.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of its standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and with
``--trace 1`` ``breakdown``).  Exits non-zero, printing no result, where the
worker finds no TPU or the program under test is not in the checkout.  A run
cut by SIGTERM ends its worker and its helpers before it exits (code 143).
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, as near as Python lets us stand

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _cut(signum, frame) -> None:
    """SIGTERM raises into ``run_cell``, so its ``finally`` ends the helpers
    and the worker; a second SIGTERM does not interrupt that."""
    signal.signal(signal.SIGTERM, signal.SIG_IGN)
    raise SystemExit(128 + signum)


def main(argv=None, bench=None, **run_cell_kw) -> int:
    """``bench`` (in place of ``BENCHMARK.json``) and ``run_cell_kw`` are for
    the benchmark's own tests: the command line sets neither."""
    signal.signal(signal.SIGTERM, _cut)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    try:
        if bench is None:
            with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
                bench = json.load(f)
        import hbbft_tpu  # noqa: F401  (the program under test)
        from chipbench.harness.bench import run_cell
    except (OSError, ValueError, ImportError) as e:
        print(f"chipbench: cannot start: {e!r}", file=sys.stderr)
        return 2
    return run_cell(
        bench, args.workload, args.seed, args.seconds, bool(args.trace), t0=T0,
        **run_cell_kw,
    )


if __name__ == "__main__":
    sys.exit(main())
