"""The benchmark's worker entry over a ``TpuBackend`` whose two programs are
stubs, for a traced run on the CPU backend in seconds.

``python stub_kernel_worker_entry.py oracle <worker args...>`` (called as
``control_worker_entry.py`` is, with its one mode first) replaces
``_scan_kernel`` and ``_pair_kernel`` (minutes of XLA compile each) and runs
the entry unchanged.  Everything around the two calls is the program's own:
the spans, the counters, host prep, bisection.  The stub's verdict on an
aggregate check is the oracle's on the requests that check was prepared
from, so the run's answers are honest and ``correct`` holds.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench.harness import worker_entry  # noqa: E402


def patch() -> None:
    import jax.numpy as jnp

    from hbbft_tpu.crypto.tpu import backend as B
    from hbbft_tpu.crypto.tpu import curve as dc

    checked = []  # the requests of the check being made
    honest_prep = B.TpuBackend._scan_prep

    def scan_prep(self, reqs):
        checked[:] = [self, list(reqs)]
        return honest_prep(self, reqs)

    def scan_kernel(n1, n2, nl):
        return lambda *args: (
            jnp.asarray(True),
            dc.identity(dc.G1_OPS, (1 + nl,)),
            dc.identity(dc.G2_OPS, (1 + nl,)),
        )

    def pair_kernel(n_pairs):
        def run(lhs, rhs):
            backend, reqs = checked
            return jnp.asarray(all(backend._eager.verify_batch(reqs)))

        return run

    B.TpuBackend._scan_prep = scan_prep
    B._scan_kernel = scan_kernel
    B._pair_kernel = pair_kernel
    B._compile_pair_kernel_early = lambda n_pairs: None


if __name__ == "__main__":
    if sys.argv[1] != "oracle":
        raise SystemExit(f"unknown mode {sys.argv[1]!r}")
    patch()
    sys.exit(worker_entry.main(sys.argv[2:]))
