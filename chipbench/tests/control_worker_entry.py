"""Worker entries with the timed path replaced or broken, for the tests.

``python control_worker_entry.py <mode> <worker args...>`` patches the
``eager`` backend that the worker is about to build and then runs the
benchmark's worker entry unchanged:

* ``control``: the plain reference put in the program's place, every
  request judged by its own kind's verifier (``chipbench/kinds``), with the
  Miller loops cut to the top 32 of 63 steps: the configuration's "full
  Miller loop" guarantee broken, the step that would tempt a later PR;
* ``flip``: one answer of every flush altered where it is produced;
* ``accept``: verification skipped, every answer True.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from hbbft_tpu.crypto import backend as program_backend  # noqa: E402

from chipbench import kinds  # noqa: E402
from chipbench.harness import worker_entry  # noqa: E402
from chipbench.reference.verify import Reference  # noqa: E402

CONTROL_MILLER_BITS = 32


def patch(mode: str) -> None:
    honest = program_backend.EagerBackend.verify_batch
    if mode == "control":
        reference = Reference(miller_bits=CONTROL_MILLER_BITS)

        def verify_batch(self, reqs):
            return [
                kinds.load(r.kind).verify(reference, *kinds.load(r.kind).wire_of(r))
                for r in reqs
            ]
    elif mode == "flip":
        def verify_batch(self, reqs):
            got = honest(self, reqs)
            got[-1] = not got[-1]
            return got
    elif mode == "accept":
        def verify_batch(self, reqs):
            return [True] * len(reqs)
    else:
        raise SystemExit(f"unknown mode {mode!r}")
    program_backend.EagerBackend.verify_batch = verify_batch


if __name__ == "__main__":
    patch(sys.argv[1])
    sys.exit(worker_entry.main(sys.argv[2:]))
