"""Kernels: device time per flush under the scan program's ``g2_stage``
scope: the stage's predicate and its ``conditional``, with the ``scan_g2``,
``subgroup`` and ``leg_sums`` inside it (chipbench/harness/reduce_scopes.py).

The persistent compile cache's key leaves scopes out, so a process may run
an executable compiled before ``g2_stage`` existed; its map then holds the
G2 scan and chain as a top-level ``scan_g2``, which is read instead: the
stage less its r-torsion verdicts, its sum and the ``conditional``'s own
time."""

from chipbench.harness import reduce_scopes


def read(obs):
    return reduce_scopes.scope_ms(obs, "g2_stage", "scan_g2")
