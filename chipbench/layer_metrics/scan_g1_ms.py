"""Kernels: device time per flush under the scan program's ``scan_g1``
scope, the G1 rows' 128-step scan (chipbench/harness/reduce_scopes.py)."""

from chipbench.harness import reduce_scopes


def read(obs):
    return reduce_scopes.scope_ms(obs, "scan_g1")
