"""Kernels: device time per flush under the pair program's ``final_exp``
scope, its five ``pow_x_abs`` loops included (chipbench/harness/
reduce_scopes.py)."""

from chipbench.harness import reduce_scopes


def read(obs):
    return reduce_scopes.scope_ms(obs, "final_exp")
