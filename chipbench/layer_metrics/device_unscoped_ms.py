"""Kernels: device time per flush that no named scope claims: the scan and
pair programs' ops outside their scopes, the join program and any other
module (chipbench/harness/reduce_scopes.py).  None where the program wrote
no op-to-scope map."""

from chipbench.harness import reduce_scopes


def read(obs):
    return reduce_scopes.unscoped_ms(obs)
