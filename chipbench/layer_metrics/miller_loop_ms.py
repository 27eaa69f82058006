"""Kernels: device time per flush under the pair program's ``miller_loop``
scope: the union of the intervals of the device trace's ops that the
program's op-to-scope map puts there, the loop's body inside its ``while``
counted once (chipbench/harness/reduce_scopes.py)."""

from chipbench.harness import reduce_scopes


def read(obs):
    return reduce_scopes.scope_ms(obs, "miller_loop")
