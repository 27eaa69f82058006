"""Kernels: device time per flush of the pair program, the events of the
device trace's modules line named ``jit_hbbft_pair_*``
(chipbench/harness/reduce_spans.py)."""

from chipbench.harness import reduce_spans


def read(obs):
    return reduce_spans.module_ms(obs, "pair")
