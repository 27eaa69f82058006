"""Kernels: the least time the chip could take for the pair program's part
of a flush's traffic (one Miller loop per distinct pairing of the flush that
was built, their squarings shared, and one final exponentiation:
``work.pair_fq_muls``) over ``pair_ms``.  Only where every request is valid,
as ``flush_roofline``."""

from chipbench.harness import reduce_spans, work
from chipbench.layer_metrics.scan_roofline import share


def read(obs):
    if int(obs["traffic"]["params"].get("wrong", 0)):
        return None
    pair_ms = reduce_spans.module_ms(obs, "pair")
    if pair_ms is None:
        return None
    return share(obs, work.pair_fq_muls(obs["work"].pairs), pair_ms)
