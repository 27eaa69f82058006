"""Kernels: the least time the chip could take for the pair program's part
of a flush's traffic (``1 + documents`` Miller loops that share their
squarings and one final exponentiation, chipbench/harness/work.py) over
``pair_ms``.  Only where every request is valid, as ``flush_roofline``."""

from chipbench.harness import reduce_spans, work
from chipbench.layer_metrics.scan_roofline import share


def least_fq_muls(documents):
    return (
        work.MILLER_SHARED_SQUARINGS
        + (1 + documents) * work.MILLER_PER_PAIR
        + work.FINAL_EXP
    )


def read(obs):
    if int(obs["traffic"]["params"].get("wrong", 0)):
        return None
    pair_ms = reduce_spans.module_ms(obs, "pair")
    if pair_ms is None:
        return None
    return share(obs, least_fq_muls(obs["documents_per_flush"]), pair_ms)
