"""Backend: hashing documents to G2 on the host per flush, the
``crypto.tpu.hash_to_g2`` spans of a flush summed (inside ``scan_prep``)."""

from chipbench.harness import reduce_spans


def read(obs):
    return reduce_spans.span_ms(obs, "crypto.tpu.hash_to_g2")
