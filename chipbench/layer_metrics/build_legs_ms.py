"""Backend: leg construction per flush, the ``crypto.tpu.build_legs`` spans of
a flush summed (inside ``scan_prep``, around ``_build_legs``: for a decrypt
burst the ciphertext's ``hash_input`` and canonical bytes once a request, the
negated key shares, and inside it the ``hash_to_g2`` spans).  A program
without the span gives nothing to read."""

from chipbench.harness import reduce_spans


def read(obs):
    return reduce_spans.span_ms(obs, "crypto.tpu.build_legs")
