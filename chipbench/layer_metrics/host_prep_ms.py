"""Backend: host preparation per flush, the ``crypto.tpu.well_formed`` and
``crypto.tpu.scan_prep`` spans of a flush summed (one ``scan_prep`` per
aggregate check: coefficients, hash-to-G2, packing)."""

from chipbench.harness import reduce_spans


def read(obs):
    return reduce_spans.span_ms(
        obs, "crypto.tpu.well_formed", "crypto.tpu.scan_prep"
    )
