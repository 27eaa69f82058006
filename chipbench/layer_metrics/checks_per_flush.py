"""Backend: aggregate checks per flush, the ``crypto.tpu.check`` spans of a
flush counted: 1 where the flush passes whole, more where it bisects."""

from chipbench.harness import reduce_spans


def read(obs):
    return reduce_spans.span_count(obs, "crypto.tpu.check")
