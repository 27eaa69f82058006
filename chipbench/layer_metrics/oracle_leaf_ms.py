"""Backend: bisection's oracle leaves per flush, the ``crypto.tpu.leaf``
spans of a flush summed (one request each, verified in pure Python)."""

from chipbench.harness import reduce_spans


def read(obs):
    return reduce_spans.span_ms(obs, "crypto.tpu.leaf")
