"""RPC client and wire: the server's decode of a flush's requests, the
``crypto.rpc.decode`` spans of the RPCs that the flush merged, summed: serde
and, for every point, the on-curve and r-torsion checks in pure Python."""

from chipbench.harness import reduce_spans


def read(obs):
    return reduce_spans.span_ms(obs, "crypto.rpc.decode")
