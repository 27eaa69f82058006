"""RPC client and wire: the server's decode of a burst's frame, the
``crypto.rpc.decode`` spans of the RPCs that the flush merged, summed
(``rpc_server_decode_ms``'s reading, whose list of cells is the ``coin16``
ones): serde and, for every point the memo has not seen, the on-curve and
r-torsion checks in Python integers, about 1 ms a G1 point, so it grows with
the validator set where the device's time does not.  A program without the
span gives nothing to read."""

from chipbench.harness import reduce_spans


def read(obs):
    return reduce_spans.span_ms(obs, "crypto.rpc.decode")
