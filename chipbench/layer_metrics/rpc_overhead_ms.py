"""RPC client and wire: what a flush costs outside the worker's flush.

(Sum of the client's ``verify_batch`` times, harness clock, minus the
worker's ``crypto.flush`` timer over the same flushes) / flushes.  It holds
the client's encode, the socket, the server's decode and the service's
batching window.
"""


def read(obs):
    if not obs["flushes"] or obs["worker_flushes"] != obs["flushes"]:
        return None
    return (obs["client_s"] - obs["worker_flush_s"]) / obs["flushes"] * 1e3
