"""Kernels: device busy time per flush (union of the device's op intervals
in the traced window / traced flushes)."""


def read(obs):
    trace = obs["trace"]
    if trace is None or not obs["flushes"] or not trace["busy_s"] or obs["trace_cut"]:
        return None
    return trace["busy_s"] / obs["flushes"] * 1e3
