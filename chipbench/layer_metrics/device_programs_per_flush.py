"""Backend: program launches on the device per flush (events on the
trace's XLA-modules line / traced flushes); bisection re-flushes raise it."""


def read(obs):
    trace = obs["trace"]
    if trace is None or not obs["flushes"] or not trace["launches"] or obs["trace_cut"]:
        return None
    return trace["launches"] / obs["flushes"]
