"""Backend host work: the worker's flush time that the device was not busy
((``crypto.flush`` total - device busy time) / traced flushes): host prep,
dispatch, bisection's oracle leaves."""


def read(obs):
    trace = obs["trace"]
    if trace is None or not obs["worker_flushes"] or obs["trace_cut"]:
        return None
    return (obs["worker_flush_s"] - trace["busy_s"]) / obs["worker_flushes"] * 1e3
