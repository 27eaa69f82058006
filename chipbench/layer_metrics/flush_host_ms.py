"""Backend host work: the worker's flush time that the device was not busy
((``crypto.flush`` total - device busy time) / traced flushes): host prep,
dispatch, bisection's bookkeeping.  Where the device's window was closed
inside a flush, the flushes are the host-only window's and the device time is
``device_busy_ms``'s estimate for a whole flush."""

from chipbench.layer_metrics.device_busy_ms import cut_busy_s_per_flush


def read(obs):
    trace, host = obs["trace"], obs["host"]
    if obs["trace_cut"]:
        busy = cut_busy_s_per_flush(obs)
        if busy is None or not host["worker_flushes"]:
            return None
        return (host["worker_flush_s"] / host["worker_flushes"] - busy) * 1e3
    if trace is None or not obs["worker_flushes"]:
        return None
    return (obs["worker_flush_s"] - trace["busy_s"]) / obs["worker_flushes"] * 1e3
