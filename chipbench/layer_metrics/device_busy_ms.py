"""Kernels: device busy time per flush.  The union of the device's op
intervals in the traced window / traced flushes or, where the device's
window was closed inside a flush, the launches of a whole flush (host-only
window) times the mean time of a program on the device's modules line, taken
over whole aggregate checks of the device's window."""


def cut_busy_s_per_flush(obs):
    """Device time of one whole flush where the device's window holds only
    a part of one; None where either window has nothing to read."""
    trace, host = obs["trace"], obs["host"]
    if trace is None or not trace.get("module_s_per_launch"):
        return None
    if not host or not host["flushes"] or not host["launches"]:
        return None
    return host["launches"] / host["flushes"] * trace["module_s_per_launch"]


def read(obs):
    trace = obs["trace"]
    if obs["trace_cut"]:
        busy = cut_busy_s_per_flush(obs)
        if busy is None:
            return None
        obs["notes"]["device_busy_from"] = "launches_x_module_time"
        return busy * 1e3
    if trace is None or not obs["flushes"] or not trace["busy_s"]:
        return None
    return trace["busy_s"] / obs["flushes"] * 1e3
