"""Kernels: the least time the chip could take for a flush's traffic over
the device time it took (chipbench/harness/work.py, peaks.py), the traffic
being the composition of the flush that was built (``obs["work"]``).

Only where every request is valid: a faulty round's re-flushes are device
time that the answer's least work does not hold, so the share would read
the bisection and not the kernels.
"""

from chipbench.harness import peaks, work


def read(obs):
    trace = obs["trace"]
    params = obs["traffic"]["params"]
    if trace is None or not obs["flushes"] or not trace["busy_s"] or obs["trace_cut"]:
        return None
    if int(params.get("wrong", 0)):
        return None
    least = work.least_seconds(obs["work"], peaks.peaks_for(obs["device_kind"]))
    obs["notes"]["roofline_bound"] = least["bound"]
    obs["notes"]["roofline_least_s"] = least["seconds"]
    return least["seconds"] / (trace["busy_s"] / obs["flushes"]) * 100.0
