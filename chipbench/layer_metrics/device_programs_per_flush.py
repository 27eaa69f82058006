"""Backend: program launches on the device per flush; bisection re-flushes
raise it.  Events on the device trace's XLA-modules line / traced flushes
or, where the device's window was closed inside a flush, the runtime's
launch events in the host-only window / its whole flushes."""


def read(obs):
    trace, host = obs["trace"], obs["host"]
    if obs["trace_cut"]:
        if not host or not host["flushes"] or not host["launches"]:
            return None
        obs["notes"]["device_programs_from"] = "host_only_window"
        return host["launches"] / host["flushes"]
    if trace is None or not obs["flushes"] or not trace["launches"]:
        return None
    return trace["launches"] / obs["flushes"]
