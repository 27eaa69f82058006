"""Kernels: device time per flush of the scan program in a window whose
device trace holds ``jit_hbbft_scan_256_*``, the program of a 104-node
network's decrypt burst (256 G1 lanes, 16 G2 lanes): the events of the
modules line named ``jit_hbbft_scan_*`` (``reduce_spans.module_ms``, as
``scan_ms`` and ``scan32_ms`` read them).  A window without that module
gives nothing to read."""

from chipbench.harness import reduce_spans

MODULE = "jit_hbbft_scan_256_"


def read(obs):
    trace = obs["trace"]
    if trace is None or not any(n.startswith(MODULE) for n in trace["modules"]):
        return None
    return reduce_spans.module_ms(obs, "scan")
