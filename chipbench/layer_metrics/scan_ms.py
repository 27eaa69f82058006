"""Kernels: device time per flush of the scan program, the events of the
device trace's modules line named ``jit_hbbft_scan_*``
(chipbench/harness/reduce_spans.py says how a flush's share is found where
the device's window was closed inside the flush)."""

from chipbench.harness import reduce_spans


def read(obs):
    return reduce_spans.module_ms(obs, "scan")
