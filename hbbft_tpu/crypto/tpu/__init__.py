"""Accelerator crypto plane: the RLC flush kernel and its backends.

Import surface for callers (benchmarks, embedders): ``TpuBackend`` —
the device flush; ``HybridBackend`` — size-routed host/device with
dead-device failover.  Submodules (``curve``, ``fq``, ``fq2``,
``pairing``) are the kernel internals.
"""

from hbbft_tpu.crypto.tpu.backend import HybridBackend, TpuBackend

__all__ = ["HybridBackend", "TpuBackend"]
