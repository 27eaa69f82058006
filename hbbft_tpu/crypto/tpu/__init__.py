"""Accelerator crypto plane: the RLC flush kernel and its backend.

Import surface for callers (the crypto-plane worker, embedders):
``TpuBackend`` — the device flush.  Submodules (``curve``, ``fq``,
``fq2``, ``pairing``) are the kernel internals.
"""

from hbbft_tpu.crypto.tpu.backend import TpuBackend

__all__ = ["TpuBackend"]
