"""Kernels: the least time the chip could take for the scan program's part of
a burst of 103 decryption shares (``work.scan_fq_muls`` of the flush that was
built: two G1 scalar multiplications and one G1 subgroup check a
``dec_share``, 103 x 4204 products) over ``scan256_ms``: ``scan_roofline``'s
own reading (same work, same conversion to int8 operations, same peak, only
where every request is valid) in a window whose scan program is
``jit_hbbft_scan_256_*``, and nothing elsewhere."""

from chipbench.layer_metrics import scan256_ms, scan_roofline


def read(obs):
    if scan256_ms.read(obs) is None:
        return None
    return scan_roofline.read(obs)
