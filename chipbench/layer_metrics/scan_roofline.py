"""Kernels: the least time the chip could take for the scan program's part
of a flush's traffic (every request's scalar multiplications and subgroup
checks by its kind: ``work.scan_fq_muls`` of the flush that was built) over
``scan_ms``.  With ``pair_roofline``'s it is ``work.fq_muls`` split in two;
same conversion to int8 operations, same peak.  Only where every request is
valid, as ``flush_roofline``."""

from chipbench.harness import peaks, reduce_spans, work


def share(obs, fq_muls, device_ms):
    """``fq_muls`` base-field products at the int8 peak over ``device_ms``,
    in percent."""
    peak = peaks.peaks_for(obs["device_kind"])["int8_ops_per_s"]
    least_s = fq_muls * work.INT8_OPS_PER_FQ_MUL / peak
    return least_s / (device_ms / 1e3) * 100.0


def read(obs):
    if int(obs["traffic"]["params"].get("wrong", 0)):
        return None
    scan_ms = reduce_spans.module_ms(obs, "scan")
    if scan_ms is None:
        return None
    return share(obs, work.scan_fq_muls(obs["work"].requests), scan_ms)
