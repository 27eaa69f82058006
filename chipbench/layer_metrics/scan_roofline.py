"""Kernels: the least time the chip could take for the scan program's part
of a flush's traffic (per share a scalar multiplication in G1 and in G2 and
a G2 subgroup check: ``work.PER_SHARE``) over ``scan_ms``.  With
``pair_roofline``'s it is ``work.fq_muls`` split in two; same conversion to
int8 operations, same peak.  Only where every request is valid, as
``flush_roofline``."""

from chipbench.harness import peaks, reduce_spans, work


def least_fq_muls(kind, requests):
    return requests * work.PER_SHARE[kind]


def share(obs, fq_muls, device_ms):
    """``fq_muls`` base-field products at the int8 peak over ``device_ms``,
    in percent."""
    peak = peaks.peaks_for(obs["device_kind"])["int8_ops_per_s"]
    least_s = fq_muls * work.INT8_OPS_PER_FQ_MUL / peak
    return least_s / (device_ms / 1e3) * 100.0


def read(obs):
    params = obs["traffic"]["params"]
    if int(params.get("wrong", 0)):
        return None
    scan_ms = reduce_spans.module_ms(obs, "scan")
    if scan_ms is None:
        return None
    return share(
        obs, least_fq_muls(obs["config"]["share_kind"], int(params["requests"])),
        scan_ms,
    )
