"""Backend: packing per flush, the ``crypto.tpu.pack`` spans of a flush summed
(inside ``scan_prep``, around ``TpuBackend._pack``: the legs' points as limbs,
the coefficients as bit planes, the masks, padded to the scan program's lanes
and put on the device, in Python integers a row).  Every check of a flush
packs the lanes of the flush's program, whatever its own rows.  A program
without the span gives nothing to read."""

from chipbench.harness import reduce_spans


def read(obs):
    return reduce_spans.span_ms(obs, "crypto.tpu.pack")
