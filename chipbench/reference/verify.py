"""Plain per-request share verification and share signing on wire bytes.

The reference of every cell: one request at a time, no batching, no
random linear combination, no endomorphism shortcuts.  A signature share
``sig`` of key share ``pk`` on document ``doc`` is valid iff both points
decode, lie on their curves and in the r-torsion (the definitional
``[r]P == O``), and ``e(pk, H(doc)) == e(g1, sig)``.

``miller_bits`` is the control's knob: the reference with its Miller loop
cut to the top ``miller_bits`` bits of ``|x|`` breaks the configuration's
"full Miller loop" guarantee (chipbench/tests/test_control.py); every
benchmark run uses the full loop.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from chipbench.reference import curve as C
from chipbench.reference import fields as F
from chipbench.reference import pairing as PR

G1_BYTES = 97
G2_BYTES = 193


def g1_to_bytes(jac: C.Jac) -> bytes:
    """The program's 97-byte affine G1 wire encoding."""
    aff = C.jac_to_affine(C.FQ_OPS, jac)
    if aff is None:
        return b"\x00" * G1_BYTES
    return b"\x01" + aff[0].to_bytes(48, "big") + aff[1].to_bytes(48, "big")


def g2_to_bytes(jac: C.Jac) -> bytes:
    """The program's 193-byte affine G2 wire encoding."""
    aff = C.jac_to_affine(C.FQ2_OPS, jac)
    if aff is None:
        return b"\x00" * G2_BYTES
    (x0, x1), (y0, y1) = aff
    return b"\x01" + b"".join(v.to_bytes(48, "big") for v in (x0, x1, y0, y1))


def _decode(data: bytes, fq2: bool) -> Optional[tuple]:
    """Affine point from wire bytes, or None for identity or malformed
    bytes (a share at the identity never verifies)."""
    coords = 4 if fq2 else 2
    if len(data) != 1 + 48 * coords or data[0] != 1:
        return None
    vals = [int.from_bytes(data[1 + 48 * i: 49 + 48 * i], "big") for i in range(coords)]
    if any(v >= F.P for v in vals):
        return None
    if fq2:
        return ((vals[0], vals[1]), (vals[2], vals[3]))
    return (vals[0], vals[1])


def _miller(p_aff, q_aff, miller_bits: Optional[int]) -> F.Fq12E:
    if miller_bits is None:
        return PR.miller_loop(p_aff, q_aff)
    saved = PR._X_BITS
    PR._X_BITS = saved[:miller_bits]
    try:
        return PR.miller_loop(p_aff, q_aff)
    finally:
        PR._X_BITS = saved


class Reference:
    """Verifies requests given as ``(pk_bytes, doc, sig_bytes)``."""

    def __init__(self, miller_bits: Optional[int] = None) -> None:
        self.miller_bits = miller_bits
        self._hashed: Dict[bytes, Tuple] = {}

    def _hash_affine(self, doc: bytes):
        if doc not in self._hashed:
            self._hashed[doc] = C.jac_to_affine(C.FQ2_OPS, C.hash_to_g2(doc))
        return self._hashed[doc]

    def verify(self, pk_bytes: bytes, doc: bytes, sig_bytes: bytes) -> bool:
        pk = _decode(pk_bytes, fq2=False)
        sig = _decode(sig_bytes, fq2=True)
        if pk is None or sig is None:
            return False
        if not C.g1_on_curve(*pk) or not C.g2_on_curve(*sig):
            return False
        if not C.in_subgroup_slow(C.FQ_OPS, (pk[0], pk[1], 1)):
            return False
        if not C.in_subgroup_slow(C.FQ2_OPS, (sig[0], sig[1], F.FQ2_ONE)):
            return False
        neg_g1 = C.jac_to_affine(C.FQ_OPS, C.jac_neg(C.FQ_OPS, C.G1_GEN))
        f = F.fq12_mul(
            _miller(pk, self._hash_affine(doc), self.miller_bits),
            _miller(neg_g1, sig, self.miller_bits),
        )
        return F.fq12_is_one(PR.final_exponentiation(f))


def sign(secret: int, doc_point: C.Jac) -> C.Jac:
    """A signature share: ``secret * H(doc)``."""
    return C.jac_mul(C.FQ2_OPS, doc_point, secret)


def public_share(secret: int) -> C.Jac:
    return C.jac_mul(C.FQ_OPS, C.G1_GEN, secret)
