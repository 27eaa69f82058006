"""Plain per-request verification, signing and threshold encryption on wire bytes.

The reference of every cell: one request at a time, no batching, no
random linear combination, no endomorphism shortcuts.  A point is accepted
iff it decodes, lies on its curve and is in the r-torsion by the
definitional ``[r]P == O``; the point at infinity never verifies.

* :meth:`Reference.sig_share`: ``e(pk, H(doc)) == e(g1, sig)``.
* :meth:`Reference.dec_share`: ``e(share, H(U, V)) == e(pk, W)``.
* :meth:`Reference.ciphertext`: ``e(g1, W) == e(U, H(U, V))``.

``H(U, V)`` is the program's own map on the program's own hash input
(``canonical_bytes(b"ct", U.to_bytes(), V)``, hbbft_tpu/crypto/keys.py),
written out again in :func:`ciphertext_hash_input`.

A request kind is judged through ``chipbench/kinds/<kind>.py``, which calls
one of these; a further kind writes its equation there from
:meth:`Reference.g1`, :meth:`Reference.g2`, :meth:`Reference.hashed` and
:meth:`Reference.pairings_equal`.

``miller_bits`` is the control's knob: the reference with its Miller loops
cut to the top ``miller_bits`` bits of ``|x|`` breaks the configuration's
"full Miller loop" guarantee (chipbench/tests/control_worker_entry.py);
every benchmark run uses the full loop.
"""

from __future__ import annotations

import hashlib
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

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


def canonical_bytes(*parts: bytes) -> bytes:
    """The program's framing of a hash input: every part behind its length
    as 8 big-endian bytes (hbbft_tpu/utils/__init__.py, byte strings only)."""
    return b"".join(len(p).to_bytes(8, "big") + p for p in parts)


def ciphertext_hash_input(u_bytes: bytes, v: bytes) -> bytes:
    """What the program hashes to G2 for a ciphertext ``(U, V, W)``."""
    return canonical_bytes(b"ct", u_bytes, v)


_G1_AFFINE = (C.G1_GEN[0], C.G1_GEN[1])


class Reference:
    """Verifies one request from its wire bytes."""

    def __init__(self, miller_bits: Optional[int] = None) -> None:
        self.miller_bits = miller_bits
        self._hashed: Dict[bytes, Tuple] = {}

    # -- what every kind's equation is made of --------------------------

    def g1(self, data: bytes) -> Optional[Tuple[int, int]]:
        """The affine G1 point that ``data`` encodes, or None where it does
        not decode, is the point at infinity, is off the curve or is outside
        the r-torsion."""
        point = _decode(data, fq2=False)
        if point is None or not C.g1_on_curve(*point):
            return None
        if not C.in_subgroup_slow(C.FQ_OPS, (point[0], point[1], 1)):
            return None
        return point

    def g2(self, data: bytes) -> Optional[Tuple[F.Fq2E, F.Fq2E]]:
        """As :meth:`g1`, for a G2 point on the twist."""
        point = _decode(data, fq2=True)
        if point is None or not C.g2_on_curve(*point):
            return None
        if not C.in_subgroup_slow(C.FQ2_OPS, (point[0], point[1], F.FQ2_ONE)):
            return None
        return point

    def hashed(self, data: bytes) -> Tuple[F.Fq2E, F.Fq2E]:
        """The program's hash-to-G2 of ``data``, affine."""
        if data not in self._hashed:
            self._hashed[data] = C.jac_to_affine(C.FQ2_OPS, C.hash_to_g2(data))
        return self._hashed[data]

    def pairings_equal(self, p1, q1, p2, q2) -> bool:
        """``e(p1, q1) == e(p2, q2)`` for affine points of the two groups,
        as ``e(p1, q1) * e(-p2, q2) == 1``: two Miller loops, one final
        exponentiation."""
        f = F.fq12_mul(
            _miller(p1, q1, self.miller_bits),
            _miller((p2[0], -p2[1] % F.P), q2, self.miller_bits),
        )
        return F.fq12_is_one(PR.final_exponentiation(f))

    # -- the three kinds the program carries ----------------------------

    def sig_share(self, pk_bytes: bytes, doc: bytes, sig_bytes: bytes) -> bool:
        pk, sig = self.g1(pk_bytes), self.g2(sig_bytes)
        if pk is None or sig is None:
            return False
        return self.pairings_equal(pk, self.hashed(doc), _G1_AFFINE, sig)

    def dec_share(
        self, pk_bytes: bytes, u_bytes: bytes, v: bytes, w_bytes: bytes,
        share_bytes: bytes,
    ) -> bool:
        pk, u, share = self.g1(pk_bytes), self.g1(u_bytes), self.g1(share_bytes)
        w = self.g2(w_bytes)
        if pk is None or u is None or share is None or w is None:
            return False
        h = self.hashed(ciphertext_hash_input(u_bytes, v))
        return self.pairings_equal(share, h, pk, w)

    def ciphertext(self, u_bytes: bytes, v: bytes, w_bytes: bytes) -> bool:
        u, w = self.g1(u_bytes), self.g2(w_bytes)
        if u is None or w is None:
            return False
        h = self.hashed(ciphertext_hash_input(u_bytes, v))
        return self.pairings_equal(_G1_AFFINE, w, u, h)


# -- what a generator needs: keys, signing, threshold encryption ------------


def sign(secret: int, doc_point: C.Jac) -> C.Jac:
    """A signature share: ``secret * H(doc)``."""
    return C.jac_mul(C.FQ2_OPS, doc_point, secret)


def public_share(secret: int) -> C.Jac:
    return C.jac_mul(C.FQ_OPS, C.G1_GEN, secret)


class Ciphertext(NamedTuple):
    """``(U, V, W)`` as points, and as the bytes a request carries."""

    u: C.Jac
    v: bytes
    w: C.Jac
    u_bytes: bytes
    w_bytes: bytes


def _kem_mask(point: C.Jac, n: int) -> bytes:
    """The program's mask of ``n`` bytes from a G1 point: SHA3-256 in
    counter mode over ``canonical_bytes(b"kem", point bytes)``."""
    seed = canonical_bytes(b"kem", g1_to_bytes(point))
    out = b""
    ctr = 0
    while len(out) < n:
        out += hashlib.sha3_256(seed + ctr.to_bytes(8, "big")).digest()
        ctr += 1
    return out[:n]


def _xor(a: bytes, b: bytes) -> bytes:
    return bytes(x ^ y for x, y in zip(a, b))


def encrypt(master_pk: C.Jac, msg: bytes, r: int) -> Ciphertext:
    """The program's threshold encryption of ``msg`` to the key set's public
    key with randomness ``r``: ``U = r g1``, ``V = msg xor mask(r pk)``,
    ``W = r H(U, V)``."""
    u = C.jac_mul(C.FQ_OPS, C.G1_GEN, r)
    v = _xor(msg, _kem_mask(C.jac_mul(C.FQ_OPS, master_pk, r), len(msg)))
    u_bytes = g1_to_bytes(u)
    w = C.jac_mul(C.FQ2_OPS, C.hash_to_g2(ciphertext_hash_input(u_bytes, v)), r)
    return Ciphertext(u, v, w, u_bytes, g2_to_bytes(w))


def decryption_share(secret: int, u: C.Jac) -> C.Jac:
    """A decryption share: ``secret * U``."""
    return C.jac_mul(C.FQ_OPS, u, secret)


def combine_decryption_shares(
    indices: Sequence[int], shares: Sequence[C.Jac], v: bytes
) -> bytes:
    """The plaintext from threshold + 1 shares: Lagrange interpolation in
    the exponent at 0 (the holder of ``indices[k]`` has the polynomial at
    ``indices[k] + 1``), then the mask taken off ``V``."""
    acc = C.jac_identity(C.FQ_OPS)
    xs = [i + 1 for i in indices]
    for xk, share in zip(xs, shares):
        num = den = 1
        for xj in xs:
            if xj != xk:
                num = num * xj % F.R
                den = den * (xj - xk) % F.R
        lam = num * pow(den, -1, F.R) % F.R
        acc = C.jac_add(C.FQ_OPS, acc, C.jac_mul(C.FQ_OPS, share, lam))
    return _xor(v, _kem_mask(acc, len(v)))
