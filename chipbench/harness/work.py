"""The least work a flush's traffic needs, counted in base-field products.

The roofline's numerator is the traffic's work, not the program's: it is
computed from the number of requests and distinct documents alone, with no
bucket padding, no bisection re-flush and nothing from XLA's cost analysis,
so it reads the same whatever later implements the flush.

What the batch-verification equation needs for ``n`` signature shares over
``d`` documents (the random-linear-combination check
``e(g1, sum c_i sig_i) * prod_d e(sum_{i in d} c_i (-pk_i), H_d) == 1``):

* per share, one 128-bit scalar multiplication in G1 (the key share) and
  one in G2 (the share), and one G2 subgroup check of the wire-sourced share;
* ``1 + d`` Miller loops that share their squarings;
* one final exponentiation.

Hashing a document to G2 runs on the host in this system and is not counted.

Costs are textbook formula costs in base-field multiplications (``m``),
counting a squaring as a multiplication, an Fq2 product as 3 m (Karatsuba)
and an Fq2 square as 2 m (complex squaring).
"""

from __future__ import annotations

import math
from typing import Dict

FQ2_MUL = 3
FQ2_SQR = 2

# Jacobian formulas on y^2 = x^3 + b (a = 0), Explicit-Formulas Database:
# dbl-2009-l is 2M + 5S; madd-2007-bl (affine second operand) is 7M + 4S.
G1_DOUBLE = 2 + 5
G1_MIXED_ADD = 7 + 4
G2_DOUBLE = 2 * FQ2_MUL + 5 * FQ2_SQR
G2_MIXED_ADD = 7 * FQ2_MUL + 4 * FQ2_SQR

# Double-and-add over a uniformly random 128-bit scalar: 127 doublings and,
# on average, 64 additions.
RLC_BITS = 128
G1_SCALAR_MUL = (RLC_BITS - 1) * G1_DOUBLE + (RLC_BITS // 2) * G1_MIXED_ADD
G2_SCALAR_MUL = (RLC_BITS - 1) * G2_DOUBLE + (RLC_BITS // 2) * G2_MIXED_ADD

# |x| = 0xd201000000010000 of BLS12-381: 64 bits, Hamming weight 6.
X_DOUBLINGS = 63
X_ADDITIONS = 5

# G2 membership by psi(Q) == [x]Q (Scott, "A note on group membership tests
# for G1, G2 and GT on BLS pairing-friendly curves", 2021): one
# multiplication by |x|, and psi as two Fq2 products by constants.
G2_SUBGROUP_CHECK = (
    X_DOUBLINGS * G2_DOUBLE + X_ADDITIONS * G2_MIXED_ADD + 2 * FQ2_MUL
)

# Miller loop, projective coordinates on the twist (Aranha, Karabina,
# Longa, Gebotys, Lopez, "Faster explicit formulas for computing pairings
# over ordinary curves", Eurocrypt 2011, section 4): doubling step
# 3 m~ + 6 s~ + 4 m, addition step 11 m~ + 2 s~ + 4 m, each followed by a
# sparse Fq12 product of 13 m~; one Fq12 squaring per doubling (12 m~ by two
# Fq6 products), shared by all the pairs of a product of pairings.
MILLER_DOUBLE_STEP = 3 * FQ2_MUL + 6 * FQ2_SQR + 4 + 13 * FQ2_MUL
MILLER_ADD_STEP = 11 * FQ2_MUL + 2 * FQ2_SQR + 4 + 13 * FQ2_MUL
MILLER_PER_PAIR = X_DOUBLINGS * MILLER_DOUBLE_STEP + X_ADDITIONS * MILLER_ADD_STEP
MILLER_SHARED_SQUARINGS = X_DOUBLINGS * 12 * FQ2_MUL

# Final exponentiation.  Easy part: one Fq12 inversion (about 97 m and one
# Fq inversion, the latter by Fermat as 380 squarings and about 190
# products), two Fq12 products of 18 m~ and a Frobenius of 5 m~.  Hard part
# for BLS12 (Hayashida, Hayasaka, Teruya, "Efficient final exponentiation
# via cyclotomic structure for pairings over families of elliptic curves",
# 2020): five exponentiations by |x|, each 63 cyclotomic squarings of
# 9 s~ (Granger, Scott, 2010) and 5 Fq12 products, and about 12 more Fq12
# products and Frobenius maps.
FQ12_MUL = 18 * FQ2_MUL
FINAL_EXP_EASY = 97 + 570 + 2 * FQ12_MUL + 5 * FQ2_MUL
EXP_BY_X = X_DOUBLINGS * 9 * FQ2_SQR + X_ADDITIONS * FQ12_MUL
FINAL_EXP = FINAL_EXP_EASY + 5 * EXP_BY_X + 12 * FQ12_MUL

PER_SHARE: Dict[str, int] = {
    "sig_share": G1_SCALAR_MUL + G2_SCALAR_MUL + G2_SUBGROUP_CHECK,
}

#: Wire bytes of one request: a 97-byte G1 key share and a 193-byte G2 share.
WIRE_BYTES: Dict[str, int] = {"sig_share": 97 + 193}

#: int8 operations that stand for one base-field product: a 381-bit product
#: as ceil(381 / 8) ** 2 byte products, a multiply and an add each, and three
#: such products for one Montgomery multiplication (each configuration's
#: file lists this conversion under ``assumed``).
INT8_OPS_PER_FQ_MUL = 3 * 2 * math.ceil(381 / 8) ** 2


def fq_muls(kind: str, n_requests: int, n_documents: int) -> int:
    """Base-field multiplications that verifying ``n_requests`` shares of
    ``kind`` over ``n_documents`` documents needs at the least."""
    if kind not in PER_SHARE:
        raise KeyError(f"no work formula for request kind {kind!r}")
    if n_requests < 1 or n_documents < 1:
        raise ValueError("a flush has at least one request and one document")
    pairs = 1 + n_documents
    return (
        n_requests * PER_SHARE[kind]
        + MILLER_SHARED_SQUARINGS
        + pairs * MILLER_PER_PAIR
        + FINAL_EXP
    )


def least_seconds(
    kind: str, n_requests: int, n_documents: int, document_bytes: int,
    peaks: Dict[str, float],
) -> Dict[str, float]:
    """The least time one chip could take for the flush: the larger of its
    operations over the int8 peak and its wire bytes over the HBM peak.
    Returns both, the larger as ``seconds`` and its name as ``bound``."""
    compute_s = (
        fq_muls(kind, n_requests, n_documents) * INT8_OPS_PER_FQ_MUL
        / peaks["int8_ops_per_s"]
    )
    wire = n_requests * WIRE_BYTES[kind] + n_documents * document_bytes
    memory_s = wire / peaks["hbm_bytes_per_s"]
    bound = "compute_int8" if compute_s >= memory_s else "memory_hbm"
    return {
        "seconds": max(compute_s, memory_s),
        "bound": bound,
        "compute_s": compute_s,
        "memory_s": memory_s,
    }
