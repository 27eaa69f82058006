"""The least work a flush's traffic needs, counted in base-field products.

The roofline's numerator is the traffic's work, not the program's: it is
computed from the flush's requests by kind and from the distinct documents
and ciphertexts among them alone, with no bucket padding, no bisection
re-flush and nothing from XLA's cost analysis, so it reads the same whatever
later implements the flush.

What the batch-verification equation needs (the random-linear-combination
check over signature shares ``sig_i`` of keys ``pk_i`` on documents ``d``,
decryption shares ``share_j`` of keys ``pk_j`` on ciphertexts ``ct`` and
ciphertext checks ``(U_k, V_k, W_k)``)::

    e(g1, sum c_i sig_i + sum c_k W_k)
      * prod_d  e(sum_{i in d} c_i (-pk_i), H_d)
      * prod_ct e(sum_{j in ct} c_j share_j - sum_{k in ct} c_k U_k, H_ct)
      * prod_ct e(sum_{j in ct} c_j (-pk_j), W_ct)  ==  1

* per request the scalar multiplications and subgroup checks of its kind
  (``SCAN_FQ_MULS`` in ``chipbench/kinds/<kind>.py``): the scan program's
  part;
* one Miller loop per distinct second argument above (a kind's
  ``pairs``): the generator's only where a signature share or a ciphertext
  check is in the flush, one per distinct document, one per distinct
  ``H_ct``, one per distinct ``W_ct`` that a decryption share brings; the
  loops share their squarings;
* one final exponentiation.  Loops and exponentiation are the pair
  program's part; the two parts sum to the whole.

Hashing a document or a ciphertext to G2 runs on the host in this system and
is not counted.

Costs are textbook formula costs in base-field multiplications (``m``),
counting a squaring as a multiplication, an Fq2 product as 3 m (Karatsuba)
and an Fq2 square as 2 m (complex squaring).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Hashable, NamedTuple, Sequence, Tuple

from chipbench import kinds as request_kinds

FQ2_MUL = 3
FQ2_SQR = 2

# Jacobian formulas on y^2 = x^3 + b (a = 0), Explicit-Formulas Database:
# dbl-2009-l is 2M + 5S; madd-2007-bl (affine second operand) is 7M + 4S.
# add-2007-bl (both operands Jacobian) is 11M + 5S.
G1_DOUBLE = 2 + 5
G1_MIXED_ADD = 7 + 4
G1_ADD = 11 + 5
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

# G1 membership by phi(P) == -[x^2]P, phi(x, y) = (beta x, y) (Scott, the
# same note, section 4; Bowe, "Faster subgroup checks for BLS12-381", 2019):
# two multiplications by |x|, the second of a point that is no longer affine,
# so its additions are general ones, and phi as one product by a constant.
G1_SUBGROUP_CHECK = (
    2 * X_DOUBLINGS * G1_DOUBLE
    + X_ADDITIONS * G1_MIXED_ADD
    + X_ADDITIONS * G1_ADD
    + 1
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

#: int8 operations that stand for one base-field product: a 381-bit product
#: as ceil(381 / 8) ** 2 byte products, a multiply and an add each, and three
#: such products for one Montgomery multiplication (each configuration's
#: file lists this conversion under ``assumed``).
INT8_OPS_PER_FQ_MUL = 3 * 2 * math.ceil(381 / 8) ** 2

# The second arguments of the batch equation's pairings, as a kind's
# ``pairs`` names them: equal keys are one Miller loop of a flush.
GENERATOR_PAIR: Tuple[Hashable, ...] = ("generator",)


def document_pair(doc: bytes) -> Tuple[Hashable, ...]:
    return ("document", doc)


def hashed_ciphertext_pair(u_bytes: bytes, v: bytes) -> Tuple[Hashable, ...]:
    return ("hashed_ciphertext", u_bytes, v)


def ciphertext_w_pair(w_bytes: bytes) -> Tuple[Hashable, ...]:
    return ("ciphertext_w", w_bytes)


class Composition(NamedTuple):
    """What the least work of a flush is a function of."""

    requests: Dict[str, int]  #: requests by kind
    pairs: int                #: distinct second arguments: Miller loops
    wire_bytes: int           #: bytes sent, what requests share sent once


def compose(kinds: Sequence[str], wire: Sequence[Tuple[Any, ...]]) -> Composition:
    """The composition of the flush whose request ``i`` is of ``kinds[i]``
    with the wire form ``wire[i]``, through each kind's module."""
    requests: Dict[str, int] = {}
    pairs, shared, sent = set(), set(), 0
    for kind, parts in zip(kinds, wire):
        module = request_kinds.load(kind)
        requests[kind] = requests.get(kind, 0) + 1
        pairs.update(module.pairs(*parts))
        own, once = module.sent(*parts)
        sent += own
        shared.update(once)
    return Composition(requests, len(pairs), sent + sum(len(b) for b in shared))


def scan_fq_muls(requests: Dict[str, int]) -> int:
    """The scan program's part: every request's scalar multiplications and
    subgroup checks."""
    if not requests or any(n < 1 for n in requests.values()):
        raise ValueError("a flush has at least one request of each kind it names")
    return sum(n * request_kinds.load(k).SCAN_FQ_MULS for k, n in requests.items())


def pair_fq_muls(pairs: int) -> int:
    """The pair program's part: ``pairs`` Miller loops that share their
    squarings, and one final exponentiation."""
    if pairs < 2:
        raise ValueError("a verification equation has at least two pairings")
    return MILLER_SHARED_SQUARINGS + pairs * MILLER_PER_PAIR + FINAL_EXP


def fq_muls(requests: Dict[str, int], pairs: int) -> int:
    """Base-field multiplications that verifying a flush of ``requests`` (by
    kind) over ``pairs`` distinct pairings needs at the least."""
    return scan_fq_muls(requests) + pair_fq_muls(pairs)


def least_seconds(flush: Composition, peaks: Dict[str, float]) -> Dict[str, float]:
    """The least time one chip could take for the flush: the larger of its
    operations over the int8 peak and its wire bytes over the HBM peak.
    Returns both, the larger as ``seconds`` and its name as ``bound``."""
    compute_s = (
        fq_muls(flush.requests, flush.pairs) * INT8_OPS_PER_FQ_MUL
        / peaks["int8_ops_per_s"]
    )
    memory_s = flush.wire_bytes / peaks["hbm_bytes_per_s"]
    bound = "compute_int8" if compute_s >= memory_s else "memory_hbm"
    return {
        "seconds": max(compute_s, memory_s),
        "bound": bound,
        "compute_s": compute_s,
        "memory_s": memory_s,
    }
