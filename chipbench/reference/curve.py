# Plain reference of the benchmark: a copy of hbbft_tpu/crypto/bls/curve.py as of
# commit 0bd1641, imports rewritten.  It imports nothing of the program, so a
# later change to the program cannot move the yardstick (chipbench/README.md).
"""BLS12-381 curve groups: Jacobian arithmetic, generators, hash-to-G2.

G1: E/Fq: y^2 = x^3 + 4.  G2: the M-twist E'/Fq2: y^2 = x^3 + 4*(1+u).

Constants policy: only p, r, the BLS parameter x, and the standard
generator coordinates are taken as given; curve orders and the G2
cofactor are *derived* (trace t = x + 1, twist-order candidates from the
Fq2 trace, selected by an actual order check on a sample point) and
verified by :func:`selfcheck`, so a mis-remembered constant cannot survive
the test suite.
"""

from __future__ import annotations

import hashlib
import math
from functools import lru_cache
from typing import Optional, Tuple

from chipbench.reference import fields as F
from chipbench.reference.fields import BLS_X, P, R, XI

# ---------------------------------------------------------------------------
# Generic Jacobian arithmetic, parameterized by field ops
# ---------------------------------------------------------------------------


class FieldOps:
    __slots__ = ("add", "sub", "neg", "mul", "sqr", "inv", "eq", "is_zero", "zero", "one", "muls")

    def __init__(self, add, sub, neg, mul, sqr, inv, eq, is_zero, zero, one, muls):
        self.add, self.sub, self.neg = add, sub, neg
        self.mul, self.sqr, self.inv = mul, sqr, inv
        self.eq, self.is_zero = eq, is_zero
        self.zero, self.one = zero, one
        self.muls = muls


FQ_OPS = FieldOps(
    add=lambda a, b: (a + b) % P,
    sub=lambda a, b: (a - b) % P,
    neg=lambda a: -a % P,
    mul=lambda a, b: a * b % P,
    sqr=lambda a: a * a % P,
    inv=lambda a: pow(a, P - 2, P),
    eq=lambda a, b: (a - b) % P == 0,
    is_zero=lambda a: a % P == 0,
    zero=0,
    one=1,
    muls=lambda a, s: a * s % P,
)

FQ2_OPS = FieldOps(
    add=F.fq2_add,
    sub=F.fq2_sub,
    neg=F.fq2_neg,
    mul=F.fq2_mul,
    sqr=F.fq2_sqr,
    inv=F.fq2_inv,
    eq=F.fq2_eq,
    is_zero=F.fq2_is_zero,
    zero=F.FQ2_ZERO,
    one=F.FQ2_ONE,
    muls=F.fq2_muls,
)

Jac = Tuple  # (X, Y, Z) in the underlying field


def jac_identity(ops: FieldOps) -> Jac:
    return (ops.one, ops.one, ops.zero)


def jac_is_identity(ops: FieldOps, p: Jac) -> bool:
    return ops.is_zero(p[2])


def jac_double(ops: FieldOps, p: Jac) -> Jac:
    X1, Y1, Z1 = p
    if ops.is_zero(Z1) or ops.is_zero(Y1):
        return jac_identity(ops)
    A = ops.sqr(X1)
    B = ops.sqr(Y1)
    C = ops.sqr(B)
    D = ops.sub(ops.sqr(ops.add(X1, B)), ops.add(A, C))
    D = ops.add(D, D)
    E = ops.add(ops.add(A, A), A)
    Ff = ops.sqr(E)
    X3 = ops.sub(Ff, ops.add(D, D))
    eightC = ops.add(C, C)
    eightC = ops.add(eightC, eightC)
    eightC = ops.add(eightC, eightC)
    Y3 = ops.sub(ops.mul(E, ops.sub(D, X3)), eightC)
    Z3 = ops.mul(ops.add(Y1, Y1), Z1)
    return (X3, Y3, Z3)


def jac_add(ops: FieldOps, p: Jac, q: Jac) -> Jac:
    if jac_is_identity(ops, p):
        return q
    if jac_is_identity(ops, q):
        return p
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q
    Z1Z1 = ops.sqr(Z1)
    Z2Z2 = ops.sqr(Z2)
    U1 = ops.mul(X1, Z2Z2)
    U2 = ops.mul(X2, Z1Z1)
    S1 = ops.mul(ops.mul(Y1, Z2), Z2Z2)
    S2 = ops.mul(ops.mul(Y2, Z1), Z1Z1)
    H = ops.sub(U2, U1)
    if ops.is_zero(H):
        if ops.eq(S1, S2):
            return jac_double(ops, p)
        return jac_identity(ops)
    I = ops.sqr(ops.add(H, H))
    J = ops.mul(H, I)
    rr = ops.sub(S2, S1)
    rr = ops.add(rr, rr)
    V = ops.mul(U1, I)
    X3 = ops.sub(ops.sub(ops.sqr(rr), J), ops.add(V, V))
    S1J = ops.mul(S1, J)
    Y3 = ops.sub(ops.mul(rr, ops.sub(V, X3)), ops.add(S1J, S1J))
    Z3 = ops.mul(
        ops.sub(ops.sub(ops.sqr(ops.add(Z1, Z2)), Z1Z1), Z2Z2), H
    )
    return (X3, Y3, Z3)


def jac_neg(ops: FieldOps, p: Jac) -> Jac:
    return (p[0], ops.neg(p[1]), p[2])


def jac_mul(ops: FieldOps, p: Jac, k: int) -> Jac:
    if k < 0:
        return jac_mul(ops, jac_neg(ops, p), -k)
    acc = jac_identity(ops)
    if k == 0 or jac_is_identity(ops, p):
        return acc
    for bit in bin(k)[2:]:
        acc = jac_double(ops, acc)
        if bit == "1":
            acc = jac_add(ops, acc, p)
    return acc


def jac_to_affine(ops: FieldOps, p: Jac) -> Optional[Tuple]:
    """Affine (x, y), or None for the identity."""
    if jac_is_identity(ops, p):
        return None
    zinv = ops.inv(p[2])
    zinv2 = ops.sqr(zinv)
    return (ops.mul(p[0], zinv2), ops.mul(ops.mul(p[1], zinv2), zinv))


def jac_eq(ops: FieldOps, p: Jac, q: Jac) -> bool:
    pi, qi = jac_is_identity(ops, p), jac_is_identity(ops, q)
    if pi or qi:
        return pi and qi
    Z1Z1 = ops.sqr(p[2])
    Z2Z2 = ops.sqr(q[2])
    if not ops.eq(ops.mul(p[0], Z2Z2), ops.mul(q[0], Z1Z1)):
        return False
    return ops.eq(
        ops.mul(ops.mul(p[1], q[2]), Z2Z2), ops.mul(ops.mul(q[1], p[2]), Z1Z1)
    )


# ---------------------------------------------------------------------------
# Curve parameters and derived orders
# ---------------------------------------------------------------------------

B1 = 4
B2 = F.fq2_muls(XI, 4)  # 4 * (1 + u)

# Standard generators.
G1_GEN = (
    0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB,
    0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1,
    1,
)
G2_GEN = (
    (
        0x024AA2B2F08F0A91260805272DC51051C6E47AD4FA403B02B4510B647AE3D1770BAC0326A805BBEFD48056C8C121BDB8,
        0x13E02B6052719F607DACD3A088274F65596BD0D09920B61AB5DA61BBDC7F5049334CF11213945D57E5AC7D055D042B7E,
    ),
    (
        0x0CE5D527727D6E118CC9CDC6DA2E351AADFD9BAA8CBDD3A76D429A695160D12C923AC9CC3BACA289E193548608B82801,
        0x0606C4A02EA734CC32ACD2B02BC28B99CB3E287E85A763AF267492AB572E99AB3F370D275CEC1DA1AAA9075FF05F79BE,
    ),
    F.FQ2_ONE,
)

TRACE = BLS_X + 1  # Frobenius trace of E/Fq
N1 = P + 1 - TRACE  # |E(Fq)|
H1 = N1 // R  # G1 cofactor


def g1_on_curve(x: int, y: int) -> bool:
    return (y * y - (x * x * x + B1)) % P == 0


def g2_on_curve(x: F.Fq2E, y: F.Fq2E) -> bool:
    rhs = F.fq2_add(F.fq2_mul(F.fq2_sqr(x), x), B2)
    return F.fq2_eq(F.fq2_sqr(y), rhs)


def g1_on_curve_jac(jac: Jac) -> bool:
    """On-curve in Jacobian form: Y^2 == X^3 + b*Z^6 — no inversion.

    (Affine x = X/Z^2, y = Y/Z^3; multiply the affine equation by Z^6.)
    Identity (Z == 0) counts as on-curve.
    """
    x, y, z = jac
    if z % P == 0:
        return True
    z2 = z * z % P
    return (y * y - (x * x % P * x + B1 * pow(z2, 3, P))) % P == 0


def g2_on_curve_jac(jac: Jac) -> bool:
    x, y, z = jac
    if F.fq2_is_zero(z):
        return True
    z2 = F.fq2_sqr(z)
    z6 = F.fq2_mul(F.fq2_sqr(z2), z2)
    rhs = F.fq2_add(F.fq2_mul(F.fq2_sqr(x), x), F.fq2_mul(B2, z6))
    return F.fq2_eq(F.fq2_sqr(y), rhs)


def _isqrt_exact(n: int) -> Optional[int]:
    if n < 0:
        return None
    s = math.isqrt(n)
    return s if s * s == n else None


@lru_cache(maxsize=1)
def twist_order() -> int:
    """|E'(Fq2)| for the M-twist, derived from the trace and verified.

    t2 = t^2 - 2p is the trace over Fq2; with t2^2 - 4p^2 = -3 f2^2, the
    sextic twists have orders p^2 + 1 - (±t2 ± 3 f2)/2.  The (unique)
    candidate that is divisible by r *and* annihilates a sample twist
    point is the order of our twist.
    """
    t2 = TRACE * TRACE - 2 * P
    f2 = _isqrt_exact((4 * P * P - t2 * t2) // 3)
    assert f2 is not None, "t2^2 - 4p^2 != -3 f2^2 — wrong trace"
    sample = _twist_sample_point()
    for num in (t2 + 3 * f2, t2 - 3 * f2, -t2 + 3 * f2, -t2 - 3 * f2):
        if num % 2:
            continue
        n = P * P + 1 - num // 2
        if n % R == 0 and jac_is_identity(FQ2_OPS, jac_mul(FQ2_OPS, sample, n)):
            return n
    raise AssertionError("no twist-order candidate verified")


def _twist_sample_point() -> Jac:
    """Deterministic non-generator point on E'(Fq2) via try-and-increment."""
    x0 = 7
    while True:
        for x1 in range(4):
            x = (x0, x1)
            rhs = F.fq2_add(F.fq2_mul(F.fq2_sqr(x), x), B2)
            y = F.fq2_sqrt(rhs)
            if y is not None:
                return (x, y, F.FQ2_ONE)
        x0 += 1


@lru_cache(maxsize=1)
def h2_cofactor() -> int:
    return twist_order() // R


# ---------------------------------------------------------------------------
# Fast subgroup membership via endomorphisms
# ---------------------------------------------------------------------------
#
# Replaces the definitional [r]P == O test (255 doubles + ~127 adds per
# point) with the standard endomorphism membership tests for BLS12-381
# (Bowe, "Faster subgroup checks for BLS12-381", eprint 2019/814; Scott,
# "A note on group membership tests for G1, G2 and GT", eprint 2021/1130
# — the simplified forms below are the ones deployed in blst):
#
#   G1:  phi(P) == -[x^2]P,  phi(X, Y, Z) = (beta*X, Y, Z) the GLV
#        endomorphism, beta a cube root of unity in Fq with eigenvalue
#        -x^2 on G1 (x = BLS_X, |x| 64 bits; x^2 is a fixed 128-bit
#        scalar -> two 64-bit chains host-side, one 128-bit chain that
#        exactly matches the RLC coefficient width on device).
#   G2:  psi(Q) == [x]Q,     psi the untwist-Frobenius-twist
#        endomorphism (|x| is 64 bits -> one 64-bit chain).
#
# Constants policy (matches the module docstring): beta and the psi
# coefficients are DERIVED at import — beta as the cube root of unity
# whose eigenvalue on the generator is -x^2, the psi coefficients by
# solving psi(G2) = [x]G2 coordinate-wise — then verified as genuine
# endomorphisms with the right eigenvalue on random multiples
# (selfcheck + tests/test_bls.py).  Soundness (no point OUTSIDE the
# r-torsion passes) is the cited results'; tests additionally construct
# cofactor-order points for every small prime factor of h1/h2 and check
# they fail (the passing set is a subgroup, so killing each prime
# ell-torsion kills every mixed-order component with ell | order).
#
# The ORACLE keeps the definitional check available as
# ``in_subgroup_slow`` — equivalence on random + adversarial points is
# pinned by tests; the TPU flush kernel mirrors the endomorphism form
# (crypto/tpu/backend.py) where it halves the batched scan width.

_X_ABS = -BLS_X  # |x|, positive 64-bit


@lru_cache(maxsize=1)
def g1_beta() -> int:
    """The cube root of unity in Fq whose GLV eigenvalue on G1 is
    -x^2 (i.e. beta*x_P pairs with jac_mul(P, -x^2 mod r))."""
    g = 2
    while True:
        b = pow(g, (P - 1) // 3, P)
        if b != 1:
            break
        g += 1
    lam = (-(_X_ABS * _X_ABS)) % R
    want = jac_mul(FQ_OPS, G1_GEN, lam)
    for beta in (b, b * b % P):
        x, y, z = G1_GEN
        if jac_eq(FQ_OPS, (beta * x % P, y, z), want):
            return beta
    raise AssertionError("no cube root of unity has eigenvalue -x^2")


@lru_cache(maxsize=1)
def psi_consts() -> Tuple[F.Fq2E, F.Fq2E]:
    """(cx, cy) with psi(X, Y, Z) = (cx*conj(X), cy*conj(Y), conj(Z)).

    Derived by solving psi(G2) = [x]G2 coordinate-wise (the generator's
    coordinates are nonzero, so the solution is unique and must equal
    the canonical untwist-Frobenius-twist coefficients); verified as an
    endomorphism with eigenvalue x on random multiples by selfcheck."""
    gx, gy, _ = G2_GEN  # affine (z = 1)
    target = jac_to_affine(FQ2_OPS, jac_mul(FQ2_OPS, G2_GEN, BLS_X % R))
    assert target is not None
    cx = F.fq2_mul(target[0], F.fq2_inv(F.fq2_conj(gx)))
    cy = F.fq2_mul(target[1], F.fq2_inv(F.fq2_conj(gy)))
    return cx, cy


def g2_psi(q: Jac) -> Jac:
    """The untwist-Frobenius-twist endomorphism on E'(Fq2), Jacobian
    form: Frobenius is coordinate conjugation (q-power), the twist
    constants fold into cx/cy (affine x = X/Z^2 conjugates to
    conj(X)/conj(Z)^2, so Z' = conj(Z) keeps the coordinates valid)."""
    cx, cy = psi_consts()
    x, y, z = q
    return (
        F.fq2_mul(cx, F.fq2_conj(x)),
        F.fq2_mul(cy, F.fq2_conj(y)),
        F.fq2_conj(z),
    )


def g1_in_subgroup(jac: Jac) -> bool:
    """P on E(Fq) is in the r-torsion iff phi(P) == -[x^2]P (identity
    included).  Callers must have checked on-curve already."""
    if jac_is_identity(FQ_OPS, jac):
        return True
    x, y, z = jac
    phi = (g1_beta() * x % P, y, z)
    xxp = jac_mul(FQ_OPS, jac_mul(FQ_OPS, jac, _X_ABS), _X_ABS)
    return jac_eq(FQ_OPS, phi, jac_neg(FQ_OPS, xxp))


def g2_in_subgroup(jac: Jac) -> bool:
    """Q on E'(Fq2) is in the r-torsion iff psi(Q) == [x]Q (identity
    included; x < 0 so the comparison is against -[|x|]Q)."""
    if jac_is_identity(FQ2_OPS, jac):
        return True
    return jac_eq(
        FQ2_OPS,
        g2_psi(jac),
        jac_neg(FQ2_OPS, jac_mul(FQ2_OPS, jac, _X_ABS)),
    )


def in_subgroup_slow(ops: FieldOps, jac: Jac) -> bool:
    """The definitional r-torsion test ([r]P == O) — oracle ground truth
    for the endomorphism checks above (tests pin their equivalence)."""
    return jac_is_identity(ops, jac_mul(ops, jac, R))


# ---------------------------------------------------------------------------
# Hash to G2 (try-and-increment + cofactor clearing)
# ---------------------------------------------------------------------------


def _hash_to_fq(tag: bytes) -> int:
    # 64 bytes of SHA3 -> uniform mod p (512 >> 381 bits: negligible bias).
    h = hashlib.sha3_256(tag + b"\x00").digest() + hashlib.sha3_256(tag + b"\x01").digest()
    return int.from_bytes(h, "big") % P


@lru_cache(maxsize=4096)
def hash_to_g2(data: bytes) -> Jac:
    """Map bytes to a point of order r on E'(Fq2), dlog unknown.

    Not the IETF SWU map (no wire-format interop requirement in a closed
    system — the reference's own ``hash_g2`` is a ChaCha-seeded random
    point, equally non-standard); try-and-increment is uniform over the
    curve and simple to audit.  Cofactor-cleared into the r-torsion.
    """
    ctr = 0
    while True:
        tag = b"h2g2" + len(data).to_bytes(8, "big") + data + ctr.to_bytes(4, "big")
        x = (_hash_to_fq(tag + b"c0"), _hash_to_fq(tag + b"c1"))
        rhs = F.fq2_add(F.fq2_mul(F.fq2_sqr(x), x), B2)
        y = F.fq2_sqrt(rhs)
        if y is None:
            ctr += 1
            continue
        # Deterministic sign choice from the hash, independent of which
        # root Tonelli-Shanks returned.
        want_odd = bool(_hash_to_fq(tag + b"sign") & 1)
        if bool(y[0] & 1) != want_odd:
            y = F.fq2_neg(y)
        point = jac_mul(FQ2_OPS, (x, y, F.FQ2_ONE), h2_cofactor())
        if jac_is_identity(FQ2_OPS, point):
            ctr += 1
            continue
        return point


# ---------------------------------------------------------------------------
# Self-check (exercised by the test suite)
# ---------------------------------------------------------------------------


def selfcheck() -> None:
    assert g1_on_curve(G1_GEN[0], G1_GEN[1]), "G1 generator not on curve"
    assert g2_on_curve(G2_GEN[0], G2_GEN[1]), "G2 generator not on twist"
    assert N1 % R == 0, "r does not divide |E(Fq)|"
    assert jac_is_identity(FQ_OPS, jac_mul(FQ_OPS, G1_GEN, R)), "G1 gen not r-torsion"
    assert jac_is_identity(FQ2_OPS, jac_mul(FQ2_OPS, G2_GEN, R)), "G2 gen not r-torsion"
    assert twist_order() % R == 0
    p = hash_to_g2(b"selfcheck")
    assert jac_is_identity(FQ2_OPS, jac_mul(FQ2_OPS, p, R)), "hashed point not r-torsion"
