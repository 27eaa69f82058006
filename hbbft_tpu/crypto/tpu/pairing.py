"""Batched optimal-ate pairing on TPU: Fq12 tower, Miller loop, final exp.

Device counterpart of the oracle in ``hbbft_tpu/crypto/bls/pairing.py``
(same math, re-architected for XLA):

* Fq12 elements are ``(..., 6, 2, NL)`` limb arrays (coefficients of w,
  ``w^6 = xi``), so a full Fq12 multiply is ONE batched Fq2 multiply over
  the 6x6 coefficient cross (3 ``mont_mul`` dispatches) plus cheap
  anti-diagonal reductions — the TPU sees wide vector ops, not 36 scalar
  multiplies.
* The Miller loop is a ``lax.scan`` over the 63 fixed bits of |x|, the
  addition step under a ``lax.cond`` on the bit (``_x_schedule``); T is
  tracked in Jacobian coordinates and every line is scaled by a nonzero
  Fq2 factor (killed by the final exponentiation), so there are NO field
  inversions in the loop.
* The final exponentiation's hard part uses the verified identity
      3·(p^4 - p^2 + 1)/r = (x-1)^2·(x+p)·(x^2+p^2-1) + 3
  (checked against the integer value at import).  Raising to 3·hard
  instead of hard is sound for the ==1 check because 3 ∤ p^4-p^2+1, so
  cubing is a bijection on the cyclotomic subgroup.

Everything is batched over a leading "pairs" axis; the pairing-product
check shares one final exponentiation across all pairs (as the oracle's
``multi_pairing_is_one`` does).
"""

from __future__ import annotations

from functools import lru_cache
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hbbft_tpu.crypto.bls import fields as OF
from hbbft_tpu.crypto.bls.fields import BLS_X, P, R
from hbbft_tpu.crypto.tpu import curve as dcurve
from hbbft_tpu.crypto.tpu import fq, fq2

NL = fq.NL
X_ABS = -BLS_X

# The hard-part chain identity (module docstring); kept as an executable
# guard so a wrong refactor of the chain can't silently ship.
assert 3 * ((P**4 - P**2 + 1) // R) == (BLS_X - 1) ** 2 * (BLS_X + P) * (
    BLS_X**2 + P**2 - 1
) + 3
assert (P**4 - P**2 + 1) % 3 != 0

# Bits of |x| below the MSB, MSB-first — the Miller/x-exp schedule.
X_BITS = np.array([int(b) for b in bin(X_ABS)[3:]], dtype=np.int32)


def _x_schedule(carry, double, add):
    """Run the |x| schedule: per bit of :data:`X_BITS`, ``double`` and,
    where the bit is set, ``add``.  ONE ``lax.scan`` over the bits, the
    add under a ``lax.cond`` on the bit: of the 63 steps only the FIVE set
    bits compute the add arm (a branch-free select would compute it at
    every step: about half the fixed per-flush pairing cost), and the
    program holds one copy of each arm however the bits fall."""

    def bit(carry, b):
        return jax.lax.cond(b, add, lambda c: c, double(carry)), None

    carry, _ = jax.lax.scan(bit, carry, jnp.asarray(X_BITS, dtype=bool))
    return carry


FQ12_ONE = np.zeros((6, 2, NL), dtype=np.int32)
FQ12_ONE[0, 0] = fq.ONE_MONT


@lru_cache(maxsize=None)
def _gamma_dev(k: int) -> np.ndarray:
    """Frobenius constants gamma[k][i] = xi^(i(p^k-1)/6) as device limbs."""
    g = OF._gamma(k)
    return np.stack([fq2.to_mont_np(c) for c in g])


# ---------------------------------------------------------------------------
# Fq12 arithmetic
# ---------------------------------------------------------------------------


def mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Full Fq12 multiply: one batched 6x6 Fq2 cross + xi-reduction."""
    prod = fq2.mul(a[..., :, None, :, :], b[..., None, :, :, :])
    return _reduce_cross(prod, np.arange(6), np.arange(6))


def _reduce_cross(prod: jnp.ndarray, ioffs: np.ndarray, joffs: np.ndarray) -> jnp.ndarray:
    """Sum prod[..., i, j, :, :] into w^(ioffs[i]+joffs[j]) buckets and
    fold w^(6+k) = xi·w^k.  Raw limb sums stay far inside int32."""
    out_lo = [None] * 6
    out_hi = [None] * 6
    for i, io in enumerate(ioffs):
        for j, jo in enumerate(joffs):
            k = int(io + jo)
            term = prod[..., i, j, :, :]
            if k < 6:
                out_lo[k] = term if out_lo[k] is None else out_lo[k] + term
            else:
                out_hi[k - 6] = term if out_hi[k - 6] is None else out_hi[k - 6] + term
    coeffs = []
    for k in range(6):
        lo = out_lo[k]
        hi = out_hi[k]
        if lo is None and hi is None:
            raise AssertionError("empty bucket")
        if hi is None:
            coeffs.append(fq2.normalize(lo))
        elif lo is None:
            coeffs.append(fq2.mul_by_xi(hi))
        else:
            coeffs.append(fq.add(lo, fq2.mul_by_xi(hi)))
    return jnp.stack(coeffs, axis=-3)


def sqr(a: jnp.ndarray) -> jnp.ndarray:
    return mul(a, a)


def sparse_mul(a: jnp.ndarray, l0: jnp.ndarray, l2: jnp.ndarray, l3: jnp.ndarray) -> jnp.ndarray:
    """a · (l0 + l2·w^2 + l3·w^3) — the Miller-line shape."""
    l = jnp.stack([l0, l2, l3], axis=-3)
    prod = fq2.mul(a[..., :, None, :, :], l[..., None, :, :, :])
    return _reduce_cross(prod, np.arange(6), np.array([0, 2, 3]))


def conj(a: jnp.ndarray) -> jnp.ndarray:
    """a^(p^6): inverse on the cyclotomic unit circle."""
    return frobenius(a, 6)


def frobenius(a: jnp.ndarray, k: int) -> jnp.ndarray:
    g = jnp.asarray(_gamma_dev(k))
    c = fq2.conj(a) if k % 2 == 1 else a
    return fq2.mul(c, g)


def inv(a: jnp.ndarray) -> jnp.ndarray:
    """Inverse via the norm to Fq2 (mirrors the oracle's fq12_inv)."""
    prod_conj = None
    for k in (2, 4, 6, 8, 10):
        fr = frobenius(a, k)
        prod_conj = fr if prod_conj is None else mul(prod_conj, fr)
    norm12 = mul(a, prod_conj)
    ninv = fq2.inv(norm12[..., 0, :, :])
    return fq2.mul(prod_conj, ninv[..., None, :, :])


def pow_x_abs(f: jnp.ndarray) -> jnp.ndarray:
    """f^|x| — square-and-multiply over :data:`X_BITS`, the multiply
    only at the 5 set bits (:func:`_x_schedule`; ~45% fewer Fq12 ops
    than a multiply computed and discarded at every bit).  Under the
    ``jax.named_scope`` ``pow_x_abs``: the final exponentiation's five
    calls are its loops on a device trace."""
    with jax.named_scope("pow_x_abs"):
        return _x_schedule(f, sqr, lambda acc: mul(acc, f))


def pow_x(f: jnp.ndarray) -> jnp.ndarray:
    """f^x for the (negative) BLS parameter; f must be unitary."""
    return conj(pow_x_abs(f))


def is_one(a: jnp.ndarray) -> jnp.ndarray:
    """Batched check a == 1 (sequential scans; once per flush)."""
    ok = fq.is_zero(fq.sub(a[..., 0, 0, :], jnp.asarray(fq.ONE_MONT)))
    ok = ok & fq.is_zero(a[..., 0, 1, :])
    for i in range(1, 6):
        ok = ok & fq2.is_zero(a[..., i, :, :])
    return ok


# ---------------------------------------------------------------------------
# Miller loop (Jacobian T on the twist, scaled lines)
# ---------------------------------------------------------------------------


def miller_loop(px: jnp.ndarray, py: jnp.ndarray, qx: jnp.ndarray, qy: jnp.ndarray) -> jnp.ndarray:
    """f_{|x|,Q}(P) conjugated for x<0; batched over leading axes.

    px, py: (..., NL) affine G1; qx, qy: (..., 2, NL) affine twist point.
    Lines are scaled by 2YZ^3 (doubling) and HZ (addition) — nonzero Fq2
    factors the final exponentiation kills (oracle docstring, and e.g.
    upstream threshold_crypto's pairing backend relies on the same fact).
    """
    px_neg = fq.neg(px)
    one = jnp.broadcast_to(jnp.asarray(fq2.ONE), qx.shape)
    f0 = jnp.broadcast_to(jnp.asarray(FQ12_ONE), (*qx.shape[:-2], 6, 2, NL))

    def dbl_step(X, Y, Z, f):
        A = fq2.sqr(X)
        B = fq2.sqr(Y)
        Z1Z1 = fq2.sqr(Z)
        l0 = fq.sub(fq2.small_mul(fq2.mul(X, A), 3), fq2.small_mul(B, 2))
        l2 = fq.neg(fq2.mul_fq(fq2.small_mul(fq2.mul(A, Z1Z1), 3), px))
        Znew = fq2.small_mul(fq2.mul(Y, Z), 2)
        l3 = fq2.mul_fq(fq2.mul(Znew, Z1Z1), py)
        C = fq2.sqr(B)
        D = fq2.small_mul(fq.sub(fq.sub(fq2.sqr(fq.add(X, B)), A), C), 2)
        E = fq2.small_mul(A, 3)
        F = fq2.sqr(E)
        X3 = fq.sub(F, fq2.small_mul(D, 2))
        Y3 = fq.sub(fq2.mul(E, fq.sub(D, X3)), fq2.small_mul(C, 8))
        f = sqr(f)
        f = sparse_mul(f, l0, l2, l3)
        return X3, Y3, Znew, f

    def add_step(X, Y, Z, f):
        Z1Z1 = fq2.sqr(Z)
        U2 = fq2.mul(qx, Z1Z1)
        S2 = fq2.mul(qy, fq2.mul(Z, Z1Z1))
        H = fq.sub(U2, X)
        theta = fq.sub(S2, Y)
        HZ = fq2.mul(H, Z)
        l0 = fq.sub(fq2.mul(theta, qx), fq2.mul(qy, HZ))
        l2 = fq2.mul_fq(theta, px_neg)
        l3 = fq2.mul_fq(HZ, py)
        HH = fq2.sqr(H)
        I = fq2.small_mul(HH, 4)
        J = fq2.mul(H, I)
        rr = fq2.small_mul(theta, 2)
        V = fq2.mul(X, I)
        X3 = fq.sub(fq.sub(fq2.sqr(rr), J), fq2.small_mul(V, 2))
        Y3 = fq.sub(fq2.mul(rr, fq.sub(V, X3)), fq2.small_mul(fq2.mul(Y, J), 2))
        Z3 = fq2.small_mul(fq2.mul(Z, H), 2)
        f = sparse_mul(f, l0, l2, l3)
        return X3, Y3, Z3, f

    # The add step only at the 5 set bits of |x| (_x_schedule).
    f = _x_schedule(
        (qx, qy, one, f0), lambda c: dbl_step(*c), lambda c: add_step(*c)
    )[3]
    # x < 0: f_{x,Q} = conjugate(f_{|x|,Q})
    return conj(f)


# ---------------------------------------------------------------------------
# Final exponentiation
# ---------------------------------------------------------------------------


def final_exp_is_one(f: jnp.ndarray) -> jnp.ndarray:
    """Is f^((p^12-1)/r) == 1?  Uses the 3·hard chain (module docstring)."""
    # Easy part: f^((p^6-1)(p^2+1)); result is unitary.
    f1 = mul(conj(f), inv(f))
    m = mul(frobenius(f1, 2), f1)
    # Hard part to the power 3·(p^4-p^2+1)/r = (x-1)^2(x+p)(x^2+p^2-1)+3.
    a = mul(pow_x(m), conj(m))                # m^(x-1)
    b = mul(pow_x(a), conj(a))                # a^(x-1)
    c = mul(pow_x(b), frobenius(b, 1))        # b^(x+p)
    d = pow_x(pow_x(c))                       # c^(x^2)
    g = mul(mul(d, frobenius(c, 2)), conj(c))  # c^(x^2+p^2-1)
    res = mul(g, mul(sqr(m), m))              # · m^3
    return is_one(res)


# ---------------------------------------------------------------------------
# Affine conversion + pairing-product check
# ---------------------------------------------------------------------------


def g1_affine(p: dcurve.Point) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Jacobian G1 -> affine; identity becomes garbage (caller gates on
    the inf flag).  One Fq inversion (Fermat scan)."""
    x, y, z, _inf = p
    zi = fq.inv(fq.normalize(z))
    zi2 = fq.mont_sqr(zi)
    return fq.mont_mul(x, zi2), fq.mont_mul(y, fq.mont_mul(zi2, zi))


def g2_affine(p: dcurve.Point) -> Tuple[jnp.ndarray, jnp.ndarray]:
    x, y, z, _inf = p
    zi = fq2.inv(fq2.normalize(z))
    zi2 = fq2.sqr(zi)
    return fq2.mul(x, zi2), fq2.mul(y, fq2.mul(zi2, zi))


def miller_product(g1s: dcurve.Point, g2s: dcurve.Point) -> jnp.ndarray:
    """prod_i f_{x,Q_i}(P_i) over the batch axis — the pairing product
    BEFORE the final exponentiation (one Fq12 element).

    Pairs where either side is the identity contribute the factor 1
    (mirrors the oracle's multi_pairing_is_one None-skip).  Splitting
    this from :func:`final_exp_is_one` lets a caller combine several
    independently-computed Miller products and pay ONE final
    exponentiation for all of them (the TpuBackend cross-chunk flush).
    """
    px, py = g1_affine(g1s)
    qx, qy = g2_affine(g2s)
    fs = miller_loop(px, py, qx, qy)
    skip = (g1s[3] | g2s[3]).astype(bool)
    one = jnp.broadcast_to(jnp.asarray(FQ12_ONE), fs.shape)
    fs = jnp.where(skip.reshape(skip.shape + (1, 1, 1)), one, fs)
    acc, _ = jax.lax.scan(lambda acc, f: (mul(acc, f), None), fs[0], fs[1:])
    return acc


def pairing_product_is_one(g1s: dcurve.Point, g2s: dcurve.Point) -> jnp.ndarray:
    """prod_i e(P_i, Q_i) == 1 over a batch axis; one final exponentiation.
    The two stages carry ``jax.named_scope``s so that a device trace's ops
    say which they belong to."""
    with jax.named_scope("miller_loop"):
        f = miller_product(g1s, g2s)
    with jax.named_scope("final_exp"):
        return final_exp_is_one(f)
