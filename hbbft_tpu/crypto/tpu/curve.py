"""Batched Jacobian point arithmetic for G1 (Fq) and G2 (Fq2) on TPU.

Replaces the per-point CPU group ops of upstream ``threshold_crypto``
(SURVEY.md §2 #14) with branch-free, vmappable formulas.

Point representation: ``(x, y, z, inf)`` — Jacobian coordinates as limb
arrays plus an explicit int32 infinity flag (1 = identity).  Carrying the
flag avoids data-dependent field-equality tests (which need sequential
carry scans) in the hot paths.

``add_unsafe`` is branch-free and WRONG when both inputs are the same
non-identity point or exact negatives.  Its callers guarantee that can't
happen (or happens with cryptographically negligible probability):

* ``scalar_mul``: acc = m·B meets addend B only if m ≡ ±1 (mod r); after
  the first set bit m ∈ [2, 2^255) and the scalars here are either
  Fiat-Shamir RLC coefficients (< 2^128 ≪ r) or Lagrange coefficients we
  derive ourselves — hitting (r±1)/2 prefixes is a 2^-250-class event an
  adversary cannot steer.
* tree reduction over RLC-scaled points: points are c_i·P_i with c_i
  Fiat-Shamir coefficients fixed only after the P_i are committed, so
  engineered cancellations/collisions are negligible.

``add_safe`` (field-equality corrected, sequential scans inside) exists
for tests and cold paths.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hbbft_tpu.crypto.bls import fields as F
from hbbft_tpu.crypto.tpu import fq, fq2

Point = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]


@dataclass(frozen=True)
class Ops:
    """Field-op namespace a curve works over (G1: fq, G2: fq2)."""

    add: Callable
    sub: Callable
    mul: Callable
    sqr: Callable
    small_mul: Callable
    is_zero: Callable
    one: np.ndarray
    zero: np.ndarray
    elem_ndim: int  # trailing dims of one field element


G1_OPS = Ops(fq.add, fq.sub, fq.mont_mul, fq.mont_sqr, fq.small_mul,
             fq.is_zero, fq.ONE_MONT, fq.ZERO, 1)
G2_OPS = Ops(fq2.add, fq2.sub, fq2.mul, fq2.sqr, fq2.small_mul,
             fq2.is_zero, fq2.ONE, fq2.ZERO, 2)


def identity(ops: Ops, batch: Tuple[int, ...] = ()) -> Point:
    one = jnp.broadcast_to(jnp.asarray(ops.one), (*batch, *ops.one.shape))
    zero = jnp.broadcast_to(jnp.asarray(ops.zero), (*batch, *ops.zero.shape))
    return (one, one, zero, jnp.ones(batch, dtype=jnp.int32))


def double(ops: Ops, p: Point) -> Point:
    """Jacobian doubling (a = 0 curve).  Correct for all inputs: the
    subgroup has prime order, so y = 0 never occurs on valid points, and
    the identity flag rides through unchanged (z' = 2yz keeps z = 0)."""
    x, y, z, inf = p
    a = ops.sqr(x)
    b = ops.sqr(y)
    c = ops.sqr(b)
    d = ops.small_mul(ops.sub(ops.sub(ops.sqr(ops.add(x, b)), a), c), 2)
    e = ops.small_mul(a, 3)
    f = ops.sqr(e)
    x3 = ops.sub(f, ops.small_mul(d, 2))
    y3 = ops.sub(ops.mul(e, ops.sub(d, x3)), ops.small_mul(c, 8))
    z3 = ops.small_mul(ops.mul(y, z), 2)
    return (x3, y3, z3, inf)


def _sel(flag: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray, ndim: int) -> jnp.ndarray:
    """where(flag, a, b) with flag broadcast over trailing element dims."""
    f = flag.reshape(flag.shape + (1,) * ndim).astype(bool)
    return jnp.where(f, a, b)


def select(flag: jnp.ndarray, p: Point, q: Point, ops: Ops) -> Point:
    """Pointwise where(flag, p, q)."""
    return (
        _sel(flag, p[0], q[0], ops.elem_ndim),
        _sel(flag, p[1], q[1], ops.elem_ndim),
        _sel(flag, p[2], q[2], ops.elem_ndim),
        jnp.where(flag.astype(bool), p[3], q[3]),
    )


def add_unsafe(ops: Ops, p: Point, q: Point) -> Point:
    """General Jacobian addition; identity flags handled, p == ±q NOT
    (see module docstring for why callers may rely on that)."""
    x1, y1, z1, inf1 = p
    x2, y2, z2, inf2 = q
    z1z1 = ops.sqr(z1)
    z2z2 = ops.sqr(z2)
    u1 = ops.mul(x1, z2z2)
    u2 = ops.mul(x2, z1z1)
    s1 = ops.mul(y1, ops.mul(z2, z2z2))
    s2 = ops.mul(y2, ops.mul(z1, z1z1))
    h = ops.sub(u2, u1)
    i = ops.sqr(ops.small_mul(h, 2))
    j = ops.mul(h, i)
    rr = ops.small_mul(ops.sub(s2, s1), 2)
    v = ops.mul(u1, i)
    x3 = ops.sub(ops.sub(ops.sqr(rr), j), ops.small_mul(v, 2))
    y3 = ops.sub(ops.mul(rr, ops.sub(v, x3)), ops.small_mul(ops.mul(s1, j), 2))
    z3 = ops.mul(ops.small_mul(ops.mul(z1, z2), 2), h)
    out: Point = (x3, y3, z3, jnp.zeros_like(inf1))
    out = select(inf1, q, out, ops)
    out = select(inf2 & (1 - inf1), p, out, ops)
    return out


def add_safe(ops: Ops, p: Point, q: Point) -> Point:
    """Addition correct for ALL inputs (uses field-equality tests; slow —
    sequential scans — so keep out of scans/hot loops)."""
    x1, y1, z1, inf1 = p
    x2, y2, z2, inf2 = q
    z1z1 = ops.sqr(z1)
    z2z2 = ops.sqr(z2)
    u1 = ops.mul(x1, z2z2)
    u2 = ops.mul(x2, z1z1)
    s1 = ops.mul(y1, ops.mul(z2, z2z2))
    s2 = ops.mul(y2, ops.mul(z1, z1z1))
    h_zero = ops.is_zero(ops.sub(u2, u1)).astype(jnp.int32)
    r_zero = ops.is_zero(ops.sub(s2, s1)).astype(jnp.int32)
    both = (1 - inf1) * (1 - inf2)
    is_dbl = both * h_zero * r_zero
    is_cancel = both * h_zero * (1 - r_zero)
    # safety: the two select()s below replace exactly the lanes where
    # add_unsafe's P == ±Q precondition fails (is_dbl / is_cancel).
    out = add_unsafe(ops, p, q)
    out = select(is_dbl, double(ops, p), out, ops)
    out = select(is_cancel, identity(ops, tuple(inf1.shape)), out, ops)
    return out


def neg(ops: Ops, p: Point) -> Point:
    x, y, z, inf = p
    return (x, ops.sub(jnp.zeros_like(y), y), z, inf)


def scalar_mul(ops: Ops, base: Point, bits: jnp.ndarray) -> Point:
    """Batched double-and-add: bits ``(..., nbits)`` int32, MSB first.

    Scans over the bit axis; everything else is batch.  See module
    docstring for the add_unsafe safety argument.
    """
    nbits = bits.shape[-1]
    batch = bits.shape[:-1]
    acc = identity(ops, batch)
    started = jnp.zeros(batch, dtype=jnp.int32)
    xs = jnp.moveaxis(bits, -1, 0)  # (nbits, ...)

    def step(carry, bit):
        acc, started = carry
        acc = double(ops, acc)
        # "acc is identity" is exactly "no set bit yet": use the flag
        # instead of a field test.
        acc_id = (1 - started)
        summed = add_unsafe(ops, (acc[0], acc[1], acc[2], acc_id), base)
        acc = select(bit, summed, acc, ops)
        started = started | bit
        return (acc, started), None

    (acc, started), _ = jax.lax.scan(step, (acc, started), xs)
    x, y, z, _ = acc
    inf = (1 - started) | base[3]
    return (x, y, z, inf)


def jac_eq_dev(ops: Ops, p: Point, q: Point) -> jnp.ndarray:
    """Batched projective equality (cross-multiplied), device-side.

    Contains ``is_zero`` sequential scans — once-per-flush use only.
    Points whose z is zero but whose infinity flag is unset (the garbage
    add_unsafe produces on forbidden inputs) compare UNEQUAL to
    everything, so downstream checks fail closed.
    """
    x1, y1, z1, i1 = p
    x2, y2, z2, i2 = q
    z1z1 = ops.sqr(z1)
    z2z2 = ops.sqr(z2)
    ex = ops.is_zero(ops.sub(ops.mul(x1, z2z2), ops.mul(x2, z1z1)))
    ey = ops.is_zero(
        ops.sub(
            ops.mul(y1, ops.mul(z2, z2z2)), ops.mul(y2, ops.mul(z1, z1z1))
        )
    )
    z_ok = (~ops.is_zero(z1)) & (~ops.is_zero(z2))
    both_fin = (i1 == 0) & (i2 == 0)
    both_inf = (i1 == 1) & (i2 == 1)
    return both_inf | (both_fin & z_ok & ex & ey)


# ---------------------------------------------------------------------------
# Endomorphism subgroup checks — device mirror of bls.curve.g1_in_subgroup /
# g2_in_subgroup (see the derivation + soundness notes there and the
# equivalence/soundness tests in tests/test_bls.py, tests/test_tpu_crypto.py).
#
#   G1: phi(P) == -[x^2]P   (phi: X *= beta; x^2 is 127 bits)
#   G2: psi(Q) == -[|x|]Q   (psi: conjugate coords, X *= cx, Y *= cy)
#
# Both scalars fit the 128-bit RLC coefficient width, so the flush
# kernel's shared-doubling scan drops from the 255-step [r-1]P chain
# (the round-2 design) to 128 steps.
#
# Fail-closed safety with add_unsafe: an adversarial SMALL-ORDER point
# can steer the fixed-scalar chain into add_unsafe's forbidden P == ±Q
# cases, but those produce z = 0 outputs and z stays 0 through every
# subsequent double/add (z3 always carries a factor of the incoming z),
# and jac_eq_dev treats unflagged z == 0 as UNEQUAL — so a corrupted
# chain can only REJECT, which is the correct verdict for any point
# that could steer it (subgroup points can't: the prefix-coincidence
# argument in scalar_mul2's docstring).
# ---------------------------------------------------------------------------

ENDO_NBITS = 128


@lru_cache(maxsize=1)
def _endo_consts():
    """(beta_mont, psi_cx_mont, psi_cy_mont, x2_bits, xabs_bits) — device
    forms of the oracle-derived endomorphism constants."""
    from hbbft_tpu.crypto.bls import curve as OC

    x_abs = -F.BLS_X
    beta = fq.to_mont_np(OC.g1_beta())
    cx, cy = OC.psi_consts()
    x2_bits = _scalars_to_bits_np([x_abs * x_abs], ENDO_NBITS)[0]
    xabs_bits = _scalars_to_bits_np([x_abs], ENDO_NBITS)[0]
    return (
        beta,
        fq2.to_mont_np(cx),
        fq2.to_mont_np(cy),
        x2_bits,
        xabs_bits,
    )


def endo_bits(g2: bool, n: int) -> np.ndarray:
    """(n, ENDO_NBITS) LSB-first bits of the endomorphism-check scalar
    (x^2 for G1 rows, |x| for G2 rows) — the bits_b of the shared scan."""
    _, _, _, x2_bits, xabs_bits = _endo_consts()
    return np.broadcast_to(xabs_bits if g2 else x2_bits, (n, ENDO_NBITS))


def phi_g1(p: Point) -> Point:
    """GLV endomorphism on batched G1 Jacobian points: X *= beta."""
    beta, _, _, _, _ = _endo_consts()
    x, y, z, inf = p
    bx = fq.mont_mul(x, jnp.broadcast_to(jnp.asarray(beta), x.shape))
    return (bx, y, z, inf)


def psi_g2(p: Point) -> Point:
    """Untwist-Frobenius-twist on batched G2 Jacobian points:
    (cx*conj(X), cy*conj(Y), conj(Z))."""
    _, cx, cy, _, _ = _endo_consts()
    x, y, z, inf = p
    cxb = jnp.broadcast_to(jnp.asarray(cx), x.shape)
    cyb = jnp.broadcast_to(jnp.asarray(cy), y.shape)
    return (
        fq2.mul(cxb, fq2.conj(x)),
        fq2.mul(cyb, fq2.conj(y)),
        fq2.conj(z),
        inf,
    )


def endo_subgroup_eq(ops: Ops, pts: Point, chain_out: Point) -> jnp.ndarray:
    """Batched membership verdicts given ``chain_out`` = [x^2]P (G1) or
    [|x|]Q (G2) from the shared scan: endo(P) == -chain_out."""
    endo = psi_g2(pts) if ops is G2_OPS else phi_g1(pts)
    return jac_eq_dev(ops, endo, neg(ops, chain_out))


def scalar_mul2(
    ops: Ops, base: Point, bits_a: jnp.ndarray, bits_b: jnp.ndarray
) -> Tuple[Point, Point]:
    """Two scalar multiples of the SAME base per batch element, one scan.

    LSB-first double-and-add sharing the base-doubling chain: per step
    one double (of the base) + two conditional adds, so computing
    ``[a]P`` and ``[b]P`` together costs ~35% less than two MSB-first
    scans and halves the number of compiled scan bodies.  ``bits_a``/
    ``bits_b``: (..., nbits) int32, LSB FIRST, equal width (pad the
    shorter scalar with zero bits).

    add_unsafe safety (on top of the module-docstring argument): the
    accumulator after k steps holds ``(m mod 2^k)·P`` (fixed scalar) or a
    committed-coefficient partial sum (Fiat-Shamir), and the addend is
    ``2^k·P``; coincidence needs m mod 2^k ≡ ±2^k (mod r).  For any
    FIXED m < 2^128 over k ≤ 128 steps (the RLC coefficients and both
    endomorphism-chain scalars x^2 and |x| qualify) that is impossible:
    m mod 2^k < 2^k rules out +2^k as integers, and -2^k mod r =
    r - 2^k > 2^128 > m mod 2^k rules out the negative case; the same
    bounds covered the historic m = r-1 chain.  For small-ORDER inputs
    (adversarial non-subgroup points, where the arithmetic is mod
    ord(P), not r) a coincidence CAN occur, but then z becomes and
    stays 0, ``jac_eq_dev`` reports unequal, and the membership check
    fails closed — rejection being the right verdict for any point able
    to steer the chain (see the endo section notes above).
    """
    assert bits_a.shape == bits_b.shape
    batch = bits_a.shape[:-1]
    acc_a = identity(ops, batch)
    acc_b = identity(ops, batch)
    started_a = jnp.zeros(batch, dtype=jnp.int32)
    started_b = jnp.zeros(batch, dtype=jnp.int32)
    xs = (jnp.moveaxis(bits_a, -1, 0), jnp.moveaxis(bits_b, -1, 0))

    def acc_step(acc, started, cur, bit):
        summed = add_unsafe(ops, (acc[0], acc[1], acc[2], 1 - started), cur)
        return select(bit, summed, acc, ops), started | bit

    def step(carry, bits):
        acc_a, started_a, acc_b, started_b, cur = carry
        bit_a, bit_b = bits
        acc_a, started_a = acc_step(acc_a, started_a, cur, bit_a)
        acc_b, started_b = acc_step(acc_b, started_b, cur, bit_b)
        return (acc_a, started_a, acc_b, started_b, double(ops, cur)), None

    (acc_a, started_a, acc_b, started_b, _), _ = jax.lax.scan(
        step, (acc_a, started_a, acc_b, started_b, base), xs
    )
    inf_a = (1 - started_a) | base[3]
    inf_b = (1 - started_b) | base[3]
    return (
        (acc_a[0], acc_a[1], acc_a[2], inf_a),
        (acc_b[0], acc_b[1], acc_b[2], inf_b),
    )


def scalars_to_bits_lsb(scalars, nbits: int) -> jnp.ndarray:
    """Host: list of ints -> (N, nbits) int32 LSB-first bit matrix."""
    return jnp.asarray(_scalars_to_bits_np(scalars, nbits))


# ---------------------------------------------------------------------------
# Static-endo flush scans (round 4) — the per-row cost cut
#
# The flush kernel's per-row work is the scalar-mul scan.  Two structural
# facts the shared 128-step scan (scalar_mul2) never exploited:
#
#   * the endomorphism-check scalars are FIXED — x^2 (hamming weight 17)
#     on G1, |x| (hamming weight 6) on G2 — so the check chain needs an
#     ADD at only those static positions, not a computed-and-discarded
#     conditional add at every one of 128 steps;
#   * on G2 the verified psi(Q) = [x]Q endomorphism gives a second base:
#     a 128-bit RLC coefficient c splits as c = q·|x| + s (q ≤ 65 bits,
#     s < 64), and [c]Q = [s]Q + [q]([|x|]Q) = [s]Q + [q](-psi(Q)) — a
#     65-step two-scalar scan instead of 128 steps.  Using psi(Q) as a
#     base is sound exactly when psi(Q) == [x]Q, which is the subgroup
#     check VERIFIED IN THE SAME KERNEL: if it fails, the aggregate
#     verdict is already False and the RLC value is irrelevant; if it
#     holds, Q ∈ G2 (Bowe 2019/814 / Scott 2021/1130, bls.curve notes)
#     and the decomposition is exact group algebra.
#
# Structure note: each doubling chain is ONE lax.scan that writes the
# chain points [2^k]P at the static set bits k into slots of its carry,
# and the check chain is the tree_sum of the slots after the scan ([m]P =
# sum of distinct powers): the schedule is data (each step's slot), not
# code, so the program holds one copy of each step body however many
# set bits the scalar has.  Nothing accumulates between scans — XLA
# 0.9.0's CPU pipeline dies with "Unknown MLIR failure" on scan→add→scan
# chains (reproduced + bisected round 4).
#
# add_unsafe safety for the NEW uses (CLAUDE.md invariant — on top of
# the scalar_mul2 docstring argument):
#
#   * check-chain tree_sum (both groups): every partial sum is
#     [m1]P for m1 a sub-mask of the fixed scalar's set bits, every
#     addend [m2]P for a DISJOINT nonzero sub-mask (tree_sum pairs sums
#     over disjoint sets of its rows); coincidence needs m1 ≡ ±m2
#     (mod r) with m1 ≠ m2, both < 2^128 ≪ r — impossible.
#   * B01 = Q + (-psi(Q)) precompute: forbidden iff psi(Q) == ±Q, i.e.
#     [x ∓ 1]Q = O — impossible for genuine G2 points (0 < |x ∓ 1| <
#     2^65 < r); adversarial non-subgroup points can poison it, but
#     they fail the psi check in the same kernel (fail-closed z = 0
#     argument in the endo section above), so a poisoned RLC never
#     reaches a True verdict.
#   * MSB accumulator adds (G2 RLC scan): deterministically impossible,
#     not merely improbable.  After the step's double the accumulator is
#     [2m]Q with m = s_k + |x|*q_k (k-bit MSB prefixes, k <= 64, so
#     2m < 2^131 << r: a mod-r wrap 2m = r - t is out of reach and any
#     coincidence must hold over the integers).  The addend scalars are
#     1 and |x|+1 (both odd — never equal to the even 2m) or |x| (even:
#     needs m = |x|/2, which forces q_k = 0 and s_k = |x|/2; but |x|/2
#     has 63 bits, so k >= 63 and s >= 2^(64-k) * |x|/2 >= |x|,
#     contradicting the decomposition's 0 <= s < |x|).
# ---------------------------------------------------------------------------

XSQ = (F.BLS_X * F.BLS_X)  # 128-bit G1 endo-check scalar (positive)


def _chain_slots(scalar: int, steps: int) -> np.ndarray:
    """Where step j of a doubling scan, which makes [2^(j+1)]P, writes it:
    slot i where j + 1 is ``scalar``'s i-th set bit, else the one spare
    slot past them, which nobody reads.  Bit 0 of both check scalars
    (x^2, |x|) is clear, so no chain point precedes the first double."""
    set_bits = [k for k in range(scalar.bit_length()) if (scalar >> k) & 1]
    if set_bits[0] == 0 or set_bits[-1] > steps:
        raise ValueError(f"{steps} doublings cannot make the chain of {scalar}")
    slots = np.full(steps, len(set_bits), dtype=np.int32)
    slots[np.array(set_bits) - 1] = np.arange(len(set_bits))
    return slots


def _chain_init(base: Point, scalar: int) -> Point:
    """The slots a doubling scan's carry writes ``scalar``'s chain points
    into (:func:`_chain_slots`): one row per set bit, and the spare."""
    n = bin(scalar).count("1") + 1
    return tuple(jnp.zeros((n, *c.shape), c.dtype) for c in base)


def _chain_write(chain: Point, slot, pt: Point) -> Point:
    return tuple(
        jax.lax.dynamic_update_index_in_dim(c, p, slot, 0)
        for c, p in zip(chain, pt)
    )


def scalar_mul_rlc_g1(base: Point, bits_lsb: jnp.ndarray) -> Tuple[Point, Point]:
    """([c]P, [x^2]P) per row — ONE LSB-first shared-doubling scan.

    ``bits_lsb``: (..., 128) LSB-first RLC coefficient bits.  One base
    doubling chain serves both results; the RLC add is conditional per
    step, and the [x^2]P check chain is the tree_sum of the chain
    points [2^k]P that the scan writes into its carry at x^2's 17 static
    set bits (structure + safety: section notes above).
    """
    ops = G1_OPS
    nbits = bits_lsb.shape[-1]
    # The [x^2]P check chain reads chain points at x^2's set bits; a
    # narrower scan would silently truncate the check scalar and reject
    # every genuine point (fail-closed but undiagnosable) — mirror the
    # G2 scan's width guard.  Explicit raise, not assert: `python -O`
    # strips asserts, and this failure mode is exactly the one that
    # must stay loud (ADVICE round 5).
    if nbits < XSQ.bit_length():
        raise ValueError(
            f"RLC bit width {nbits} < x^2 width {XSQ.bit_length()}"
        )
    batch = bits_lsb.shape[:-1]
    acc = identity(ops, batch)
    started = jnp.zeros(batch, dtype=jnp.int32)
    xs_all = jnp.moveaxis(bits_lsb, -1, 0)  # (nbits, ...)
    slots = jnp.asarray(_chain_slots(XSQ, nbits))

    def step(carry, xs):
        acc, started, cur, chain = carry
        bit, slot = xs
        summed = add_unsafe(ops, (acc[0], acc[1], acc[2], 1 - started), cur)
        acc = select(bit, summed, acc, ops)
        started = started | bit
        cur = double(ops, cur)
        return (acc, started, cur, _chain_write(chain, slot, cur)), None

    (acc, started, _, chain), _ = jax.lax.scan(
        step, (acc, started, base, _chain_init(base, XSQ)), (xs_all, slots)
    )
    inf = (1 - started) | base[3]
    scaled = (acc[0], acc[1], acc[2], inf)
    chain = tree_sum(ops, tuple(c[:-1] for c in chain))
    chain = (chain[0], chain[1], chain[2], chain[3] | base[3])
    return scaled, chain


G2_SCAN_NBITS = 65  # max(|x| bits, q = c div |x| bits) for c < 2^128


def decompose_g2_scalar(c: int) -> Tuple[int, int]:
    """Host: RLC coefficient c -> (s, q) with c = q·|x| + s, 0 ≤ s < |x|.

    Then [c]Q = [s]Q + [q][|x|]Q = [s]Q + [q](-psi(Q)) for subgroup Q
    (psi(Q) = [x]Q, x < 0).  For c < 2^128: q < 2^65, s < 2^64.
    """
    q, s = divmod(c, -F.BLS_X)
    return s, q


def scalar_mul_rlc_g2(
    base: Point, bits_s: jnp.ndarray, bits_q: jnp.ndarray
) -> Tuple[Point, Point]:
    """([c]Q, [|x|]Q) per row via the psi decomposition (section notes).

    ``bits_s``/``bits_q``: (..., 65) MSB-first bits of s and q from
    :func:`decompose_g2_scalar`.  The RLC sum is ONE MSB-first scan —
    per step one accumulator double and one add_unsafe of the addend
    selected from {O, Q, -psi(Q), Q-psi(Q)}; the [|x|]Q check chain is
    the tree_sum of [2^j]Q that ONE double-only scan writes into its
    carry at |x|'s 6 static set bits.  ~129 doubles + ~72 adds replaces the
    shared 128-step scan's 128 doubles + 256 computed adds — and the G2 rows
    are the most expensive in the flush (every Fq2 op is ~3 Fq muls).
    """
    ops = G2_OPS
    nbits = bits_s.shape[-1]
    assert bits_s.shape == bits_q.shape and nbits == G2_SCAN_NBITS
    batch = bits_s.shape[:-1]
    b0 = base
    b1 = neg(ops, psi_g2(base))
    b01 = add_unsafe(ops, b0, b1)  # safety: section notes (psi(Q) != ±Q)
    acc = identity(ops, batch)
    xs = (jnp.moveaxis(bits_s, -1, 0), jnp.moveaxis(bits_q, -1, 0))

    def step(acc, bits):
        sbit, qbit = bits
        acc = double(ops, acc)
        both = sbit & qbit
        addend = select(both, b01, select(sbit, b0, b1, ops), ops)
        # Identity addend when neither bit is set (and inherit the
        # base's own identity flag) — add_unsafe routes on the flag.
        addend = (
            addend[0],
            addend[1],
            addend[2],
            (1 - (sbit | qbit)) | addend[3],
        )
        # safety: MSB accumulator adds — deterministically impossible
        # coincidence (section notes above, third bullet).
        return add_unsafe(ops, acc, addend), None

    acc, _ = jax.lax.scan(step, acc, xs)

    # [|x|]Q check chain: one double-only scan over the base up to |x|'s
    # top bit, [2^j]Q written at its set bits, then one tree reduction
    # (structure + add_unsafe safety: section notes above).
    def dbl_step(carry, slot):
        cur, chain = carry
        cur = double(ops, cur)
        return (cur, _chain_write(chain, slot, cur)), None

    x_abs = -F.BLS_X
    (_, chain), _ = jax.lax.scan(
        dbl_step,
        (base, _chain_init(base, x_abs)),
        jnp.asarray(_chain_slots(x_abs, x_abs.bit_length() - 1)),
    )
    chain = tree_sum(ops, tuple(c[:-1] for c in chain))

    # Identity flags: acc started as identity and add_unsafe tracked
    # flags through every add (a zero scalar leaves the flag set); the
    # chain inherits the base's flag through doubling.
    scaled = (acc[0], acc[1], acc[2], acc[3] | base[3])
    chain = (chain[0], chain[1], chain[2], chain[3] | base[3])
    return scaled, chain


#: Point adds that one add_unsafe step of a loop takes about as long for
#: as for one: a v5e runs a tree level of 256 G1 adds in about the time
#: of a level of 32 (0.2 ms), and 2048 in seven times that.
ADDS_PER_STEP = 256


def tree_sum(ops: Ops, pts: Point) -> Point:
    """Sum points over axis 0 (any trailing batch dims) in two loops of
    ONE ``add_unsafe`` body each, so the program's size does not depend
    on the m rows: the rows, padded with points at infinity to blocks of
    w, fold block by block into the first (a ``lax.scan`` of ceil(m/w) - 1
    steps), then the w sums halve to one (log2 w levels adding adjacent
    pairs at a fixed width w/2).  w is a power of two below m, as small as
    puts :data:`ADDS_PER_STEP` adds (over the trailing dims) in a step:
    about m - 1 adds in all, as a halving tree makes, in about as few
    steps where a step's time is its dispatch.

    add_unsafe safety (the CALLER's argument covers the pairs; this is
    which pairs there are): every add is of two sums over DISJOINT sets
    of the input rows, or has the point at infinity on one side, which
    add_unsafe routes by its flag.  So it adds what a halving tree adds:
    committed-coefficient partial sums of RLC-scaled rows (module
    docstring, second bullet), or disjoint sub-masks of a check chain
    (static-endo section notes)."""
    m = pts[0].shape[0]
    if m == 1:
        return tuple(x[0] for x in pts)
    batch = pts[3].shape[1:]
    fill = -(-ADDS_PER_STEP // max(1, int(np.prod(batch))))
    w = min(1 << ((m - 1).bit_length() - 1), 1 << (fill - 1).bit_length())
    blocks = -(-m // w)
    pad = identity(ops, (blocks * w - m, *batch))
    rows = tuple(
        jnp.concatenate([x, p]).reshape((blocks, w) + x.shape[1:])
        for x, p in zip(pts, pad)
    )

    def fold(acc, block):
        # safety: row i of acc sums rows i + j·w of the blocks before,
        # disjoint from the block's row i (docstring above).
        return add_unsafe(ops, acc, block), None

    acc, _ = jax.lax.scan(
        fold, tuple(x[0] for x in rows), tuple(x[1:] for x in rows)
    )
    if w > 1:
        half = identity(ops, (w // 2, *batch))

        def level(acc, _):
            # safety: adjacent rows sum disjoint blocks (docstring above).
            summed = add_unsafe(
                ops, tuple(x[0::2] for x in acc), tuple(x[1::2] for x in acc)
            )
            return tuple(
                jnp.concatenate([s, h]) for s, h in zip(summed, half)
            ), None

        acc, _ = jax.lax.scan(level, acc, None, length=w.bit_length() - 1)
    return tuple(x[0] for x in acc)


# ---------------------------------------------------------------------------
# Host conversions to/from the oracle's Jacobian-int representation
# ---------------------------------------------------------------------------


def g1_to_dev(jacs) -> Point:
    """Host: list of oracle G1 Jacobian points -> batched device point.

    Batch-vectorized (bytes + unpackbits): the per-limb Python loop
    dominated host->device conversion at firehose batch sizes."""
    n = len(jacs)
    xs, ys, zs = [], [], []
    infs = np.zeros(n, dtype=np.int32)
    for i, (x, y, z) in enumerate(jacs):
        if z % F.P == 0:
            infs[i] = 1
            x, y, z = 1, 1, 0
        xs.append(x)
        ys.append(y)
        zs.append(z)
    flat = fq.to_mont_batch(xs + ys + zs)
    return (jnp.asarray(flat[:n]), jnp.asarray(flat[n : 2 * n]),
            jnp.asarray(flat[2 * n :]), jnp.asarray(infs))


def g2_to_dev(jacs) -> Point:
    n = len(jacs)
    coords: list = []
    infs = np.zeros(n, dtype=np.int32)
    pts = []
    for i, (x, y, z) in enumerate(jacs):
        if z[0] % F.P == 0 and z[1] % F.P == 0:
            infs[i] = 1
            x, y, z = (1, 0), (1, 0), (0, 0)
        pts.append((x, y, z))
    for sel in range(3):
        for c in range(2):
            coords.extend(p[sel][c] for p in pts)
    flat = fq.to_mont_batch(coords)  # (6n, NL): x0 x1 y0 y1 z0 z1 blocks
    def elem(block):
        return jnp.asarray(
            np.stack([flat[block * 2 * n : block * 2 * n + n],
                      flat[block * 2 * n + n : (block + 1) * 2 * n]], axis=1)
        )
    return (elem(0), elem(1), elem(2), jnp.asarray(infs))


def g1_from_dev(p: Point, idx=None):
    """Host: one device G1 point -> oracle Jacobian int tuple."""
    x, y, z, inf = [np.asarray(v) for v in p]
    if idx is not None:
        x, y, z, inf = x[idx], y[idx], z[idx], inf[idx]
    if int(inf):
        return (1, 1, 0)
    return (fq.from_mont_int(x), fq.from_mont_int(y), fq.from_mont_int(z))


def g2_from_dev(p: Point, idx=None):
    x, y, z, inf = [np.asarray(v) for v in p]
    if idx is not None:
        x, y, z, inf = x[idx], y[idx], z[idx], inf[idx]
    if int(inf):
        return ((1, 0), (1, 0), (0, 0))
    return (fq2.from_mont_int(x), fq2.from_mont_int(y), fq2.from_mont_int(z))


def _scalars_to_bits_np(scalars, nbits: int) -> np.ndarray:
    """(N, nbits) int32 LSB-first bit matrix, vectorized."""
    nbytes = (nbits + 7) // 8
    for s in scalars:
        assert 0 <= s < (1 << nbits)
    if not scalars:
        return np.zeros((0, nbits), dtype=np.int32)
    data = np.frombuffer(
        b"".join(s.to_bytes(nbytes, "little") for s in scalars), dtype=np.uint8
    ).reshape(len(scalars), nbytes)
    return np.unpackbits(data, axis=1, bitorder="little")[:, :nbits].astype(
        np.int32
    )


def scalars_to_bits(scalars, nbits: int) -> jnp.ndarray:
    """Host: list of ints -> (N, nbits) int32 MSB-first bit matrix."""
    return jnp.asarray(_scalars_to_bits_np(scalars, nbits)[:, ::-1].copy())
