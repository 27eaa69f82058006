"""Device crypto plane vs the pure-Python oracle (SURVEY.md §7 step 1).

Every layer of the TPU path — limb field arithmetic, Fq2, Jacobian curve
ops, the Fq12 tower, Miller loop/final exponentiation, and the
``TpuBackend`` RLC flush — is cross-checked against the oracle suite.
Runs on the virtual-CPU platform from conftest; the persistent XLA cache
keeps recompiles out of repeat runs.
"""

import os
import random
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hbbft_tpu.crypto.bls import curve as oc
from hbbft_tpu.crypto.bls import fields as OF
from hbbft_tpu.crypto.bls import pairing as OP
from hbbft_tpu.crypto.bls.suite import BLSSuite
from hbbft_tpu.crypto.tpu import curve as dc
from hbbft_tpu.crypto.tpu import fq, fq2
from hbbft_tpu.crypto.tpu import pairing as dp
from hbbft_tpu.crypto.backend import BatchedBackend, VerifyRequest
from hbbft_tpu.crypto.keys import SecretKeySet
from hbbft_tpu.crypto.tpu.backend import TpuBackend

P = OF.P

# Cold, each of these costs minutes of XLA compile on the CPU backend
# (Miller loop / flush programs), so they run in the slow tier; the
# default tier keeps the limb/field/curve layers (seconds to compile).
# The kernel-against-oracle comparison at the API boundary runs on the
# chip: chip_smoke.py, phase ``round``.
heavy_compile = pytest.mark.slow


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(42)


# ---------------------------------------------------------------------------
# Fq limbs
# ---------------------------------------------------------------------------


def test_fq_ops_match_ints(rng):
    n = 32
    avals = [int.from_bytes(rng.bytes(48), "big") % P for _ in range(n)]
    bvals = [int.from_bytes(rng.bytes(48), "big") % P for _ in range(n)]
    A = jnp.asarray(np.stack([fq.to_mont_np(a) for a in avals]))
    B = jnp.asarray(np.stack([fq.to_mont_np(b) for b in bvals]))

    @jax.jit
    def ops(A, B):
        # includes a deep alternating chain — the historic failure mode of
        # the signed-limb design was corruption after repeated sub+mul.
        s = A
        for _ in range(8):
            s = fq.mont_mul(fq.sub(s, B), fq.add(s, s))
        return (fq.mont_mul(A, B), fq.add(A, B), fq.sub(A, B),
                fq.small_mul(A, 8), fq.neg(A), s,
                fq.is_zero(fq.sub(A, A)), fq.is_zero(A))

    mul, ad, su, sm, ng, s, iz0, izn = [np.asarray(x) for x in ops(A, B)]
    for i in range(n):
        a, b = avals[i], bvals[i]
        ss = a
        for _ in range(8):
            ss = (ss - b) * (2 * ss) % P
        assert fq.from_mont_int(mul[i]) == a * b % P
        assert fq.from_mont_int(ad[i]) == (a + b) % P
        assert fq.from_mont_int(su[i]) == (a - b) % P
        assert fq.from_mont_int(sm[i]) == 8 * a % P
        assert fq.from_mont_int(ng[i]) == -a % P
        assert fq.from_mont_int(s[i]) == ss
        assert bool(iz0[i])
        assert bool(izn[i]) == (a % P == 0)


def test_fq_limb_invariant_zero_and_identity():
    z = jnp.asarray(fq.ZERO)
    one = jnp.asarray(fq.ONE_MONT)
    assert bool(fq.is_zero(z))
    assert not bool(fq.is_zero(one))
    assert fq.from_mont_int(np.asarray(fq.mont_mul(one, one))) == 1


# The limb normalisation as it was written before each carry round became
# slices of one padded array (a shifted carry padded back per round, the
# fold's excess read out per row and broadcast back, ``.at[...].add``
# updates in mont_mul's tail).  Kept as the reference the slice form must
# match limb for limb.


def _carry_ref(x, rounds):
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, rounds + 1)])
    for _ in range(rounds):
        lo = x & fq.MASK
        c = x >> fq.B
        x = lo + jnp.pad(c[..., :-1], [(0, 0)] * (x.ndim - 1) + [(1, 0)])
    return x


def _fold_ref(x, stages):
    NL = fq.NL
    for _ in range(stages):
        e = x[..., NL - 1]
        for i in range(NL, min(x.shape[-1], NL + 2)):
            e = e + x[..., i] * (1 << (fq.B * (i - (NL - 1))))
        folded = x[..., : NL - 1] + e[..., None] * jnp.asarray(fq.FOLD385)
        x = _carry_ref(folded, rounds=2)
    return x[..., :NL]


def _mont_mul_ref(a, b):
    NL = fq.NL
    t = _carry_ref(fq._conv(a, b), rounds=3)
    m = _carry_ref(fq._conv_const(t[..., :NL], fq._NPRIME_MAT), rounds=3)[..., :NL]
    mp = fq._conv_const(m, fq._P_MAT)
    full = jnp.pad(
        t, [(0, 0)] * (t.ndim - 1) + [(0, max(0, mp.shape[-1] - t.shape[-1]))]
    )
    full = full.at[..., : mp.shape[-1]].add(mp)
    full = _carry_ref(full, rounds=3)
    lo, hi = full[..., :NL], full[..., NL : 2 * NL]
    corr = (lo[..., NL - 1] + 3) >> fq.B
    return _carry_ref(hi.at[..., 0].add(corr), rounds=1)[..., :NL]


_CVEC, _DELTA = jnp.asarray(fq.CVEC), jnp.asarray(fq.DELTA)
# (reference, program, arity, Python-int value it must be congruent to mod P)
_FIELD_OPS = {
    "add": (
        lambda a, b: _fold_ref(_carry_ref(a + b, 2), 1), fq.add, 2,
        lambda a, b: a + b,
    ),
    "sub": (
        lambda a, b: _fold_ref(_carry_ref(a + (_CVEC - b) + _DELTA, 2), 3),
        fq.sub, 2, lambda a, b: a - b,
    ),
    "neg": (
        lambda a: _fold_ref(_carry_ref((_CVEC - a) + _DELTA, 2), 3), fq.neg, 1,
        lambda a: -a,
    ),
    "small_mul": (
        lambda a: _fold_ref(_carry_ref(a * 16, 2), 1),
        lambda a: fq.small_mul(a, 16), 1, lambda a: 16 * a,
    ),
    "normalize": (
        lambda a: _fold_ref(_carry_ref(a, 2), 1), fq.normalize, 1, lambda a: a,
    ),
    "mont_mul": (
        _mont_mul_ref, fq.mont_mul, 2,
        lambda a, b: a * b * pow(fq.R, -1, P),
    ),
}


def _redundant(rng, v, n, limb_max):
    """Limbs of value ``v`` (n of them, each in [0, limb_max]): the strict
    limbs with random multiples of 2^11 moved down one limb, top first."""
    x = fq.to_limbs_np(v, n).astype(np.int64)
    for i in range(n - 2, -1, -1):
        t = int(rng.integers(0, min(x[i + 1], (limb_max - x[i]) // 2048) + 1))
        x[i] += 2048 * t
        x[i + 1] -= t
    assert fq.from_limbs_int(x) == v and x.min() >= 0 and x.max() <= limb_max
    return x.astype(np.int32)


def _under(rng, bound_bits, n, limb_max, rows):
    """``rows`` redundant limb vectors with values under 2^bound_bits by
    less than 2^296."""
    top = int(2.0**bound_bits)
    return np.stack([
        _redundant(rng, top - 1 - int.from_bytes(rng.bytes(37), "big"), n, limb_max)
        for _ in range(rows)
    ])


@pytest.mark.parametrize("rounds", [1, 2, 3])
@pytest.mark.parametrize(
    "kind", ["random_4096", "random_raw_sums", "all_4096", "random_conv_sums"]
)
def test_carry_matches_the_padded_shift_form(rng, kind, rounds):
    """Limb for limb, every width a call site carries (35, 36, 71, 75)
    and the lowest ``width`` limbs alone."""
    for n in (35, 36, 71, 75):
        if kind == "random_4096":
            x = rng.integers(0, 4097, size=(64, n))
        elif kind == "random_raw_sums":  # what ``sub`` accepts
            x = rng.integers(0, 1 << 15, size=(64, n))
        elif kind == "all_4096":
            x = np.full((4, n), 4096)
        else:  # the 36-term sums of a convolution, < 2^29.2
            x = rng.integers(0, 1 << 29, size=(64, n))
        x = jnp.asarray(x, dtype=jnp.int32)
        want = np.asarray(_carry_ref(x, rounds))
        got = np.asarray(fq._carry(x, rounds))
        assert got.shape == want.shape == (x.shape[0], n + rounds + 1)
        np.testing.assert_array_equal(got, want)
        for width in (fq.NL, n):
            np.testing.assert_array_equal(
                np.asarray(fq._carry(x, rounds, width=width)), want[:, :width]
            )


@pytest.mark.parametrize("stages", [1, 3])
@pytest.mark.parametrize(
    "kind", ["carried_sums", "under_2_398", "all_4096", "carried_raw_sums"]
)
def test_fold_matches_the_scalar_broadcast_form(rng, kind, stages):
    """Post-carry inputs in the fold's range (value < 2^398), at the widths
    ``add`` and ``sub`` hand it (39 limbs) and a fold stage makes (38)."""
    if kind == "carried_sums":  # what add / normalize fold
        x = _carry_ref(jnp.asarray(rng.integers(0, 4097, size=(64, fq.NL)), jnp.int32), 2)
    elif kind == "carried_raw_sums":  # what sub folds: value < 2^403
        x = _carry_ref(jnp.asarray(rng.integers(0, 1 << 16, size=(64, fq.NL)), jnp.int32), 2)
    elif kind == "under_2_398":
        x = jnp.asarray(_under(rng, 398, 39, 4096, 32))
    else:  # value about 2^397
        x = jnp.full((4, fq.NL), 4096, jnp.int32)
    for w in sorted({x.shape[-1], 38}):
        xw = x[..., :w]
        if w < x.shape[-1]:
            assert not np.asarray(x[..., w:]).any()
        want = np.asarray(_fold_ref(xw, stages))
        got = np.asarray(fq._fold(xw, stages))
        assert got.shape == want.shape == (x.shape[0], fq.NL)
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kind", ["random", "at_the_bounds"])
@pytest.mark.parametrize("op", list(_FIELD_OPS))
def test_field_op_matches_the_reference_normalisation_and_ints(rng, op, kind):
    """Every public op of ``fq`` equals its reference form limb for limb
    and is congruent mod P to the Python-int result; its limbs stay in
    [0, 4096] (and mont_mul's value under 2^382.5).
    ``at_the_bounds``: values just under each op's input bound: 2^385.9
    (the invariant), 2^389 with limbs up to 2^15 - 1 (``sub``'s raw
    sums), 2^386 (``mont_mul``)."""
    ref, prog, arity, want = _FIELD_OPS[op]
    rows = 32
    if kind == "random":
        args = [
            np.stack([fq.to_mont_np(int.from_bytes(rng.bytes(48), "big") % P)
                      for _ in range(rows)])
            for _ in range(arity)
        ]
    elif op == "sub":
        args = [_under(rng, 389, fq.NL, (1 << 15) - 1, rows) for _ in range(2)]
    elif op == "mont_mul":
        args = [_under(rng, 386, fq.NL, 4096, rows) for _ in range(2)]
    else:
        args = [_under(rng, 385.9, fq.NL, 4096, rows) for _ in range(arity)]
    args = [jnp.asarray(a) for a in args]
    got = np.asarray(prog(*args))
    np.testing.assert_array_equal(got, np.asarray(ref(*args)))
    assert got.min() >= 0 and got.max() <= 4096
    for r in range(rows):
        vals = [fq.from_limbs_int(a[r]) for a in args]
        v = fq.from_limbs_int(got[r])
        assert op != "mont_mul" or v < 2**382.5
        assert (v - want(*vals)) % P == 0


# ---------------------------------------------------------------------------
# Fq2
# ---------------------------------------------------------------------------


def test_fq2_ops_match_oracle(rng):
    a = (int.from_bytes(rng.bytes(48), "big") % P, int.from_bytes(rng.bytes(48), "big") % P)
    b = (int.from_bytes(rng.bytes(48), "big") % P, int.from_bytes(rng.bytes(48), "big") % P)
    da, db = jnp.asarray(fq2.to_mont_np(a)), jnp.asarray(fq2.to_mont_np(b))

    assert fq2.from_mont_int(np.asarray(fq2.mul(da, db))) == OF.fq2_mul(a, b)
    assert fq2.from_mont_int(np.asarray(fq2.sqr(da))) == OF.fq2_sqr(a)
    assert fq2.from_mont_int(np.asarray(fq2.conj(da))) == OF.fq2_conj(a)
    assert fq2.from_mont_int(np.asarray(fq2.mul_by_xi(da))) == OF.fq2_mul(a, OF.XI)
    got_inv = fq2.from_mont_int(np.asarray(fq2.inv(da)))
    assert OF.fq2_eq(OF.fq2_mul(got_inv, a), OF.FQ2_ONE)


# ---------------------------------------------------------------------------
# Curve (G1/G2): double/add/scalar-mul/tree-sum
# ---------------------------------------------------------------------------


def _rand_points(rng, n):
    g1s = [oc.jac_mul(oc.FQ_OPS, oc.G1_GEN, int.from_bytes(rng.bytes(32), "big") % OF.R)
           for _ in range(n)]
    g2s = [oc.jac_mul(oc.FQ2_OPS, oc.G2_GEN, int.from_bytes(rng.bytes(32), "big") % OF.R)
           for _ in range(n)]
    return g1s, g2s


def test_curve_g1_g2_vs_oracle(rng):
    n = 4
    g1s, g2s = _rand_points(rng, n)
    scalars = [int.from_bytes(rng.bytes(8), "big") | 1 for _ in range(n)]
    P1, P2 = dc.g1_to_dev(g1s), dc.g2_to_dev(g2s)
    bits = dc.scalars_to_bits(scalars, 64)

    @jax.jit
    def work(P1, P2, bits):
        d1 = dc.double(dc.G1_OPS, P1)
        s1 = dc.add_unsafe(dc.G1_OPS, P1, d1)
        m1 = dc.scalar_mul(dc.G1_OPS, P1, bits)
        t1 = dc.tree_sum(dc.G1_OPS, m1)
        d2 = dc.double(dc.G2_OPS, P2)
        s2 = dc.add_unsafe(dc.G2_OPS, P2, d2)
        m2 = dc.scalar_mul(dc.G2_OPS, P2, bits)
        t2 = dc.tree_sum(dc.G2_OPS, m2)
        return d1, s1, m1, t1, d2, s2, m2, t2

    d1, s1, m1, t1, d2, s2, m2, t2 = work(P1, P2, bits)
    acc1, acc2 = oc.jac_identity(oc.FQ_OPS), oc.jac_identity(oc.FQ2_OPS)
    for i in range(n):
        assert oc.jac_eq(oc.FQ_OPS, dc.g1_from_dev(d1, i), oc.jac_double(oc.FQ_OPS, g1s[i]))
        assert oc.jac_eq(oc.FQ_OPS, dc.g1_from_dev(s1, i), oc.jac_mul(oc.FQ_OPS, g1s[i], 3))
        assert oc.jac_eq(oc.FQ_OPS, dc.g1_from_dev(m1, i), oc.jac_mul(oc.FQ_OPS, g1s[i], scalars[i]))
        assert oc.jac_eq(oc.FQ2_OPS, dc.g2_from_dev(d2, i), oc.jac_double(oc.FQ2_OPS, g2s[i]))
        assert oc.jac_eq(oc.FQ2_OPS, dc.g2_from_dev(s2, i), oc.jac_mul(oc.FQ2_OPS, g2s[i], 3))
        assert oc.jac_eq(oc.FQ2_OPS, dc.g2_from_dev(m2, i), oc.jac_mul(oc.FQ2_OPS, g2s[i], scalars[i]))
        acc1 = oc.jac_add(oc.FQ_OPS, acc1, oc.jac_mul(oc.FQ_OPS, g1s[i], scalars[i]))
        acc2 = oc.jac_add(oc.FQ2_OPS, acc2, oc.jac_mul(oc.FQ2_OPS, g2s[i], scalars[i]))
    assert oc.jac_eq(oc.FQ_OPS, dc.g1_from_dev(t1), acc1)
    assert oc.jac_eq(oc.FQ2_OPS, dc.g2_from_dev(t2), acc2)


def test_curve_identity_flags(rng):
    g1s, _ = _rand_points(rng, 2)
    P1 = dc.g1_to_dev(g1s)
    z = dc.scalar_mul(dc.G1_OPS, P1, jnp.zeros((2, 16), jnp.int32))
    assert all(int(v) for v in np.asarray(z[3]))
    # identity + P = P through add_unsafe
    s = dc.add_unsafe(dc.G1_OPS, dc.identity(dc.G1_OPS, (2,)), P1)
    for i in range(2):
        assert oc.jac_eq(oc.FQ_OPS, dc.g1_from_dev(s, i), g1s[i])


def _coeffs(rng, n):
    """128-bit RLC coefficients, the second of them zero."""
    return [0 if i == 1 else int.from_bytes(rng.bytes(16), "big") for i in range(n)]


def test_rlc_g1_scan_matches_oracle(rng):
    """The G1 scan (one ``lax.scan``, chain points gathered from its
    outputs): [c]P and [x^2]P per row, a point at infinity and a zero
    coefficient among the rows."""
    g1s, _ = _rand_points(rng, 3)
    jacs = g1s + [oc.jac_identity(oc.FQ_OPS)]
    coeffs = _coeffs(rng, len(jacs))
    bits = dc.scalars_to_bits_lsb(coeffs, dc.ENDO_NBITS)
    scaled, chain = jax.jit(dc.scalar_mul_rlc_g1)(dc.g1_to_dev(jacs), bits)
    for i, (j, c) in enumerate(zip(jacs, coeffs)):
        assert oc.jac_eq(oc.FQ_OPS, dc.g1_from_dev(scaled, i), oc.jac_mul(oc.FQ_OPS, j, c))
        assert oc.jac_eq(oc.FQ_OPS, dc.g1_from_dev(chain, i), oc.jac_mul(oc.FQ_OPS, j, dc.XSQ))
    assert [int(v) for v in np.asarray(scaled[3])] == [0, 1, 0, 1]
    assert [int(v) for v in np.asarray(chain[3])] == [0, 0, 0, 1]


def test_rlc_g2_scan_matches_oracle(rng):
    """The G2 scan and its chain (one double-only scan, points gathered at
    |x|'s set bits): [c]Q and [|x|]Q per row, with the same rows."""
    _, g2s = _rand_points(rng, 3)
    jacs = g2s + [oc.jac_identity(oc.FQ2_OPS)]
    coeffs = _coeffs(rng, len(jacs))
    sq = [dc.decompose_g2_scalar(c) for c in coeffs]
    bs = dc.scalars_to_bits([s for s, _ in sq], dc.G2_SCAN_NBITS)
    bq = dc.scalars_to_bits([q for _, q in sq], dc.G2_SCAN_NBITS)
    scaled, chain = jax.jit(dc.scalar_mul_rlc_g2)(dc.g2_to_dev(jacs), bs, bq)
    for i, (j, c) in enumerate(zip(jacs, coeffs)):
        assert oc.jac_eq(oc.FQ2_OPS, dc.g2_from_dev(scaled, i), oc.jac_mul(oc.FQ2_OPS, j, c))
        assert oc.jac_eq(oc.FQ2_OPS, dc.g2_from_dev(chain, i), oc.jac_mul(oc.FQ2_OPS, j, -OF.BLS_X))
    assert [int(v) for v in np.asarray(scaled[3])] == [0, 1, 0, 1]


@pytest.mark.parametrize(
    "n, adds_per_step", [(1, 256), (2, 256), (3, 256), (5, 256), (16, 256),
                         (5, 1), (16, 4)],
)
def test_tree_sum_matches_oracle(rng, monkeypatch, n, adds_per_step):
    """The fold and the level loop against the oracle's sum, every third
    row the point at infinity: one block folded into another, then levels
    (the first five), several blocks with no level loop, and several
    blocks of four then two levels."""
    monkeypatch.setattr(dc, "ADDS_PER_STEP", adds_per_step)
    g1s, _ = _rand_points(rng, n)
    jacs = [oc.jac_identity(oc.FQ_OPS) if i % 3 == 1 else p for i, p in enumerate(g1s)]
    got = jax.jit(lambda p: dc.tree_sum(dc.G1_OPS, p))(dc.g1_to_dev(jacs))
    want = oc.jac_identity(oc.FQ_OPS)
    for p in jacs:
        want = oc.jac_add(oc.FQ_OPS, want, p)
    assert oc.jac_eq(oc.FQ_OPS, dc.g1_from_dev(got), want)


def test_tree_sum_over_a_grid_sums_each_column(rng):
    """Trailing batch dims are columns summed apart (the scan program's
    legs): G2 rows on a (5, 2) grid, one column's rows all infinity but
    one."""
    _, g2s = _rand_points(rng, 6)
    ident = oc.jac_identity(oc.FQ2_OPS)
    cols = [g2s[:5], [ident, ident, g2s[5], ident, ident]]
    flat = dc.g2_to_dev([cols[c][r] for r in range(5) for c in range(2)])
    grid = tuple(x.reshape((5, 2) + x.shape[1:]) for x in flat)
    got = jax.jit(lambda p: dc.tree_sum(dc.G2_OPS, p))(grid)
    for c, rows in enumerate(cols):
        want = ident
        for p in rows:
            want = oc.jac_add(oc.FQ2_OPS, want, p)
        assert oc.jac_eq(oc.FQ2_OPS, dc.g2_from_dev(got, c), want)


def test_add_safe_degenerate_cases(rng):
    g1s, _ = _rand_points(rng, 2)
    P1 = dc.g1_to_dev(g1s)
    dbl = dc.add_safe(dc.G1_OPS, P1, P1)  # equal inputs -> doubling
    cancel = dc.add_safe(dc.G1_OPS, P1, dc.neg(dc.G1_OPS, P1))  # P + (-P)
    for i in range(2):
        assert oc.jac_eq(oc.FQ_OPS, dc.g1_from_dev(dbl, i), oc.jac_double(oc.FQ_OPS, g1s[i]))
    assert all(int(v) for v in np.asarray(cancel[3]))


# ---------------------------------------------------------------------------
# Fq12 tower + pairing
# ---------------------------------------------------------------------------


def _rand_fq12(rng):
    return tuple(
        (int.from_bytes(rng.bytes(48), "big") % P, int.from_bytes(rng.bytes(48), "big") % P)
        for _ in range(6)
    )


def _to_dev12(a):
    return jnp.asarray(np.stack([fq2.to_mont_np(c) for c in a]))


def _from_dev12(x):
    arr = np.asarray(x)
    return tuple(fq2.from_mont_int(arr[i]) for i in range(6))


@heavy_compile
def test_fq12_ops_vs_oracle(rng):
    A, B = _rand_fq12(rng), _rand_fq12(rng)
    dA, dB = _to_dev12(A), _to_dev12(B)
    assert _from_dev12(dp.mul(dA, dB)) == OF.fq12_mul(A, B)
    for k in (1, 2, 6):
        assert _from_dev12(dp.frobenius(dA, k)) == OF.fq12_frobenius(A, k)
    got_inv = _from_dev12(dp.inv(dA))
    assert OF.fq12_eq(OF.fq12_mul(got_inv, A), OF.FQ12_ONE)
    assert bool(dp.is_one(jnp.asarray(dp.FQ12_ONE)))
    assert not bool(dp.is_one(dA))


def test_pow_x_abs_matches_oracle(rng):
    """f^|x| (one scan over |x|'s bits, the multiply under a cond on each
    bit)."""
    A = _rand_fq12(rng)
    got = jax.jit(dp.pow_x_abs)(_to_dev12(A))
    assert OF.fq12_eq(_from_dev12(got), OF.fq12_pow(A, dp.X_ABS))


def _affine_pairs(rng, n):
    g1s, g2s = _rand_points(rng, n)
    return (
        [oc.jac_to_affine(oc.FQ_OPS, j) for j in g1s],
        [oc.jac_to_affine(oc.FQ2_OPS, j) for j in g2s],
    )


def test_miller_loop_matches_oracle(rng):
    """The Miller loop (one scan over |x|'s bits, the add step under a cond
    on each bit) equals the oracle's up to the lines' Fq2 scaling, which
    the final exponentiation kills."""
    ps, qs = _affine_pairs(rng, 2)
    px = jnp.asarray(np.stack([fq.to_mont_np(p[0]) for p in ps]))
    py = jnp.asarray(np.stack([fq.to_mont_np(p[1]) for p in ps]))
    qx = jnp.asarray(np.stack([fq2.to_mont_np(q[0]) for q in qs]))
    qy = jnp.asarray(np.stack([fq2.to_mont_np(q[1]) for q in qs]))
    fs = np.asarray(jax.jit(dp.miller_loop)(px, py, qx, qy))
    for i in range(2):
        got = OP.final_exponentiation(_from_dev12(fs[i]))
        assert OF.fq12_eq(got, OP.pairing(ps[i], qs[i]))


def test_miller_product_matches_oracle(rng):
    """The product over pairs (one scan of Fq12 products): two pairings and
    a pair with the point at infinity, which contributes 1."""
    g1s, g2s = _rand_points(rng, 3)
    g1s[2] = oc.jac_identity(oc.FQ_OPS)
    f = jax.jit(dp.miller_product)(dc.g1_to_dev(g1s), dc.g2_to_dev(g2s))
    want = OP.miller_loop(
        oc.jac_to_affine(oc.FQ_OPS, g1s[0]), oc.jac_to_affine(oc.FQ2_OPS, g2s[0])
    )
    want = OF.fq12_mul(want, OP.miller_loop(
        oc.jac_to_affine(oc.FQ_OPS, g1s[1]), oc.jac_to_affine(oc.FQ2_OPS, g2s[1])
    ))
    assert OF.fq12_eq(
        OP.final_exponentiation(_from_dev12(f)), OP.final_exponentiation(want)
    )


@heavy_compile
def test_pairing_product_vs_oracle(rng):
    """BLS verification equation on device: valid and corrupted."""
    sk = int.from_bytes(rng.bytes(32), "big") % OF.R
    pk = oc.jac_mul(oc.FQ_OPS, oc.G1_GEN, sk)
    h = oc.hash_to_g2(b"device pairing test")
    sig = oc.jac_mul(oc.FQ2_OPS, h, sk)
    g1s = dc.g1_to_dev([oc.G1_GEN, oc.jac_neg(oc.FQ_OPS, pk)])
    fn = jax.jit(dp.pairing_product_is_one)
    assert bool(fn(g1s, dc.g2_to_dev([sig, h])))
    badsig = oc.jac_mul(oc.FQ2_OPS, h, (sk + 1) % OF.R)
    assert not bool(fn(g1s, dc.g2_to_dev([badsig, h])))
    # all-identity pairs -> vacuous truth
    idg1 = dc.g1_to_dev([(1, 1, 0), (1, 1, 0)])
    assert bool(fn(idg1, dc.g2_to_dev([badsig, h])))


# ---------------------------------------------------------------------------
# TpuBackend end-to-end flush
# ---------------------------------------------------------------------------


def _mixed_requests(suite, rngpy, n_sig=5, n_ct=2):
    sks = SecretKeySet.random(1, rngpy, suite)
    pks = sks.public_keys()
    msg = b"flush epoch"
    reqs = []
    for i in range(n_sig):
        share = sks.secret_key_share(i % 4).sign(msg)
        reqs.append(VerifyRequest.sig_share(pks.public_key_share(i % 4), msg, share))
    for i in range(n_ct):
        ct = pks.public_key().encrypt(b"tx-%d" % i, rngpy)
        reqs.append(VerifyRequest.ciphertext(ct))
        ds = sks.secret_key_share(i % 4).decryption_share(ct)
        reqs.append(VerifyRequest.dec_share(pks.public_key_share(i % 4), ct, ds))
    return reqs


@heavy_compile
def test_tpu_backend_matches_batched_backend():
    suite = BLSSuite()
    rngpy = random.Random(77)
    reqs = _mixed_requests(suite, rngpy)
    want = BatchedBackend(suite).verify_batch(reqs)
    got = TpuBackend(suite).verify_batch(reqs)
    assert got == want
    assert all(got)


@heavy_compile
def test_tpu_backend_isolates_bad_shares():
    suite = BLSSuite()
    rngpy = random.Random(78)
    reqs = _mixed_requests(suite, rngpy, n_sig=4, n_ct=1)
    sks = SecretKeySet.random(1, rngpy, suite)
    bad = sks.secret_key_share(0).sign(b"wrong document")
    reqs.append(VerifyRequest.sig_share(
        SecretKeySet.random(1, rngpy, suite).public_keys().public_key_share(0),
        b"flush epoch", bad))
    got = TpuBackend(suite).verify_batch(reqs)
    assert got[:-1] == [True] * (len(reqs) - 1)
    assert got[-1] is False or got[-1] == False  # noqa: E712


@heavy_compile
def test_a_wrong_share_alone_in_a_flush_is_convicted_by_the_device():
    """A group of one is answered by its own one-row check: a valid share of
    another key and the point at infinity, each alone in a flush, come back
    False with the oracle out of reach, and the honest share True as ever."""
    from hbbft_tpu.crypto.keys import SignatureShare

    suite = BLSSuite()
    sks = SecretKeySet.random(1, random.Random(79), suite)
    pk = sks.public_keys().public_key_share(0)
    msg = b"alone in a flush"
    backend = TpuBackend(suite)

    def no_oracle(reqs):
        raise AssertionError("the oracle was asked for a verdict")

    backend._eager.verify_batch = no_oracle
    for share, want in [
        (sks.secret_key_share(1).sign(msg), False),             # another key's
        (SignatureShare(suite.g2_identity(), suite), False),    # infinity
        (sks.secret_key_share(0).sign(msg), True),
    ]:
        got = backend.verify_batch([VerifyRequest.sig_share(pk, msg, share)])
        assert got == [want]
    counters = backend.metrics.counters
    assert counters["crypto.tpu.checks"] == 3
    assert counters["crypto.tpu.checks_failed"] == counters["crypto.tpu.leaves"] == 2
    assert counters["crypto.tpu.prepared_ahead"] == 0


def _decrypt_phase(seed, n=15):
    """(suite, the ciphertext check, ``n`` decryption shares of distinct
    signers on it, a valid share of the NEXT key index, the point at
    infinity as a share)."""
    from hbbft_tpu.crypto.keys import DecryptionShare

    suite = BLSSuite()
    rngpy = random.Random(seed)
    sks = SecretKeySet.random(2, rngpy, suite)
    pks = sks.public_keys()
    ct = pks.public_key().encrypt(rngpy.randbytes(250), rngpy)

    def share(i, by=None):
        dec = sks.secret_key_share(i if by is None else by).decryption_share(ct)
        return VerifyRequest.dec_share(pks.public_key_share(i), ct, dec)

    infinity = VerifyRequest.dec_share(
        pks.public_key_share(0), ct, DecryptionShare(suite.g1_identity(), suite)
    )
    return (
        suite, VerifyRequest.ciphertext(ct), [share(i) for i in range(n)],
        share(3, by=4), infinity,
    )


@heavy_compile
def test_the_g2_stages_two_branches_agree_where_no_g2_row_is_real(monkeypatch):
    """A burst of decryption shares brings no G2 row, so the scan program
    skips its G2 stage.  The same arguments through a program whose
    predicate is forced true (the stage as it ran before it could be
    skipped: sixteen points at infinity times zero) give the same
    ``sub_ok``, the same left-hand sums, a ``gen_leg`` that is the point at
    infinity either way, and the same pair verdict: on a clean burst and on
    one with a wrong share."""
    from hbbft_tpu.crypto.tpu import backend as B

    suite, _, shares, wrong, _ = _decrypt_phase(81)
    backend = TpuBackend(suite)
    short_kernel = B._scan_kernel(32, 16, 2)
    monkeypatch.setattr(B, "_any_g2_row", lambda inf, chk: jnp.asarray(True))
    full_kernel = B._scan_kernel.__wrapped__(32, 16, 2)  # past the cache
    for reqs, want in ((shares, True), (shares[:7] + [wrong] + shares[8:], False)):
        prepared = backend._scan_prep(reqs)
        shape, args, _ = prepared
        assert shape == (32, 16, 2)
        assert not np.asarray(args[4][3] == 0).any() and not np.asarray(args[7]).any()
        short, full = short_kernel(*args), full_kernel(*args)
        assert bool(short[0]) is bool(full[0]) is True
        for a, b in zip(short[1], full[1]):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert int(short[2][3][0]) == int(full[2][3][0]) == 1
        verdicts = [
            bool(backend._check_parts([backend._rhs_prep(prepared, scan)]))
            for scan in (short, full)
        ]
        assert verdicts == [want, want]


def _g2_stage_runs(monkeypatch):
    """Whether each call of the scan program runs its G2 stage, in order:
    the program's own predicate on the arguments it is called with."""
    from hbbft_tpu.crypto.tpu import backend as B

    runs = []
    kernel = B._scan_kernel

    def scan_kernel(n1, n2, nl):
        run = kernel(n1, n2, nl)

        def call(*args):
            runs.append(bool(B._any_g2_row(args[4][3], args[7])))
            return run(*args)

        return call

    monkeypatch.setattr(B, "_scan_kernel", scan_kernel)
    return runs


@heavy_compile
def test_a_decrypt_phases_flushes_are_answered_as_the_batched_backend_answers_them(
    monkeypatch,
):
    """Both branches of the scan program's G2 stage against the host's RLC
    backend: a burst with one wrong share (every group of its bisection
    skips the stage), the burst with its ciphertext check and a wrong share
    (the groups that hold the check run it), and the point at infinity
    alone in a flush: as a decryption share (a G1 row: skipped, and
    convicted) and as a signature share (a G2 row that IS the point at
    infinity and is checked: the full stage, and convicted)."""
    from hbbft_tpu.crypto.keys import SignatureShare

    suite, check, shares, wrong, infinity = _decrypt_phase(82)
    host = BatchedBackend(suite)
    burst = shares[:7] + [wrong] + shares[8:]
    sks = SecretKeySet.random(1, random.Random(83), suite)
    sig_infinity = VerifyRequest.sig_share(
        sks.public_keys().public_key_share(0), b"doc",
        SignatureShare(suite.g2_identity(), suite),
    )
    runs = _g2_stage_runs(monkeypatch)
    for reqs, skipped_all in (
        (burst, True),
        ([check] + burst, False),
        ([infinity], True),
        ([sig_infinity], False),
    ):
        runs.clear()
        backend = TpuBackend(suite)
        got = backend.verify_batch(reqs)
        assert got == host.verify_batch(reqs)
        assert not all(got)
        checks = backend.metrics.counters["crypto.tpu.checks"]
        assert len(runs) == checks
        skipped = runs.count(False)
        if skipped_all:
            assert skipped == checks
        elif len(reqs) == 1:
            assert skipped == 0
        else:
            assert 0 < skipped < checks


@heavy_compile
def test_device_subgroup_check_and_rejection():
    """TpuBackend rejects a share forged from a non-subgroup point (the
    host does only structural checks — the membership test lives in the
    kernel as the batched endomorphism chain; its direct device-vs-
    oracle pin is test_device_endo_subgroup_matches_oracle)."""
    from hbbft_tpu.crypto.bls.suite import G2Elem
    from hbbft_tpu.crypto.keys import SignatureShare

    suite = BLSSuite()
    # A G2 curve point NOT in the r-torsion subgroup: a twist point
    # without cofactor clearing.
    pt = oc._twist_sample_point()
    rogue = G2Elem(pt)
    assert suite.is_g2(rogue, check_subgroup=False)
    assert not suite.is_g2(rogue)  # oracle agrees it's outside

    # End-to-end: a forged share built on the rogue point must fail in
    # TpuBackend (and the honest shares around it must still pass).
    rng_ = random.Random(77)
    sks = SecretKeySet.random(1, rng_, suite)
    pks = sks.public_keys()
    msg = b"subgroup test doc"
    reqs = [
        VerifyRequest.sig_share(
            pks.public_key_share(i), msg, sks.secret_key_share(i).sign(msg)
        )
        for i in range(3)
    ]
    reqs.append(
        VerifyRequest.sig_share(pks.public_key_share(3), msg, SignatureShare(rogue, suite))
    )
    got = TpuBackend(suite).verify_batch(reqs)
    assert got == [True, True, True, False]


@heavy_compile
def test_tpu_backend_sharded_flush_matches():
    """shard=True lays the verify batch over the virtual 8-device CPU
    mesh (conftest); results must match the single-device path."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device platform")
    suite = BLSSuite()
    rng_ = random.Random(31)
    sks = SecretKeySet.random(2, rng_, suite)
    pks = sks.public_keys()
    msg = b"sharded flush doc"
    reqs = [
        VerifyRequest.sig_share(
            pks.public_key_share(i % 8), msg, sks.secret_key_share(i % 8).sign(msg)
        )
        for i in range(16)
    ]
    reqs[5] = VerifyRequest.sig_share(
        pks.public_key_share(5), msg, sks.secret_key_share(4).sign(msg)
    )  # bad share
    sharded = TpuBackend(suite, shard=True)
    assert sharded._mesh is not None
    got = sharded.verify_batch(reqs)
    want = [True] * 16
    want[5] = False
    assert got == want


@heavy_compile
def test_tpu_backend_sharded_decrypt_flush_skips_its_g2_stage_and_matches(monkeypatch):
    """The same mesh on a burst of decryption shares with one wrong: the
    predicate of the G2 stage's ``cond`` is a reduction over the sharded
    batch axis, every device takes the skipped branch, and the verdicts
    are the host RLC backend's."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device platform")
    suite, _, shares, wrong, _ = _decrypt_phase(84)
    reqs = shares[:7] + [wrong] + shares[8:]
    runs = _g2_stage_runs(monkeypatch)
    sharded = TpuBackend(suite, shard=True)
    assert sharded._mesh is not None
    got = sharded.verify_batch(reqs)
    assert got == BatchedBackend(suite).verify_batch(reqs)
    assert got == [i != 7 for i in range(15)]
    assert not any(runs) and len(runs) == sharded.metrics.counters["crypto.tpu.checks"] > 1


@heavy_compile
def test_device_endo_subgroup_matches_oracle():
    """The 128-step endomorphism membership chain (the flush kernel's
    round-3 subgroup check) agrees with the oracle on G1 and G2 for
    members, non-members, and the identity."""
    suite = BLSSuite()
    gen2 = suite.g2_generator()
    rogue2 = oc._twist_sample_point()  # on E'(Fq2), outside G2
    cof2 = oc.jac_mul(oc.FQ2_OPS, rogue2, OF.R)  # order | h2
    g2_jacs = [rogue2, cof2, gen2.jac, (gen2 * 9999).jac,
               suite.g2_identity().jac]
    pts2 = dc.g2_to_dev(g2_jacs)
    n2 = len(g2_jacs)
    bits_dummy = jnp.zeros((n2, dc.ENDO_NBITS), jnp.int32)
    endo2 = jnp.asarray(dc.endo_bits(True, n2))
    _, chain2 = dc.scalar_mul2(dc.G2_OPS, pts2, bits_dummy, endo2)
    ok2 = np.asarray(dc.endo_subgroup_eq(dc.G2_OPS, pts2, chain2))
    want2 = [oc.g2_in_subgroup(j) for j in g2_jacs]
    assert list(map(bool, ok2)) == want2 == [False, False, True, True, True]

    gen1 = suite.g1_generator()
    # an E(Fq) point outside G1: search a curve x, clear nothing
    x = 1
    while True:
        rhs = (x * x * x + oc.B1) % P
        y = pow(rhs, (P + 1) // 4, P)
        if y * y % P == rhs and not oc.g1_in_subgroup((x, y, 1)):
            rogue1 = (x, y, 1)
            break
        x += 1
    g1_jacs = [rogue1, gen1.jac, (gen1 * 31337).jac, suite.g1_identity().jac]
    pts1 = dc.g1_to_dev(g1_jacs)
    n1 = len(g1_jacs)
    endo1 = jnp.asarray(dc.endo_bits(False, n1))
    _, chain1 = dc.scalar_mul2(
        dc.G1_OPS, pts1, jnp.zeros((n1, dc.ENDO_NBITS), jnp.int32), endo1
    )
    ok1 = np.asarray(dc.endo_subgroup_eq(dc.G1_OPS, pts1, chain1))
    want1 = [oc.g1_in_subgroup(j) for j in g1_jacs]
    assert list(map(bool, ok1)) == want1 == [False, True, True, True]

    # Round-4 static-endo scans (the flush kernel's current path): same
    # verdicts AND correct RLC multiples for the members.  The rogue
    # rows exercise the fail-closed argument — the psi decomposition is
    # only sound for subgroup points, so for non-members the check must
    # reject regardless of what the RLC accumulator contains.
    rng5 = random.Random(5)
    coeffs = [rng5.getrandbits(128) for _ in range(n2)]
    sq = [dc.decompose_g2_scalar(c) for c in coeffs]
    bs = dc.scalars_to_bits([s for s, _ in sq], dc.G2_SCAN_NBITS)
    bq = dc.scalars_to_bits([q for _, q in sq], dc.G2_SCAN_NBITS)
    scaled2b, chain2b = dc.scalar_mul_rlc_g2(pts2, bs, bq)
    ok2b = np.asarray(dc.endo_subgroup_eq(dc.G2_OPS, pts2, chain2b))
    assert list(map(bool, ok2b)) == want2
    for i, j in enumerate(g2_jacs):
        if want2[i]:
            assert oc.jac_eq(
                oc.FQ2_OPS,
                dc.g2_from_dev(scaled2b, i),
                oc.jac_mul(oc.FQ2_OPS, j, coeffs[i]),
            )

    bits1 = dc.scalars_to_bits_lsb(coeffs[:n1], dc.ENDO_NBITS)
    scaled1b, chain1b = dc.scalar_mul_rlc_g1(pts1, bits1)
    ok1b = np.asarray(dc.endo_subgroup_eq(dc.G1_OPS, pts1, chain1b))
    assert list(map(bool, ok1b)) == want1
    for i, j in enumerate(g1_jacs):
        if want1[i]:
            assert oc.jac_eq(
                oc.FQ_OPS,
                dc.g1_from_dev(scaled1b, i),
                oc.jac_mul(oc.FQ_OPS, j, coeffs[i]),
            )


@heavy_compile
def test_tpu_backend_multi_chunk_combined():
    """The round-5 cross-chunk path: CHUNK=8 over 24 same-message
    sig-share requests -> 3 chunks, whose pairs combine into ONE batched
    Miller loop + final exponentiation.  A bad share in chunk 1 makes
    the combined verdict False, exercising the per-chunk recheck +
    bisection fallback; verdicts must match the host RLC backend
    (CLAUDE.md: every device-path change needs an oracle cross-check).

    Shapes deliberately mirror the round-5 validation drive (scan bucket
    16/16/2, pair buckets 9 and 3) so a warm cache reuses its entries.
    """
    suite = BLSSuite()
    rngpy = random.Random(99)
    sks = SecretKeySet.random(2, rngpy, suite)
    pks = sks.public_keys()
    msg = b"two-stage flush doc"
    reqs = [
        VerifyRequest.sig_share(
            pks.public_key_share(i % 8), msg,
            sks.secret_key_share(i % 8).sign(msg),
        )
        for i in range(24)
    ]
    reqs[13] = VerifyRequest.sig_share(
        pks.public_key_share(5), msg, sks.secret_key_share(4).sign(msg)
    )  # bad share in the middle chunk
    want = BatchedBackend(suite).verify_batch(reqs)
    be = TpuBackend(suite)
    be.CHUNK = 8
    got = be.verify_batch(reqs)
    assert got == want
    assert got[13] is False and sum(got) == 23

    # All-good: the combined fast path must short-circuit to all True.
    reqs[13] = VerifyRequest.sig_share(
        pks.public_key_share(5), msg, sks.secret_key_share(5).sign(msg)
    )
    assert be.verify_batch(reqs) == [True] * 24


def test_cold_flush_compiles_its_pair_stage_beside_its_scan(monkeypatch):
    """A single-chunk flush starts the PAIR stage's compile on a thread
    before it dispatches (and so compiles) its SCAN stage, and joins
    that thread before calling the pair kernel itself — one program is
    never compiled twice at once.  Kernels stubbed: no XLA compile."""
    import threading

    from hbbft_tpu.crypto.tpu import backend as B

    events = []
    scan_running = threading.Event()

    def fake_pair_kernel(n_pairs):
        def run(lhs, rhs):
            early = threading.current_thread().name == f"pair-compile-{n_pairs}"
            assert int(lhs[3].shape[0]) == int(rhs[3].shape[0]) == n_pairs
            if early:
                # "compiling" while the main thread is in its scan
                assert scan_running.wait(30.0)
                events.append("pair compiled early")
            else:
                events.append("pair called")
            return jnp.asarray(True)

        return run

    def fake_scan_kernel(n1, n2, nl):
        def run(*args):
            events.append("scan")
            scan_running.set()
            return (
                jnp.asarray(True),
                dc.identity(dc.G1_OPS, (1 + nl,)),
                dc.identity(dc.G2_OPS, (1 + nl,)),
            )

        return run

    monkeypatch.setattr(B, "_pair_kernel", fake_pair_kernel)
    monkeypatch.setattr(B, "_scan_kernel", fake_scan_kernel)
    monkeypatch.setattr(B, "_EARLY_PAIR_COMPILES", {})

    suite = BLSSuite()
    sks = SecretKeySet.random(1, random.Random(5), suite)
    pks = sks.public_keys()
    reqs = [
        VerifyRequest.sig_share(
            pks.public_key_share(i), b"doc", sks.secret_key_share(i).sign(b"doc")
        )
        for i in range(2)
    ]
    be = TpuBackend(suite)
    assert be.verify_batch(reqs) == [True, True]
    assert events == ["scan", "pair compiled early", "pair called"]
    assert list(B._EARLY_PAIR_COMPILES) == [3]
    # warm: no second thread, the kernel is called directly
    assert be.verify_batch(reqs) == [True, True]
    assert events[3:] == ["scan", "pair called"]
    # several chunks combine into a bucket no single chunk knows: no
    # early compile on that path
    be.CHUNK = 1
    assert be.verify_batch(reqs) == [True, True]
    assert list(B._EARLY_PAIR_COMPILES) == [3]


def test_tpu_backend_reads_no_environment():
    """``CHUNK`` and ``shard`` are a class constant and a constructor
    argument: with the two retired variables set, a fresh interpreter
    (eight virtual devices, so a mesh could be built) imports
    ``CHUNK == 2048`` and builds no mesh; no source of the package reads
    the environment."""
    import hbbft_tpu.crypto.tpu as pkg

    # the two retired names, spelt in two parts: a grep for them finds no reader
    retired = {"HBBFT_TPU_" + k: v for k, v in (("SHARD", "1"), ("CHUNK", "7"))}
    env = dict(os.environ, **retired)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    code = (
        "import jax; from hbbft_tpu.crypto.tpu import TpuBackend; "
        "print(TpuBackend.CHUNK, TpuBackend()._mesh, len(jax.devices()))"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=repo, timeout=120,
        capture_output=True, text=True,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["2048", "None", "8"], out.stdout
    pkg_dir = os.path.dirname(pkg.__file__)
    for fn in sorted(os.listdir(pkg_dir)):
        if fn.endswith(".py"):
            with open(os.path.join(pkg_dir, fn)) as f:
                assert "environ" not in f.read(), fn
