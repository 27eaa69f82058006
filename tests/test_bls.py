"""BLS12-381 oracle tests: curve self-validation, pairing laws, and the
threshold scheme + protocols running over the real curve (small N).
"""

import functools
import random
import threading

import pytest

from hbbft_tpu.crypto.backend import BatchedBackend, EagerBackend, VerifyRequest
from hbbft_tpu.crypto.bls import BLSSuite
from hbbft_tpu.crypto.bls import curve as C
from hbbft_tpu.crypto.bls import fields as F
from hbbft_tpu.crypto.bls import suite as bls_suite
from hbbft_tpu.crypto.keys import SecretKeySet
from hbbft_tpu.net import NetBuilder
from hbbft_tpu.protocols.threshold_sign import ThresholdSign


@pytest.fixture(scope="module")
def suite():
    return BLSSuite()


@pytest.fixture
def rng():
    return random.Random(7)


def test_curve_selfcheck():
    C.selfcheck()


def test_field_tower():
    rng = random.Random(3)
    a = (rng.randrange(F.P), rng.randrange(F.P))
    b = (rng.randrange(F.P), rng.randrange(F.P))
    # Fq2 inverse and sqrt round-trips.
    assert F.fq2_eq(F.fq2_mul(a, F.fq2_inv(a)), F.FQ2_ONE)
    sq = F.fq2_sqr(a)
    r = F.fq2_sqrt(sq)
    assert r is not None and (F.fq2_eq(r, a) or F.fq2_eq(F.fq2_neg(r), a))
    # Fq12 inverse and Frobenius composition.
    x = tuple((rng.randrange(F.P), rng.randrange(F.P)) for _ in range(6))
    assert F.fq12_is_one(F.fq12_mul(x, F.fq12_inv(x)))
    f2 = F.fq12_frobenius(F.fq12_frobenius(x, 1), 1)
    assert F.fq12_eq(f2, F.fq12_frobenius(x, 2))
    # Frobenius is the p-power map: check multiplicativity frob(xy)=frob(x)frob(y)
    y = tuple((rng.randrange(F.P), rng.randrange(F.P)) for _ in range(6))
    assert F.fq12_eq(
        F.fq12_frobenius(F.fq12_mul(x, y), 1),
        F.fq12_mul(F.fq12_frobenius(x, 1), F.fq12_frobenius(y, 1)),
    )


def test_pairing_bilinearity(suite):
    g1, g2 = suite.g1_generator(), suite.g2_generator()
    a, b = 0xDEADBEEF, 0xCAFE
    assert suite.pairing_product_is_one([(g1 * a, g2 * b), (-(g1 * (a * b)), g2)])
    assert suite.pairing_product_is_one([(g1 * a, g2 * b), (g1 * a, -(g2) * b)])
    assert not suite.pairing_product_is_one([(g1, g2)])  # non-degenerate
    # identity legs are neutral
    assert suite.pairing_product_is_one([(suite.g1_identity(), g2)])


def test_threshold_scheme_over_bls(suite, rng):
    sks = SecretKeySet.random(1, rng, suite)
    pks = sks.public_keys()
    msg = b"real curve signing"
    shares = {i: sks.secret_key_share(i).sign(msg) for i in range(4)}
    assert pks.public_key_share(2).verify_share(msg, shares[2])
    assert not pks.public_key_share(2).verify_share(b"other", shares[2])
    sig_a = pks.combine_signatures({i: shares[i] for i in (0, 3)})
    sig_b = pks.combine_signatures({i: shares[i] for i in (1, 2)})
    assert sig_a.g2 == sig_b.g2
    assert pks.verify_signature(msg, sig_a)

    ct = pks.public_key().encrypt(b"secret payload", rng)
    assert ct.verify()
    ds = {i: sks.secret_key_share(i).decryption_share(ct) for i in (0, 2)}
    assert pks.public_key_share(0).verify_decryption_share(ct, ds[0])
    assert pks.combine_decryption_shares(ds, ct) == b"secret payload"


def test_batched_backend_over_bls(suite, rng):
    sks = SecretKeySet.random(1, rng, suite)
    pks = sks.public_keys()
    msg = b"coin round 1"
    reqs = [
        VerifyRequest.sig_share(
            pks.public_key_share(i), msg, sks.secret_key_share(i).sign(msg)
        )
        for i in range(4)
    ]
    # One corrupted share (signed by the wrong share key).
    reqs[2] = VerifyRequest.sig_share(
        pks.public_key_share(2), msg, sks.secret_key_share(3).sign(msg)
    )
    batched = BatchedBackend(suite).verify_batch(reqs)
    assert batched == EagerBackend(suite).verify_batch(reqs)
    assert batched == [True, True, False, True]


@pytest.mark.slow
def test_threshold_sign_protocol_over_bls():
    doc = b"bls consensus doc"
    net = (
        NetBuilder(4, seed=5)
        .suite(BLSSuite())
        .protocol(lambda ni, sink, rng: ThresholdSign(ni, doc, sink))
        .flush_every(4)
        .build()
    )
    net.broadcast_input(lambda nid: None)
    net.run_to_termination()
    sigs = [net.node(nid).outputs[0] for nid in net.correct_ids]
    assert len({s.g2 for s in sigs}) == 1
    assert net.node(0).netinfo.public_key_set.verify_signature(doc, sigs[0])
    assert net.correct_faults() == []


# ---------------------------------------------------------------------------
# Endomorphism subgroup checks (curve.py g1_in_subgroup / g2_in_subgroup)
# ---------------------------------------------------------------------------


def _sample_e_fq(rng):
    """Random point on E(Fq) (full group, order h1*r w.h.p.)."""
    while True:
        x = rng.randrange(F.P)
        rhs = (x * x * x + C.B1) % F.P
        y = pow(rhs, (F.P + 1) // 4, F.P)  # P % 4 == 3
        if y * y % F.P == rhs:
            return (x, y, 1)


def _prime_factors(n, bound=1_000_000):
    """Primes of n found by trial division; perfect-square remainders
    are reduced (h1/h2's large factors appear squared: h1 = 3*m^2)."""
    import math

    out = {}
    d = 2
    while d * d <= n and d < bound:
        while n % d == 0:
            out[d] = out.get(d, 0) + 1
            n //= d
        d += 1
    while n > 1:
        s = math.isqrt(n)
        if s * s == n:
            n = s
            continue
        out[n] = out.get(n, 0) + 1  # treat remainder as prime (h1/h2: it is)
        break
    return out


def _point_of_prime_order(ops, cof, h, ell, k):
    """[h / ell^k]cof has order ell^s (s <= k); reduce to exact order ell.
    Returns None if cof has no ell-component."""
    q = C.jac_mul(ops, cof, h // (ell**k))
    if C.jac_is_identity(ops, q):
        return None
    while True:
        nxt = C.jac_mul(ops, q, ell)
        if C.jac_is_identity(ops, nxt):
            return q
        q = nxt


def test_endo_checks_match_definitional():
    rng = random.Random(11)
    for _ in range(4):
        k = rng.randrange(1, F.R)
        p1 = C.jac_mul(C.FQ_OPS, C.G1_GEN, k)
        q2 = C.jac_mul(C.FQ2_OPS, C.G2_GEN, k)
        assert C.g1_in_subgroup(p1) and C.in_subgroup_slow(C.FQ_OPS, p1)
        assert C.g2_in_subgroup(q2) and C.in_subgroup_slow(C.FQ2_OPS, q2)
    # identity is a member
    assert C.g1_in_subgroup(C.jac_identity(C.FQ_OPS))
    assert C.g2_in_subgroup(C.jac_identity(C.FQ2_OPS))


def test_endo_psi_is_endomorphism():
    """psi respects addition and has eigenvalue x on G2 — i.e. the
    derived constants really are the untwist-Frobenius-twist map."""
    rng = random.Random(13)
    a = C.jac_mul(C.FQ2_OPS, C.G2_GEN, rng.randrange(1, F.R))
    b = C.jac_mul(C.FQ2_OPS, C.G2_GEN, rng.randrange(1, F.R))
    lhs = C.g2_psi(C.jac_add(C.FQ2_OPS, a, b))
    rhs = C.jac_add(C.FQ2_OPS, C.g2_psi(a), C.g2_psi(b))
    assert C.jac_eq(C.FQ2_OPS, lhs, rhs)
    # psi also acts as an endomorphism on the FULL twist group (needed
    # for soundness reasoning): check on a non-G2 point.
    tw = C._twist_sample_point()
    lhs = C.g2_psi(C.jac_add(C.FQ2_OPS, tw, a))
    rhs = C.jac_add(C.FQ2_OPS, C.g2_psi(tw), C.g2_psi(a))
    assert C.jac_eq(C.FQ2_OPS, lhs, rhs)


def test_endo_g1_soundness_cofactor_primes():
    """The passing set is a subgroup of E(Fq); rejecting a point of
    exact order ell for every prime ell | h1 kills the ell-primary
    component of the passing set, so only G1 (plus nothing) passes."""
    rng = random.Random(17)
    h1 = C.H1
    factors = _prime_factors(h1)
    pt = _sample_e_fq(rng)
    cof = C.jac_mul(C.FQ_OPS, pt, F.R)  # order | h1
    assert not C.jac_is_identity(C.FQ_OPS, cof)
    assert not C.g1_in_subgroup(cof)
    checked = 0
    for ell, k in sorted(factors.items()):
        q = _point_of_prime_order(C.FQ_OPS, cof, h1, ell, k)
        if q is not None:
            assert not C.g1_in_subgroup(q), f"order-{ell} point passed"
            assert not C.in_subgroup_slow(C.FQ_OPS, q)
            checked += 1
    assert checked >= 2  # the sample point w.h.p. has most components


def test_endo_g2_soundness_cofactor_primes():
    h2 = C.h2_cofactor()
    factors = _prime_factors(h2)
    tw = C._twist_sample_point()
    cof = C.jac_mul(C.FQ2_OPS, tw, F.R)  # order | h2
    assert not C.jac_is_identity(C.FQ2_OPS, cof)
    assert not C.g2_in_subgroup(cof)
    checked = 0
    for ell, k in sorted(factors.items()):
        q = _point_of_prime_order(C.FQ2_OPS, cof, h2, ell, k)
        if q is not None:
            assert not C.g2_in_subgroup(q), f"order-{ell} point passed"
            checked += 1
    assert checked >= 2
    # full-order twist point agrees with the definitional check
    assert not C.g2_in_subgroup(tw)
    assert not C.in_subgroup_slow(C.FQ2_OPS, tw)


def test_endo_matches_suite_membership(suite):
    """suite.is_g1/is_g2 (which now ride the endomorphism checks) still
    reject wire points off the subgroup."""
    rng = random.Random(23)
    tw = C._twist_sample_point()
    cof = C.jac_mul(C.FQ2_OPS, tw, F.R)
    from hbbft_tpu.crypto.bls.suite import G2Elem

    bad = G2Elem(cof)
    assert not suite.is_g2(bad)
    good = suite.g2_generator() * rng.randrange(1, F.R)
    assert suite.is_g2(good)


# -- the decode memo: a point's bytes are validated once ----------------------

GROUPS = ("g1", "g2")


def _decoder(suite, group):
    return suite.g1_from_bytes if group == "g1" else suite.g2_from_bytes


def _valid_bytes(suite, group, k):
    gen = suite.g1_generator() if group == "g1" else suite.g2_generator()
    return (gen * k).to_bytes()


def _encode(group, aff):
    cls = bls_suite.G1Elem if group == "g1" else bls_suite.G2Elem
    return cls((aff[0], aff[1], cls.ops.one)).to_bytes()


def _off_torsion(suite, group):
    """A valid point plus a point of the cofactor's torsion: on the curve,
    outside the r-torsion (chipbench/tests/test_reference_anchor.py builds
    the G1 one the same way)."""
    if group == "g1":
        ops, gen, sample = C.FQ_OPS, C.G1_GEN, _sample_e_fq(random.Random(17))
    else:
        ops, gen, sample = C.FQ2_OPS, C.G2_GEN, C._twist_sample_point()
    torsion = C.jac_mul(ops, sample, F.R)
    assert not C.jac_is_identity(ops, torsion)
    stray = C.jac_add(ops, C.jac_mul(ops, gen, 31337), torsion)
    assert (C.g1_on_curve_jac if group == "g1" else C.g2_on_curve_jac)(stray)
    return _encode(group, C.jac_to_affine(ops, stray))


def _bad_bytes(suite, group, kind):
    good = bytearray(_valid_bytes(suite, group, 424242))
    if kind == "off_curve":
        good[-1] ^= 1  # y's lowest bit
        return bytes(good)
    if kind == "off_torsion":
        return _off_torsion(suite, group)
    if kind == "noncanonical_identity":
        return b"\x00" + b"\x01" * (len(good) - 1)
    assert kind == "out_of_range"
    good[1:49] = F.P.to_bytes(48, "big")  # x = p: not a residue's name
    return bytes(good)


def _points_and_hits(suite, before=(0, 0)):
    points, hits = suite.decode_tally()
    return points - before[0], hits - before[1]


@pytest.mark.parametrize("group", GROUPS)
def test_decode_memo_second_decode_is_a_hit(suite, group):
    memo = bls_suite._decode_validated
    memo.cache_clear()
    decode = _decoder(suite, group)
    data = _valid_bytes(suite, group, 99991)
    t0 = suite.decode_tally()
    first = decode(data)
    assert _points_and_hits(suite, t0) == (1, 0)
    assert memo.cache_info().currsize == 1
    again = decode(bytes(bytearray(data)))  # equal bytes, another object
    assert _points_and_hits(suite, t0) == (2, 1)
    assert again is first and again == first
    assert first._subgroup_ok and first.to_bytes() == data
    # the other group's memo entries are its own: equal-looking input of
    # the wrong length is refused, not answered from this entry
    with pytest.raises(ValueError):
        _decoder(suite, "g2" if group == "g1" else "g1")(data)
    # a tally is the thread's own
    seen = []
    t = threading.Thread(target=lambda: seen.append(suite.decode_tally()))
    t.start()
    t.join()
    assert seen == [(0, 0)]


@pytest.mark.parametrize(
    "kind", ["off_curve", "off_torsion", "noncanonical_identity", "out_of_range"]
)
@pytest.mark.parametrize("group", GROUPS)
def test_decode_memo_refuses_bad_bytes_every_time(suite, group, kind):
    memo = bls_suite._decode_validated
    decode = _decoder(suite, group)
    decode(_valid_bytes(suite, group, 5))  # a valid neighbour is cached
    size = memo.cache_info().currsize
    bad = _bad_bytes(suite, group, kind)
    t0 = suite.decode_tally()
    for _ in range(3):
        with pytest.raises(ValueError):
            decode(bad)
    assert _points_and_hits(suite, t0) == (3, 0)
    assert memo.cache_info().currsize == size
    assert decode(_valid_bytes(suite, group, 5)) == (
        suite.g1_generator() if group == "g1" else suite.g2_generator()
    ) * 5


@pytest.mark.parametrize("group", GROUPS)
def test_decode_refuses_what_is_not_bytes(suite, group):
    data = _valid_bytes(suite, group, 3)
    for junk in (bytearray(data), memoryview(data), data.hex(), None, 7):
        with pytest.raises(ValueError):
            _decoder(suite, group)(junk)


@pytest.mark.parametrize("group", GROUPS)
def test_decode_memo_is_bounded_and_keeps_what_is_touched(
    suite, group, monkeypatch
):
    """Least-recently-used under a bound: a key share that every flush
    touches outlives floods of fresh points that are never asked for
    again.  The mechanism is held at a bound of 8; the bound that ships
    is read off the memo itself."""
    memo = bls_suite._decode_validated
    assert memo.cache_info().maxsize == bls_suite._DECODE_MEMO_SIZE == 4096
    small = functools.lru_cache(maxsize=8)(memo.__wrapped__)
    monkeypatch.setattr(bls_suite, "_decode_validated", small)
    decode = _decoder(suite, group)
    key = _valid_bytes(suite, group, 1009)
    decode(key)
    fresh = iter(range(2000, 2100))
    for flood in range(3):
        for _ in range(7):
            decode(_valid_bytes(suite, group, next(fresh)))
            assert small.cache_info().currsize <= 8
        t0 = suite.decode_tally()
        decode(key)
        assert _points_and_hits(suite, t0) == (1, 1), f"flood {flood}"
    assert small.cache_info().currsize == 8
    # untouched through a flood of the bound's size, it goes
    for _ in range(8):
        decode(_valid_bytes(suite, group, next(fresh)))
    t0 = suite.decode_tally()
    decode(key)
    assert _points_and_hits(suite, t0) == (1, 0)
    assert small.cache_info().currsize == 8
