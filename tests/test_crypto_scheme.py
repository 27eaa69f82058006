"""Threshold-scheme unit tests against the (insecure) scalar suite.

These exercise the suite-generic algebra: share interpolation, signature
combine stability across share subsets, encryption round-trips, bivariate
polynomial symmetry (the DKG invariant), and batch verification with
fault isolation.
"""

import random

import pytest

from hbbft_tpu.crypto.backend import BatchedBackend, EagerBackend, VerifyRequest
from hbbft_tpu.crypto.keys import SecretKey, SecretKeySet
from hbbft_tpu.crypto.poly import BivarPoly, Poly, interpolate, lagrange_coefficients
from hbbft_tpu.crypto.suite import ScalarSuite


@pytest.fixture
def suite():
    return ScalarSuite()


@pytest.fixture
def rng():
    return random.Random(1234)


def test_poly_interpolation(rng, suite):
    m = suite.scalar_modulus
    p = Poly.random(3, rng, m)
    pts = [(x, p.eval(x)) for x in (2, 5, 7, 11)]
    assert interpolate(pts, m) == p.eval(0)
    lam = lagrange_coefficients([1, 4, 6, 10], m)
    acc = sum(lam[i] * p.eval(i + 1) for i in lam) % m
    assert acc == p.eval(0)


def test_sign_combine_stable_across_subsets(rng, suite):
    sks = SecretKeySet.random(2, rng, suite)
    pks = sks.public_keys()
    msg = b"hello threshold world"
    shares = {i: sks.secret_key_share(i).sign(msg) for i in range(7)}
    sig_a = pks.combine_signatures({i: shares[i] for i in (0, 1, 2)})
    sig_b = pks.combine_signatures({i: shares[i] for i in (3, 5, 6)})
    sig_c = pks.combine_signatures(shares)
    assert sig_a.g2 == sig_b.g2 == sig_c.g2
    assert pks.verify_signature(msg, sig_a)
    assert not pks.verify_signature(b"other message", sig_a)


def test_share_verification(rng, suite):
    sks = SecretKeySet.random(1, rng, suite)
    pks = sks.public_keys()
    msg = b"doc"
    good = sks.secret_key_share(2).sign(msg)
    assert pks.public_key_share(2).verify_share(msg, good)
    assert not pks.public_key_share(3).verify_share(msg, good)  # wrong index
    assert not pks.public_key_share(2).verify_share(b"doc2", good)  # wrong msg


def test_encrypt_decrypt_roundtrip(rng, suite):
    sks = SecretKeySet.random(2, rng, suite)
    pks = sks.public_keys()
    msg = b"the quick brown fox jumps over the lazy dog"
    ct = pks.public_key().encrypt(msg, rng)
    assert ct.verify()
    shares = {i: sks.secret_key_share(i).decryption_share(ct) for i in (1, 3, 4)}
    for i, sh in shares.items():
        assert pks.public_key_share(i).verify_decryption_share(ct, sh)
    assert pks.combine_decryption_shares(shares, ct) == msg
    # A share from the wrong key fails verification.
    bad = sks.secret_key_share(0).decryption_share(ct)
    assert not pks.public_key_share(5).verify_decryption_share(ct, bad)


def test_regular_keys(rng, suite):
    sk = SecretKey.random(rng, suite)
    pk = sk.public_key()
    sig = sk.sign(b"vote payload")
    assert pk.verify(b"vote payload", sig)
    assert not pk.verify(b"other", sig)
    ct = pk.encrypt(b"dkg row bytes", rng)
    assert sk.decrypt(ct) == b"dkg row bytes"


def test_bivar_poly_symmetry_and_rows(rng, suite):
    m = suite.scalar_modulus
    bp = BivarPoly.random(2, rng, m)
    assert bp.eval(3, 8) == bp.eval(8, 3)
    row5 = bp.row(5)
    assert row5.eval(9) == bp.eval(5, 9)
    # Commitment consistency: committed row(x).eval(y) == committed eval(x, y)
    bc = bp.commitment(suite)
    assert bc.row(5).eval(9) == bc.eval(5, 9)
    assert bc.row(5).eval(9) == suite.g1_generator() * bp.eval(5, 9)
    # Interpolating row values at y=0 across t+1 x-points recovers p(0, y0):
    # node j learns p(i+1, j+1) from t+1 dealers' rows -> interpolate x->p(x, j+1) at 0.
    j = 4
    pts = [(i + 1, bp.eval(i + 1, j + 1)) for i in range(3)]
    assert interpolate(pts, m) == bp.eval(0, j + 1)


def test_batched_backend_matches_eager_and_isolates_faults(rng, suite):
    sks = SecretKeySet.random(2, rng, suite)
    pks = sks.public_keys()
    msg = b"common coin round 7"
    reqs = []
    for i in range(8):
        share = sks.secret_key_share(i).sign(msg)
        reqs.append(VerifyRequest.sig_share(pks.public_key_share(i), msg, share))
    # Corrupt two entries: wrong message and wrong signer index.
    bad1 = sks.secret_key_share(3).sign(b"tampered")
    reqs[3] = VerifyRequest.sig_share(pks.public_key_share(3), msg, bad1)
    reqs[6] = VerifyRequest.sig_share(
        pks.public_key_share(6), msg, sks.secret_key_share(5).sign(msg)
    )
    # Mix in ciphertext + decryption-share requests.
    ct = pks.public_key().encrypt(b"payload", rng)
    reqs.append(VerifyRequest.ciphertext(ct))
    ds = sks.secret_key_share(1).decryption_share(ct)
    reqs.append(VerifyRequest.dec_share(pks.public_key_share(1), ct, ds))
    reqs.append(VerifyRequest.dec_share(pks.public_key_share(2), ct, ds))  # bad

    eager = EagerBackend(suite).verify_batch(reqs)
    batched = BatchedBackend(suite).verify_batch(reqs)
    assert eager == batched
    expected = [True] * 8 + [True, True, False]
    expected[3] = False
    expected[6] = False
    assert batched == expected


def test_bls_elements_survive_pickling():
    """Serde round-trips (Broadcast pickles ciphertexts into RS shards)
    must not corrupt the lazy affine/bytes caches of point elements."""
    import pickle

    from hbbft_tpu.crypto.bls.suite import BLSSuite

    suite = BLSSuite()
    rng = random.Random(5)
    sks = SecretKeySet.random(1, rng, suite)
    pks = sks.public_keys()
    ct = pks.public_key().encrypt(b"pickled payload", rng)
    ct2 = pickle.loads(pickle.dumps(ct))
    assert ct2.to_bytes() == ct.to_bytes()
    sig = sks.secret_key_share(0).sign(b"msg")
    sig2 = pickle.loads(pickle.dumps(sig))
    assert sig2.g2 == sig.g2 and sig2.g2.to_bytes() == sig.g2.to_bytes()
    # Pickled points still verify.
    from hbbft_tpu.crypto.backend import EagerBackend, VerifyRequest

    ok = EagerBackend(suite).verify_batch(
        [VerifyRequest.sig_share(pks.public_key_share(0), b"msg", sig2)]
    )
    assert ok == [True]


def test_batch_affine_edge_cases():
    """Montgomery batch inversion: duplicates, identity, cached, garbage."""
    from hbbft_tpu.crypto.bls.suite import BLSSuite

    suite = BLSSuite()
    g = suite.g2_generator()
    p1 = g * 5
    p2 = g * 9
    ident = suite.g2_identity()
    dup = p1  # same object twice in the list
    cached = g * 7
    cached.affine()  # pre-warm
    garbage = "not a point"
    suite.batch_affine([p1, dup, ident, cached, garbage, p2])
    # All finite points now have exact affine forms.
    for p in (p1, p2, cached):
        x, y = p.affine()
        import hbbft_tpu.crypto.bls.curve as oc

        assert oc.g2_on_curve(x, y)
    assert ident.affine() is None
    # Values agree with the lazy path.
    q = suite.g2_generator() * 5
    assert p1.affine() == q.affine()


@pytest.mark.parametrize("group", ["g1", "g2"])
def test_batch_affine_converts_a_repeated_element_once(group):
    """An element listed many times is converted once (the count it
    returns), a decoded element (bytes cached) not at all, and every
    element's ``affine()`` and ``to_bytes()`` equal the per-element
    ``jac_to_affine`` result, identity points included."""
    import pickle

    from hbbft_tpu.crypto.bls import curve as oc
    from hbbft_tpu.crypto.bls.suite import BLSSuite

    suite = BLSSuite()
    gen = getattr(suite, f"{group}_generator")()
    ident = getattr(suite, f"{group}_identity")
    shared = gen * 11
    pts = [gen * k for k in (3, 5, 7)]
    elems = [shared, pts[0], ident(), shared, pts[1], shared, ident(), pts[2]]
    fresh = pickle.loads(pickle.dumps(elems))  # no memo crosses a pickle
    assert fresh[0] is fresh[3] is fresh[5]
    decoded = getattr(suite, f"{group}_from_bytes")((gen * 13).to_bytes())
    assert suite.batch_affine(fresh * 3 + [decoded]) == 6
    assert suite.batch_affine(fresh) == 0  # every cache already warm
    assert decoded.affine() == (gen * 13).affine()
    for got, ref in zip(fresh, elems):
        assert got.affine() == oc.jac_to_affine(ref.ops, ref.jac)
        assert got.to_bytes() == ref.to_bytes()
    assert fresh[2].to_bytes() == b"\x00" * (97 if group == "g1" else 193)


def test_native_kem_matches_python():
    """The native scalar-suite KEM (hbe_kem_encrypt/decrypt) is
    byte-identical to the pure-Python path: same ciphertext for the same
    rng draw, same plaintext back, same rejection of tampered
    ciphertexts."""
    import random

    from hbbft_tpu.crypto import keys as K
    from hbbft_tpu.crypto.suite import ScalarSuite

    suite = ScalarSuite()
    kem = K._scalar_kem(suite)
    if kem is None:
        import pytest

        pytest.skip("native engine unavailable")

    sk = K.SecretKey.random(random.Random(1), suite)
    pk = sk.public_key()
    for trial in range(4):
        msg = bytes([trial]) * (32 * (trial + 1))
        r = random.Random(100 + trial).randrange(1, suite.scalar_modulus)
        ct_native = kem.encrypt(pk, msg, r)
        # pure-Python reference with the same r
        u = suite.g1_generator() * r
        from hbbft_tpu.utils import canonical_bytes, kdf_stream, xor_bytes

        mask = kdf_stream(
            canonical_bytes(b"kem", (pk.g1 * r).to_bytes()), len(msg)
        )
        v = xor_bytes(msg, mask)
        w = suite.hash_to_g2(K._ciphertext_hash_input(u, v)) * r
        assert ct_native.u == u and ct_native.v == v and ct_native.w == w
        # decrypt round-trips on both paths
        assert kem.decrypt(sk, ct_native) == msg
        ct_py = K.Ciphertext(u, v, w, suite)
        assert sk.decrypt(ct_py) == msg
        # tampered v: both paths reject
        bad = K.Ciphertext(u, b"\x00" + v[1:], w, suite)
        assert sk.decrypt(bad) is None
        K._KEM_CACHE[suite.name] = None  # force Python path
        try:
            assert sk.decrypt(bad) is None
            assert sk.decrypt(ct_py) == msg
        finally:
            K._KEM_CACHE.pop(suite.name, None)


def test_encrypt_rng_stream_unchanged_by_fast_path():
    """PublicKey.encrypt draws exactly one randrange from the caller's
    rng regardless of which path serves it — equivalence tests between
    Python and native nets depend on identical rng consumption."""
    import random

    from hbbft_tpu.crypto import keys as K
    from hbbft_tpu.crypto.suite import ScalarSuite

    suite = ScalarSuite()
    sk = K.SecretKey.random(random.Random(2), suite)
    pk = sk.public_key()
    r1, r2 = random.Random(7), random.Random(7)
    ct_a = pk.encrypt(b"x" * 64, r1)
    K._KEM_CACHE[suite.name] = None  # force Python path
    try:
        ct_b = pk.encrypt(b"x" * 64, r2)
    finally:
        K._KEM_CACHE.pop(suite.name, None)
    assert r1.getstate() == r2.getstate()
    assert (ct_a.u, ct_a.v, ct_a.w) == (ct_b.u, ct_b.v, ct_b.w)


def test_native_combine_matches_pure_and_rejects_oversized_indices():
    """The scalar combine fast path must be value-identical to the pure
    Lagrange path, and indices that would TRUNCATE in a ctypes c_int32
    array (no OverflowError — verified behavior) must fall back to the
    pure path instead of combining at a silently wrong point."""
    import os
    import random

    from hbbft_tpu.crypto import keys as K
    from hbbft_tpu.crypto.suite import ScalarSuite

    suite = ScalarSuite()
    rng = random.Random(9)
    sks = K.SecretKeySet.random(1, rng, suite)
    pks = sks.public_keys()
    msg = b"combine-parity"

    def pure(fn, *a):
        os.environ["HBBFT_TPU_DKG_BATCH"] = "0"
        try:
            return fn(*a)
        finally:
            del os.environ["HBBFT_TPU_DKG_BATCH"]

    # ordinary indices: fast == pure
    shares = {i: sks.secret_key_share(i).sign(msg) for i in (0, 1)}
    assert (
        pks.combine_signatures(shares).to_bytes()
        == pure(pks.combine_signatures, shares).to_bytes()
    )
    ct = pks.public_key().encrypt(b"plain" * 20, rng)
    dshares = {i: sks.secret_key_share(i).decryption_share(ct) for i in (0, 1)}
    assert pks.combine_decryption_shares(dshares, ct) == pure(
        pks.combine_decryption_shares, dshares, ct
    )

    # an index past int32: x = i + 1 would truncate in the C call; the
    # fast path must defer so both paths agree ((i + 1) % r Lagrange).
    big = 2**32 + 2
    shares_big = {
        big: sks.secret_key_share(big).sign(msg),
        1: sks.secret_key_share(1).sign(msg),
    }
    assert (
        pks.combine_signatures(shares_big).to_bytes()
        == pure(pks.combine_signatures, shares_big).to_bytes()
    )
    dshares_big = {
        big: sks.secret_key_share(big).decryption_share(ct),
        1: dshares[1],
    }
    assert pks.combine_decryption_shares(dshares_big, ct) == pure(
        pks.combine_decryption_shares, dshares_big, ct
    )
