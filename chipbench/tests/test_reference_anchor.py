"""The plain reference's arithmetic held against things outside this repo.

The reference's fields, curve and pairing began as a copy of the program's
oracle, so agreement between the two proves nothing about either.  These
tests hold the copy to what is published about BLS12-381 (the parameters of
draft-irtf-cfrg-pairing-friendly-curves, section 4.2.1, typed in here and
not imported) and to the algebra that defines a pairing: a map that is
bilinear and not degenerate on the two groups of prime order r IS the
pairing up to a fixed power coprime to r, and every such power gives the same
verdict on ``e(pk, H(doc)) == e(g1, sig)``, on
``e(share, H(U, V)) == e(pk, W)`` and on ``e(g1, W) == e(U, H(U, V))``.
"""

import random

from chipbench.reference import curve as C
from chipbench.reference import fields as F
from chipbench.reference import pairing as PR
from chipbench.reference import verify as V

# draft-irtf-cfrg-pairing-friendly-curves, 4.2.1 (BLS12-381)
X = -0xD201000000010000
P = int(
    "1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f624"
    "1eabfffeb153ffffb9feffffffffaaab", 16)
R = int("73eda753299d7d483339d80809a1d80553bda402fffe5bfeffffffff00000001", 16)
G1 = (
    int("17f1d3a73197d7942695638c4fa9ac0fc3688c4f9774b905a14e3a3f171bac58"
        "6c55e83ff97a1aeffb3af00adb22c6bb", 16),
    int("08b3f481e3aaa0f1a09e30ed741d8ae4fcf5e095d5d00af600db18cb2c04b3ed"
        "d03cc744a2888ae40caa232946c5e7e1", 16),
)
G2 = (
    (int("024aa2b2f08f0a91260805272dc51051c6e47ad4fa403b02b4510b647ae3d177"
         "0bac0326a805bbefd48056c8c121bdb8", 16),
     int("13e02b6052719f607dacd3a088274f65596bd0d09920b61ab5da61bbdc7f5049"
         "334cf11213945d57e5ac7d055d042b7e", 16)),
    (int("0ce5d527727d6e118cc9cdc6da2e351aadfd9baa8cbdd3a76d429a695160d12c"
         "923ac9cc3baca289e193548608b82801", 16),
     int("0606c4a02ea734cc32acd2b02bc28b99cb3e287e85a763af267492ab572e99ab"
         "3f370d275cec1da1aaa9075ff05f79be", 16)),
)


def test_parameters_are_the_published_ones_and_follow_from_x():
    assert (F.BLS_X, F.P, F.R) == (X, P, R)
    # the BLS12 family's polynomials in x
    assert R == X**4 - X**2 + 1
    assert P == (X - 1) ** 2 * R // 3 + X and (X - 1) ** 2 * R % 3 == 0
    assert C.N1 == P + 1 - (X + 1) and C.N1 % R == 0
    assert C.H1 == (X - 1) ** 2 // 3
    # G2's cofactor, (x^8 - 4x^7 + 5x^6 - 4x^4 + 6x^3 - 4x^2 - 4x + 13) / 9
    h2 = X**8 - 4 * X**7 + 5 * X**6 - 4 * X**4 + 6 * X**3 - 4 * X**2 - 4 * X + 13
    assert h2 % 9 == 0 and C.h2_cofactor() == h2 // 9


def test_generators_are_the_published_ones_on_their_curves_of_order_r():
    assert C.G1_GEN[:2] == G1 and C.G2_GEN[:2] == G2
    x, y = G1
    assert (y * y - x * x * x - 4) % P == 0          # E: y^2 = x^3 + 4
    (x0, x1), (y0, y1) = G2                           # E': y^2 = x^3 + 4(1 + u)
    x3 = ((x0**3 - 3 * x0 * x1 * x1) % P, (3 * x0 * x0 * x1 - x1**3) % P)
    assert ((y0 * y0 - y1 * y1 - x3[0] - 4) % P, (2 * y0 * y1 - x3[1] - 4) % P) == (0, 0)
    assert C.jac_is_identity(C.FQ_OPS, C.jac_mul(C.FQ_OPS, C.G1_GEN, R))
    assert C.jac_is_identity(C.FQ2_OPS, C.jac_mul(C.FQ2_OPS, C.G2_GEN, R))
    assert not C.jac_is_identity(C.FQ_OPS, C.jac_mul(C.FQ_OPS, C.G1_GEN, R - 1))


def _pair(a: int, b: int) -> F.Fq12E:
    p = C.jac_to_affine(C.FQ_OPS, C.jac_mul(C.FQ_OPS, C.G1_GEN, a))
    q = C.jac_to_affine(C.FQ2_OPS, C.jac_mul(C.FQ2_OPS, C.G2_GEN, b))
    return PR.pairing(p, q)


def test_the_pairing_is_bilinear_of_order_r_and_not_degenerate():
    rng = random.Random(381)
    a, b = rng.randrange(2, R), rng.randrange(2, R)
    e = _pair(1, 1)
    assert not F.fq12_is_one(e)
    assert F.fq12_is_one(F.fq12_pow(e, R))
    assert F.fq12_eq(_pair(a, 1), F.fq12_pow(e, a))
    assert F.fq12_eq(_pair(1, b), F.fq12_pow(e, b))
    assert F.fq12_eq(_pair(a, b), F.fq12_pow(e, a * b % R))
    assert F.fq12_eq(_pair(a, b), _pair(b, a))


def test_a_share_off_the_torsion_off_the_curve_or_at_infinity_is_refused():
    reference = V.Reference()
    stray = C._twist_sample_point()
    assert C.g2_on_curve(*C.jac_to_affine(C.FQ2_OPS, stray))
    assert not C.in_subgroup_slow(C.FQ2_OPS, stray)
    pk = V.g1_to_bytes(V.public_share(5))
    assert reference.sig_share(pk, b"doc", V.g2_to_bytes(stray)) is False
    assert reference.sig_share(pk, b"doc", bytes(193)) is False        # identity
    assert reference.sig_share(pk, b"doc", b"\x01" + bytes(192)) is False  # off the curve
    good = V.g2_to_bytes(V.sign(5, C.hash_to_g2(b"doc")))
    assert reference.sig_share(pk, b"doc", good) is True


# -- threshold decryption: a degree-2 key set, five signers ------------------

COEFFS = (0x1234567, 0x89ABCDE, 0xF012345)
MESSAGE = b"a proposal of thirty-seven bytes, say"


def _secret(x: int) -> int:
    return (COEFFS[0] + COEFFS[1] * x + COEFFS[2] * x * x) % R


def _phase():
    """(ciphertext, [(pk_bytes, share point)] of signers 0..4)."""
    ct = V.encrypt(V.public_share(_secret(0)), MESSAGE, 0xABCDEF0123456789)
    signers = [
        (V.g1_to_bytes(V.public_share(_secret(i + 1))),
         V.decryption_share(_secret(i + 1), ct.u))
        for i in range(5)
    ]
    return ct, signers


def _cofactor_torsion_point() -> C.Jac:
    """A point of E(Fq) whose order divides the cofactor: ``[r]`` of the
    first point of the curve found from x = 1 upwards that this leaves
    standing."""
    x = 1
    while True:
        rhs = (x * x * x + 4) % P
        y = pow(rhs, (P + 1) // 4, P)  # p = 3 mod 4
        if y * y % P == rhs:
            t = C.jac_mul(C.FQ_OPS, (x, y, 1), R)
            if not C.jac_is_identity(C.FQ_OPS, t):
                return t
        x += 1


def test_a_decryption_share_verifies_and_threshold_plus_one_decrypt():
    reference = V.Reference()
    ct, signers = _phase()
    assert ct.v != MESSAGE and len(ct.v) == len(MESSAGE)
    assert reference.ciphertext(ct.u_bytes, ct.v, ct.w_bytes) is True
    for pk, share in signers:
        assert reference.dec_share(
            pk, ct.u_bytes, ct.v, ct.w_bytes, V.g1_to_bytes(share)
        ) is True
    # Lagrange in the exponent, from any three of the five
    for indices in ((0, 1, 2), (4, 2, 0), (1, 3, 4)):
        shares = [signers[i][1] for i in indices]
        assert V.combine_decryption_shares(indices, shares, ct.v) == MESSAGE
    # two shares, or three of which one is another signer's, do not
    assert V.combine_decryption_shares(
        (0, 1), [signers[0][1], signers[1][1]], ct.v
    ) != MESSAGE
    assert V.combine_decryption_shares(
        (0, 1, 2), [signers[0][1], signers[1][1], signers[3][1]], ct.v
    ) != MESSAGE


def test_wrong_decryption_shares_are_refused_and_which_check_refuses():
    reference = V.Reference()
    ct, signers = _phase()
    pk, share = signers[0]
    args = (pk, ct.u_bytes, ct.v, ct.w_bytes)
    # a valid share of the next key: every point is sound, the equation fails
    next_share = V.g1_to_bytes(signers[1][1])
    assert reference.g1(next_share) is not None
    assert reference.dec_share(*args, next_share) is False
    # the point at infinity does not decode to a point
    assert reference.g1(bytes(97)) is None
    assert reference.dec_share(*args, bytes(97)) is False
    assert reference.dec_share(*args, b"\x01" + bytes(96)) is False  # off the curve
    # a valid share plus a point of the cofactor's torsion: on the curve,
    # and it SATISFIES the pairing equation (the pairing is trivial on the
    # cofactor's torsion), so only [r]P == O refuses it
    torsion = _cofactor_torsion_point()
    assert C.jac_is_identity(C.FQ_OPS, C.jac_mul(C.FQ_OPS, torsion, C.H1))
    stray = C.jac_add(C.FQ_OPS, share, torsion)
    stray_aff = C.jac_to_affine(C.FQ_OPS, stray)
    assert C.g1_on_curve(*stray_aff)
    assert not C.in_subgroup_slow(C.FQ_OPS, stray)
    h = reference.hashed(V.ciphertext_hash_input(ct.u_bytes, ct.v))
    w = reference.g2(ct.w_bytes)
    assert reference.pairings_equal(stray_aff, h, reference.g1(pk), w) is True
    assert reference.g1(V.g1_to_bytes(stray)) is None
    assert reference.dec_share(*args, V.g1_to_bytes(stray)) is False
    # the same share under a cut Miller loop: the control's knob reaches
    # this verifier too
    good = V.g1_to_bytes(share)
    assert reference.dec_share(*args, good) is True
    assert V.Reference(miller_bits=32).dec_share(*args, good) is False


def test_a_ciphertext_with_anothers_w_is_refused():
    reference = V.Reference()
    ct, signers = _phase()
    other = V.encrypt(V.public_share(_secret(0)), MESSAGE, 0x1122334455667788)
    assert reference.ciphertext(other.u_bytes, other.v, other.w_bytes) is True
    assert reference.g2(other.w_bytes) is not None
    assert reference.ciphertext(ct.u_bytes, ct.v, other.w_bytes) is False
    # and a share checked against that W is refused with it
    pk, share = signers[2]
    assert reference.dec_share(
        pk, ct.u_bytes, ct.v, other.w_bytes, V.g1_to_bytes(share)
    ) is False
    # V is part of what is hashed: one flipped bit of it and W no longer fits
    flipped = bytes([ct.v[0] ^ 1]) + ct.v[1:]
    assert reference.ciphertext(ct.u_bytes, flipped, ct.w_bytes) is False
    assert reference.ciphertext(bytes(97), ct.v, ct.w_bytes) is False
    assert V.Reference(miller_bits=32).ciphertext(ct.u_bytes, ct.v, ct.w_bytes) is False


def test_the_hash_input_frames_every_part_behind_its_length():
    u, v = b"\x01" + bytes(range(96)), b"payload"
    assert V.ciphertext_hash_input(u, v) == (
        (2).to_bytes(8, "big") + b"ct" + (97).to_bytes(8, "big") + u
        + (7).to_bytes(8, "big") + v
    )
    assert V.ciphertext_hash_input(u, b"") != V.ciphertext_hash_input(u + b"", b"\x00")
