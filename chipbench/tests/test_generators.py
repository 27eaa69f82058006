"""Traffic from the seed: the same seed the same bytes, and the construction's
expected verdicts are the program's oracle's and the plain reference's."""

import hashlib
import itertools
import json
import os

import pytest

from chipbench import kinds
from chipbench.generators import decrypt_flushes
from chipbench.generators import sig_share_rounds as gen
from chipbench.reference import verify as V
from chipbench.reference.verify import Reference

from .conftest import ROOT

BIG_SEED = 2**31 + 12345


def _load(kind, name):
    with open(os.path.join(ROOT, "chipbench", kind, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def coin16():
    return _load("configs", "coin16")


def _by_reference(flush):
    reference = Reference()
    return [
        kinds.load(kind).verify(reference, *wire)
        for kind, wire in zip(flush.kinds, flush.wire)
    ]


def _by_oracle(flush):
    from hbbft_tpu.crypto.backend import EagerBackend
    from hbbft_tpu.crypto.bls.suite import BLSSuite

    return EagerBackend(BLSSuite()).verify_batch(flush.requests)


def _digest(flush):
    """Every request as the program sends it, its expected verdict and its
    wire form for the reference, each part behind its length."""
    h = hashlib.sha256()
    for req, kind, expected, wire in zip(
        flush.requests, flush.kinds, flush.expected, flush.wire
    ):
        sent = kinds.load(kind).wire_of(req)
        for part in (req.kind.encode(), *sent, bytes([expected]), *wire):
            h.update(len(part).to_bytes(8, "big"))
            h.update(part)
    return h.hexdigest()


# Taken on the parent of the PR that gave ``Flush`` its ``kinds`` (9c8bee3),
# with this digest over (kind, pk, document, share, expected, wire...).
PARENT_DIGESTS = {
    "clean16 3100000001 0":
        "be78bd62e171b703a6886850a966e40f2b3e476ed8bfd29fcf23f29c37f95b4b",
    "clean16 3100000001 -1":
        "aa8ad2ad6af3f7a118adcfcd2821d40f9f7e7cc38982ef85262b2903d4206b0e",
    "clean16 3100000001 1":
        "04bd9343bcc842507b57a150527c3c092bf0901613f78847f2e1b180ccd998d7",
    "clean16 3100002801 0":
        "c487ba4e89664fd67355270205377e2a4bd59b0715aaa6b0e5580345cbdb3aaf",
    "clean16 3100002801 -1":
        "c5d7e1c29abfeb96263732fcbbb1a2d8bb5d81873d307d9fd5ad07cbececc8e0",
    "clean16 3100002801 1":
        "469d48652023e42916aca078f3fed1522c71223b82826ad2457275de75ce071f",
    "byz5of16 3100000001 0":
        "2cbdf1acfac6f4403539deb09ce028a61a08b53f59ad02af6f606bee9da91975",
    "byz5of16 3100000001 -1":
        "aa8ad2ad6af3f7a118adcfcd2821d40f9f7e7cc38982ef85262b2903d4206b0e",
    "byz5of16 3100000001 1":
        "08852307d7f6198465193830acb695d0b18943470a89218aeed989b93b1027c1",
    "byz5of16 3100002801 0":
        "a9c8f208ba6781e1d51048b7c06466e8f08afed9cce87a0ef6ca833d603fedd0",
    "byz5of16 3100002801 -1":
        "c5d7e1c29abfeb96263732fcbbb1a2d8bb5d81873d307d9fd5ad07cbececc8e0",
    "byz5of16 3100002801 1":
        "aeaf0d3399c8e7147cde134d95896f633b4944b94f5091f55f322b43905ca921",
}


@pytest.mark.parametrize("case", sorted(PARENT_DIGESTS))
def test_sig_share_rounds_builds_what_it_built_on_the_parent(coin16, case):
    traffic_name, seed, index = case.split()
    traffic = _load("traffic", traffic_name)
    params = traffic["probe"] if index == "-1" else traffic["params"]
    keys = gen.make_keys(coin16, traffic["params"], int(seed))
    flush = gen.make_flush(coin16, params, int(seed), int(index), keys)
    assert flush.kinds == ["sig_share"] * 16
    assert _digest(flush) == PARENT_DIGESTS[case]


def test_same_seed_same_traffic_other_seed_other_traffic(coin16):
    params = {"requests": 4, "wrong": 1, "bisection_hit_nodes": 2}
    keys = gen.make_keys(coin16, params, BIG_SEED)
    again = gen.make_keys(coin16, params, BIG_SEED)
    assert keys.pk_bytes == again.pk_bytes
    a = gen.make_flush(coin16, params, BIG_SEED, 3, keys)
    b = gen.make_flush(coin16, params, BIG_SEED, 3, again)
    assert a.wire == b.wire and a.expected == b.expected
    assert [r.payload[2].to_bytes() for r in a.requests] == [w[2] for w in a.wire]
    assert [r.payload[0].to_bytes() for r in a.requests] == [w[0] for w in a.wire]
    other_flush = gen.make_flush(coin16, params, BIG_SEED, 4, keys)
    assert other_flush.wire[0][1] != a.wire[0][1]  # a fresh document
    other_seed = gen.make_keys(coin16, params, BIG_SEED + 1)
    assert other_seed.pk_bytes != keys.pk_bytes


def test_hit_nodes_counts_failing_groups():
    assert gen.hit_nodes(16, []) == 0
    assert gen.hit_nodes(16, [0]) == 4  # 16, 8, 4, 2
    assert gen.hit_nodes(16, [0, 15]) == 7
    counts = {}
    for pos in itertools.combinations(range(16), 5):
        n = gen.hit_nodes(16, pos)
        counts[n] = counts.get(n, 0) + 1
    assert counts == {7: 48, 8: 160, 9: 320, 10: 1536, 11: 1280, 12: 1024}


def test_byz5of16_fixes_the_work_of_every_round(coin16):
    import random

    params = _load("traffic", "byz5of16")["params"]
    assert params["bisection_hit_nodes"] == 10  # the mode of the 4368 sets
    rng = random.Random(5)
    drawn = {tuple(gen.wrong_positions(params, rng)) for _ in range(50)}
    assert len(drawn) > 20
    assert all(len(p) == 5 and gen.hit_nodes(16, p) == 10 for p in drawn)


def test_byz5of16_expected_verdicts_against_the_oracle_and_the_reference(coin16):
    params = _load("traffic", "byz5of16")["params"]
    keys = gen.make_keys(coin16, params, BIG_SEED)
    flush = gen.make_flush(coin16, params, BIG_SEED, 1, keys)
    assert len(flush.requests) == 16 and flush.expected.count(False) == 5
    assert _by_oracle(flush) == flush.expected
    assert _by_reference(flush) == flush.expected


def test_wrong_kinds_are_dealt_in_turn_and_judged_false(coin16):
    params = _load("traffic", "byz5of16")["probe"]
    assert params["wrong_kinds"] == ["next_key", "identity"]
    keys = gen.make_keys(coin16, params, BIG_SEED)
    flush = gen.make_flush(coin16, params, BIG_SEED, -1, keys)
    bad = [i for i, ok in enumerate(flush.expected) if not ok]
    assert len(bad) == 2 and gen.hit_nodes(16, bad) == 4
    # the second wrong share is the point at infinity, in the program's
    # encoding of it, and the first a share that decodes to a point
    assert flush.wire[bad[1]][2] == bytes(193)
    assert flush.wire[bad[0]][2][0] == 1
    assert [r.payload[2].to_bytes() for r in flush.requests] == [w[2] for w in flush.wire]
    assert _by_oracle(flush) == flush.expected
    assert _by_reference(flush) == flush.expected
    with pytest.raises(ValueError):
        gen.make_flush(coin16, dict(params, wrong_kinds=["off_curve"]), 1, 1, keys)


# -- the decrypt phase ------------------------------------------------------

HB4 = {"name": "hb4", "threshold": 1, "validators": 4}


@pytest.mark.parametrize(
    "params,expected",
    [
        # a ciphertext check and one wrong share of each kind
        ({"requests": 4, "ciphertext_checks": 1, "wrong": 2,
          "wrong_kinds": ["next_key", "identity"], "bisection_hit_nodes": 2},
         [True, True, False, False]),
        # the ciphertext check sent with another ciphertext's W
        ({"requests": 4, "ciphertext_checks": 1, "wrong": 1,
          "wrong_kinds": ["other_w"]},
         [False, True, True, True]),
        # shares alone, all valid
        ({"requests": 3, "payload_bytes": 100}, [True, True, True]),
    ],
)
def test_decrypt_flushes_three_times_the_same_verdicts(params, expected):
    """The program's oracle, the plain reference and the construction."""
    keys = decrypt_flushes.make_keys(HB4, params, BIG_SEED)
    flush = decrypt_flushes.make_flush(HB4, params, BIG_SEED, 1, keys)
    checks = params.get("ciphertext_checks", 0)
    assert flush.kinds == ["ciphertext"] * checks + ["dec_share"] * (4 - 1 if checks else 3)
    assert flush.expected == expected
    assert _by_oracle(flush) == flush.expected
    assert _by_reference(flush) == flush.expected
    # what the client sends is what the reference judged
    for req, kind, wire in zip(flush.requests, flush.kinds, flush.wire):
        assert req.kind == kind and kinds.load(kind).wire_of(req) == wire
    first = 0 if flush.kinds[0] == "ciphertext" else 1  # where (U, V, W) begin
    assert len(flush.wire[0][first + 1]) == params.get("payload_bytes", 32)
    # one ciphertext: every share carries the same (U, V, W), the check its (U, V)
    carried = {w[1:4] for k, w in zip(flush.kinds, flush.wire) if k == "dec_share"}
    assert len(carried) == 1
    assert flush.wire[0][first:first + 2] == next(iter(carried))[:2]
    again = decrypt_flushes.make_flush(HB4, params, BIG_SEED, 1, keys)
    assert again.wire == flush.wire and again.expected == flush.expected
    fresh = decrypt_flushes.make_flush(HB4, params, BIG_SEED, 2, keys)
    assert fresh.wire[0][first] != flush.wire[0][first]  # a fresh ciphertext


def test_the_references_ciphertext_is_the_programs():
    """The reference's hash input on seeded U, V is ``Ciphertext.hash_input``,
    its ciphertext verifies in the program, and the program decrypts it from
    threshold + 1 of the reference's shares."""
    from hbbft_tpu.crypto.bls.suite import BLSSuite, G1Elem, G2Elem
    from hbbft_tpu.crypto.keys import Ciphertext, DecryptionShare, PublicKeySet
    from hbbft_tpu.crypto.poly import Commitment

    suite = BLSSuite()
    params = {"requests": 3}
    keys = decrypt_flushes.make_keys(HB4, params, BIG_SEED)
    for v in (b"", b"a proposal", bytes(range(256)) * 3):
        ct = V.encrypt(keys.master_pk, v, 0xC0FFEE + len(v))
        program_ct = Ciphertext(G1Elem(ct.u), ct.v, G2Elem(ct.w), suite)
        assert program_ct.hash_input() == V.ciphertext_hash_input(ct.u_bytes, ct.v)
        assert program_ct.u.to_bytes() == ct.u_bytes
        assert program_ct.w.to_bytes() == ct.w_bytes
        assert program_ct.verify()
    shares = {
        i: DecryptionShare(G1Elem(V.decryption_share(keys.secrets[i], ct.u)), suite)
        for i in (0, 2)
    }
    # the key set's commitment from the reference's own points: the master
    # key and signer 0's key fix the degree-1 polynomial in the exponent
    c0 = G1Elem(keys.master_pk)
    c1 = G1Elem(keys.pk_jac[0]) + (-c0)
    pks = PublicKeySet(Commitment((c0, c1)), suite)
    assert pks.combine_decryption_shares(shares, program_ct) == v


def test_decrypt_flushes_pins_the_work_and_refuses_what_it_cannot_build():
    import random

    params = {"requests": 16, "ciphertext_checks": 1, "wrong": 3,
              "wrong_kinds": ["next_key", "other_w"], "bisection_hit_nodes": 6}
    rng = random.Random(7)
    drawn = {tuple(decrypt_flushes.wrong_positions(params, rng)) for _ in range(30)}
    assert len(drawn) > 5
    assert all(
        p[0] == 0 and len(p) == 3 and gen.hit_nodes(16, p) == 6 for p in drawn
    )
    for bad in (
        {"requests": 4, "wrong": 1, "wrong_kinds": ["off_curve"]},
        {"requests": 4, "wrong": 1, "wrong_kinds": ["other_w"]},  # no check to break
        {"requests": 4, "ciphertext_checks": 2},
        {"requests": 1, "ciphertext_checks": 1},
        {"requests": 2, "ciphertext_checks": 1, "wrong": 1},  # no next signer
    ):
        with pytest.raises(ValueError):
            decrypt_flushes.wrong_positions(bad, rng)
