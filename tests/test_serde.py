"""Safe-codec tests: round trips, strictness, and Byzantine rejection.

The codec replaces the reference's ``bincode`` boundary (upstream
``src/honey_badger/honey_badger.rs`` serializes contributions before
threshold-encrypting them).  Committed payloads are attacker-authored, so
``loads`` must be total over arbitrary bytes: decode a registered value
or raise — never execute code, never construct unregistered types.
"""

import pickle
import random

import pytest

from hbbft_tpu.crypto.keys import Ciphertext, SecretKey
from hbbft_tpu.crypto.suite import ScalarSuite
from hbbft_tpu.protocols.dynamic_honey_badger import (
    Change,
    InternalContrib,
    JoinPlan,
    SignedKeyGenMsg,
    SignedVote,
)
from hbbft_tpu.protocols.honey_badger import EncryptionSchedule
from hbbft_tpu.protocols.sync_key_gen import SyncKeyGen
from hbbft_tpu.utils import serde
from hbbft_tpu.utils.serde import DecodeError

SUITE = ScalarSuite()


@pytest.fixture
def rng():
    return random.Random(42)


def roundtrip(obj):
    data = serde.dumps(obj)
    assert isinstance(data, bytes)
    out = serde.loads(data)
    assert out == obj
    # byte stability: same object -> same bytes
    assert serde.dumps(out) == data
    return out


# -- primitives -------------------------------------------------------------


def test_primitive_roundtrips():
    for obj in [
        None,
        True,
        False,
        0,
        1,
        -1,
        2**300,
        -(2**300),
        b"",
        b"\x00\xff" * 100,
        "",
        "unicode é中",
        (),
        (1, (2, (3,))),
        [],
        [1, "two", b"three", None],
        {},
        {"a": 1, 2: b"b", (1, 2): "tuple-key"},
    ]:
        roundtrip(obj)


def test_bool_int_distinction():
    assert serde.loads(serde.dumps(True)) is True
    assert serde.loads(serde.dumps(1)) == 1
    assert serde.dumps(True) != serde.dumps(1)


def test_unencodable_types_raise():
    with pytest.raises(serde.EncodeError):
        serde.dumps(object())
    with pytest.raises(serde.EncodeError):
        serde.dumps(lambda: None)
    with pytest.raises(serde.EncodeError):
        serde.dumps({1: object()})


# -- strictness over raw bytes ---------------------------------------------


def test_malformed_bytes_rejected():
    bad = [
        b"",
        b"\xff",
        b"\x03",  # truncated int
        b"\x03\x02\x00\x00\x00\x01\x05",  # bad sign byte
        b"\x03\x00\x00\x00\x00\x02\x00\x01",  # non-minimal int
        b"\x03\x01\x00\x00\x00\x00",  # negative zero
        b"\x04\xff\xff\xff\xff",  # bytes len >> input
        b"\x06\xff\xff\xff\xff",  # tuple count >> input
        b"\x05\x00\x00\x00\x01\xff",  # invalid utf-8
        b"\x10\x05bogus\x06\x00\x00\x00\x00",  # unknown struct
        b"\x11\x03xyz\x01\x00\x00\x00\x00",  # unknown suite
        serde.dumps((1, 2))[:-1],  # truncation
        serde.dumps((1, 2)) + b"\x00",  # trailing bytes
    ]
    for data in bad:
        assert serde.try_loads(data) is None, data
        with pytest.raises(DecodeError):
            serde.loads(data)


def test_depth_bomb_rejected():
    # 1000 nested tuples: encoder refuses to build it, decoder refuses
    # hand-rolled bytes at the same bound.
    data = b"\x06\x00\x00\x00\x01" * 1000 + b"\x00"
    assert serde.try_loads(data) is None


def test_pickle_bytes_rejected():
    for payload in [["tx"], {"a": 1}, object()]:
        try:
            blob = pickle.dumps(payload)
        except Exception:
            continue
        assert serde.try_loads(blob) is None


def test_duplicate_dict_key_rejected():
    one = serde.dumps(1)
    item = one + one
    data = b"\x08" + (2).to_bytes(4, "big") + item + item
    assert serde.try_loads(data) is None


# -- crypto types -----------------------------------------------------------


def test_ciphertext_roundtrip_and_decrypt(rng):
    sk = SecretKey.random(rng, SUITE)
    ct = sk.public_key().encrypt(b"payload", rng)
    ct2 = roundtrip(ct)
    assert isinstance(ct2, Ciphertext)
    assert sk.decrypt(ct2) == b"payload"


def test_group_element_range_enforced(rng):
    sk = SecretKey.random(rng, SUITE)
    ct = sk.public_key().encrypt(b"x", rng)
    data = bytearray(serde.dumps(ct))
    # Overwrite the first group element payload with r (out of range).
    idx = bytes(data).index(b"\x11")
    # tag(1) + namelen(1) + name + group(1) + len(4) -> payload
    name_len = data[idx + 1]
    payload_at = idx + 2 + name_len + 1 + 4
    data[payload_at : payload_at + 32] = SUITE.scalar_modulus.to_bytes(32, "big")
    assert serde.try_loads(bytes(data)) is None


def test_signature_and_votes_roundtrip(rng):
    sk = SecretKey.random(rng, SUITE)
    pk = sk.public_key()
    change = Change.node_change({"a": pk, "b": pk})
    vote = SignedVote("a", 0, 3, change, sk.sign(b"payload"))
    roundtrip(vote)
    roundtrip(InternalContrib(["t1", "t2"], (), (vote,)))
    roundtrip(EncryptionSchedule.tick_tock(2))


def test_vote_with_wrong_signature_type_rejected(rng):
    sk = SecretKey.random(rng, SUITE)
    change = Change.node_change({"a": sk.public_key()})
    vote = SignedVote("a", 0, 1, change, sk.sign(b"m"))
    data = serde.dumps(vote)
    # Splice: replace the struct name "svote"'s signature field by
    # re-encoding with a non-Signature: build the tuple by hand.
    forged = serde.dumps(("a", 0, 1, change, b"not-a-signature"))
    # direct unpack-level check via a hand-built struct frame
    frame = b"\x10" + bytes([len(b"svote")]) + b"svote" + forged
    assert serde.try_loads(frame) is None
    assert serde.loads(data) == vote


def test_change_cross_field_invariants_enforced(rng):
    sk = SecretKey.random(rng, SUITE)
    pk = sk.public_key()

    def frame(fields):
        return (
            b"\x10" + bytes([len(b"change")]) + b"change" + serde.dumps(fields)
        )

    # schedule change without a schedule -> would crash encrypt_on(None)
    assert serde.try_loads(frame(("encryption_schedule", (), None))) is None
    # schedule change smuggling validators
    assert (
        serde.try_loads(
            frame(
                (
                    "encryption_schedule",
                    (("a", pk),),
                    EncryptionSchedule.always(),
                )
            )
        )
        is None
    )
    # node change with empty validator set -> threshold -1
    assert serde.try_loads(frame(("node_change", (), None))) is None
    # node change smuggling a schedule
    assert (
        serde.try_loads(
            frame(("node_change", (("a", pk),), EncryptionSchedule.always()))
        )
        is None
    )
    # honest constructions still round-trip
    roundtrip(Change.node_change({"a": pk}))
    roundtrip(Change.encryption_schedule(EncryptionSchedule.tick_tock(2)))


def test_dkg_part_ack_roundtrip(rng):
    ids = ["n0", "n1", "n2", "n3"]
    sks = {i: SecretKey.random(rng, SUITE) for i in ids}
    pub = {i: sks[i].public_key() for i in ids}
    kg, part = SyncKeyGen.new("n0", sks["n0"], pub, 1, rng, SUITE)
    part2 = roundtrip(part)
    outcome = kg.handle_part("n0", part2, rng)
    assert outcome.is_valid and outcome.ack is not None
    roundtrip(outcome.ack)
    msg = SignedKeyGenMsg(0, "n0", part, sks["n0"].sign(b"kg"))
    roundtrip(msg)


def test_join_plan_roundtrip(rng):
    from hbbft_tpu.crypto.keys import SecretKeySet

    sks = SecretKeySet.random(1, rng, SUITE)
    pks = sks.public_keys()
    reg = {i: SecretKey.random(rng, SUITE).public_key() for i in "abcd"}
    plan = JoinPlan(
        2,
        pks,
        tuple(sorted(reg.items())),
        EncryptionSchedule.always(),
    )
    plan2 = roundtrip(plan)
    assert plan2.public_key_set.public_key() == pks.public_key()


def test_node_id_restricted_to_plain_scalars(rng):
    sk = SecretKey.random(rng, SUITE)
    change = Change.node_change({"a": sk.public_key()})
    # voter id as a tuple: encodable as a value, but rejected as node id
    forged = serde.dumps((("evil", "tuple"), 0, 1, change, sk.sign(b"m")))
    frame = b"\x10" + bytes([len(b"svote")]) + b"svote" + forged
    assert serde.try_loads(frame) is None


def test_unencodable_contribution_raises_at_input_boundary(rng):
    """API misuse raises a typed error BEFORE any state change — a bad
    transaction cannot crash the node epochs later (upstream analog:
    bincode's Serialize bound rejects at compile time)."""
    from hbbft_tpu.net import NetBuilder
    from hbbft_tpu.protocols.errors import ContributionNotEncodable
    from hbbft_tpu.protocols.honey_badger import HoneyBadger
    from hbbft_tpu.protocols.queueing_honey_badger import QueueingHoneyBadger

    class CustomTxn:
        pass

    net = (
        NetBuilder(4, seed=13)
        .num_faulty(0)
        .protocol(lambda ni, sink, rng: HoneyBadger(ni, sink))
        .build()
    )
    hb = net.node(0).protocol
    with pytest.raises(ContributionNotEncodable):
        hb.handle_input(CustomTxn(), rng)
    assert not hb.has_input  # no state change

    qnet = (
        NetBuilder(4, seed=13)
        .num_faulty(0)
        .protocol(lambda ni, sink, rng: QueueingHoneyBadger(ni, sink, batch_size=8))
        .build()
    )
    qhb = qnet.node(0).protocol
    with pytest.raises(ContributionNotEncodable):
        qhb.push_transaction(CustomTxn(), rng)
    assert len(qhb.queue) == 0  # never queued


def test_none_contribution_is_not_a_fault():
    """An honest proposer of None must not be faulted: decoded-None and
    decode-failure are distinct."""
    from hbbft_tpu.net import NetBuilder
    from hbbft_tpu.protocols.honey_badger import HoneyBadger

    net = (
        NetBuilder(4, seed=17)
        .num_faulty(0)
        .protocol(lambda ni, sink, rng: HoneyBadger(ni, sink))
        .build()
    )
    net.broadcast_input(lambda nid: None if nid == 0 else [f"tx-{nid}"])
    net.crank_until(
        lambda n: all(len(n.node(i).outputs) >= 1 for i in n.correct_ids)
    )
    assert net.correct_faults() == []
    batch = net.node(1).outputs[0]
    cm = batch.contribution_map()
    if 0 in cm:  # Subset may or may not include node 0's proposal
        assert cm[0] is None


# -- BLS suite --------------------------------------------------------------


def test_bls_ciphertext_roundtrip(rng):
    from hbbft_tpu.crypto.bls.suite import BLSSuite

    suite = BLSSuite()
    sk = SecretKey.random(rng, suite)
    ct = sk.public_key().encrypt(b"bls payload", rng)
    ct2 = roundtrip(ct)
    assert sk.decrypt(ct2) == b"bls payload"


def test_suite_pinning_rejects_other_suites(rng):
    """A deployment pins its suite: bytes naming any other suite (e.g.
    the INSECURE ScalarSuite in a BLS network) are rejected at the frame
    level, so a Byzantine proposer cannot select forgeable crypto."""
    from hbbft_tpu.crypto.bls.suite import BLSSuite

    bls = BLSSuite()
    sk = SecretKey.random(rng, SUITE)
    scalar_ct = sk.public_key().encrypt(b"x", rng)
    data = serde.dumps(scalar_ct)
    # unpinned: decodes fine; pinned to BLS: rejected
    assert serde.loads(data) == scalar_ct
    assert serde.try_loads(data, suite=bls) is None
    with pytest.raises(DecodeError, match="not allowed"):
        serde.loads(data, suite=bls)
    # pinned to its own suite: fine
    assert serde.loads(data, suite=SUITE) == scalar_ct


def test_honey_badger_decodes_with_pinned_suite():
    """HoneyBadger passes its network suite into serde decoding."""
    from hbbft_tpu.net import NetBuilder
    from hbbft_tpu.protocols.honey_badger import HoneyBadger

    net = (
        NetBuilder(4, seed=21)
        .num_faulty(0)
        .protocol(lambda ni, sink, rng: HoneyBadger(ni, sink))
        .build()
    )
    net.broadcast_input(lambda nid: [f"tx-{nid}"])
    net.crank_until(
        lambda n: all(len(n.node(i).outputs) >= 1 for i in n.correct_ids)
    )
    batches = [net.node(i).outputs[0] for i in net.correct_ids]
    assert all(b == batches[0] for b in batches)
    assert net.correct_faults() == []


def test_bls_identity_point_roundtrip_and_canonical():
    from hbbft_tpu.crypto.bls.suite import BLSSuite

    suite = BLSSuite()
    ident = suite.g1_identity()
    assert suite.g1_from_bytes(ident.to_bytes()) == ident
    # non-canonical identity (flag 0 but nonzero body) rejected
    bad = b"\x00" + b"\x01" * 96
    with pytest.raises(ValueError):
        suite.g1_from_bytes(bad)
    ident2 = suite.g2_identity()
    assert suite.g2_from_bytes(ident2.to_bytes()) == ident2


def test_bls_non_subgroup_point_rejected():
    """An on-curve G1 point OUTSIDE the r-torsion subgroup must be
    rejected at decode (CLAUDE.md invariant: wire-sourced points get
    subgroup checks).  A random on-curve point lies outside the subgroup
    with overwhelming probability (cofactor ~2^125)."""
    from hbbft_tpu.crypto.bls import fields as F
    from hbbft_tpu.crypto.bls.suite import BLSSuite

    suite = BLSSuite()
    P = F.P
    x = 5
    while True:
        rhs = (x * x * x + 4) % P
        y = pow(rhs, (P + 1) // 4, P)  # sqrt (p % 4 == 3)
        if y * y % P == rhs:
            break
        x += 1
    enc = b"\x01" + x.to_bytes(48, "big") + y.to_bytes(48, "big")
    with pytest.raises(ValueError):
        suite.g1_from_bytes(enc)
    # sanity: same encoding with a generator multiple IS accepted
    g = suite.g1_generator() * 12345
    assert suite.g1_from_bytes(g.to_bytes()) == g


def test_bls_subgroup_memo_single_check():
    """The torsion memo: a second is_g1 on the same element skips the
    scalar mult (observable via the private flag)."""
    from hbbft_tpu.crypto.bls.suite import BLSSuite

    suite = BLSSuite()
    g = suite.g1_generator() * 7
    assert not g._subgroup_ok
    assert suite.is_g1(g)
    assert g._subgroup_ok
    assert suite.is_g1(g)  # second call: memo hit


def test_bls_off_curve_point_rejected(rng):
    from hbbft_tpu.crypto.bls.suite import BLSSuite

    suite = BLSSuite()
    sk = SecretKey.random(rng, suite)
    ct = sk.public_key().encrypt(b"x", rng)
    data = bytearray(serde.dumps(ct))
    # find the G1 payload (97 bytes after the group header) and corrupt y
    idx = bytes(data).index(b"\x11")
    name_len = data[idx + 1]
    payload_at = idx + 2 + name_len + 1 + 4
    data[payload_at + 96] ^= 1  # flip a bit of y
    assert serde.try_loads(bytes(data)) is None


def _bls_decrypt_frame(seed, n=15, payload=400):
    """One crypto-plane REQ body as ``RpcServiceClient`` sends it: ``n``
    ``dec_share`` requests on one fresh ciphertext, under one key set."""
    from hbbft_tpu.crypto.backend import VerifyRequest
    from hbbft_tpu.crypto.bls.suite import BLSSuite
    from hbbft_tpu.crypto.keys import SecretKeySet

    suite = BLSSuite()
    sks = SecretKeySet.random(2, random.Random(77), suite)  # the era's keys
    pks = sks.public_keys()
    rng = random.Random(seed)
    ct = pks.public_key().encrypt(rng.randbytes(payload), rng)
    reqs = tuple(
        VerifyRequest.dec_share(
            pks.public_key_share(i), ct,
            sks.secret_key_share(i).decryption_share(ct),
        )
        for i in range(n)
    )
    return suite, reqs, serde.dumps((seed, "verify", reqs))


def _pure_loads(data, suite=None):
    """The recursive decoder, whatever ``serde.loads`` would choose."""
    r = serde._Reader(data, None if suite is None else suite.name)
    obj = serde._decode(r, 0)
    if r.pos != len(r.data):
        raise DecodeError("trailing bytes")
    return obj


@pytest.mark.parametrize("decoder", ["loads", "recursive"])
def test_bls_decrypt_frame_validates_u_and_w_once(decoder):
    """A burst of 15 ``dec_share`` carries its ciphertext 15 times: 60
    points, of which ``U`` and ``W`` are validated once (28 hits in a cold
    memo) and, once an earlier frame has brought the key shares, 17 are
    new: ``U``, ``W`` and the 15 shares (43 hits).  Both decoders (the
    native scan's builder where an engine is loaded, the recursive one)
    decode through the suite's two methods."""
    from hbbft_tpu.crypto.bls import suite as bls_suite

    loads = serde.loads if decoder == "loads" else _pure_loads
    bls_suite._decode_validated.cache_clear()
    for seed, want_hits in ((1, 28), (2, 43), (3, 43)):
        suite, reqs, frame = _bls_decrypt_frame(seed)
        points0, hits0 = suite.decode_tally()
        req_id, op, body = loads(frame, suite=suite)
        points, hits = suite.decode_tally()
        assert (points - points0, hits - hits0) == (60, want_hits)
        assert (req_id, op) == (seed, "verify") and body == reqs
        ct = body[0].payload[1]
        assert all(r.payload[1].u is ct.u and r.payload[1].w is ct.w for r in body)
    assert bls_suite._decode_validated.cache_info().currsize == 15 + 3 * 17


def test_bls_eight_threads_decode_the_same_frame_and_agree():
    import sys
    import threading

    from hbbft_tpu.crypto.bls import suite as bls_suite

    bls_suite._decode_validated.cache_clear()
    suite, reqs, frame = _bls_decrypt_frame(9, n=4)
    start = threading.Barrier(8)
    got, tallies = [None] * 8, [None] * 8

    def work(k):
        start.wait()
        got[k] = serde.loads(frame, suite=suite)
        tallies[k] = suite.decode_tally()  # this thread's alone

    threads = [threading.Thread(target=work, args=(k,)) for k in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # switch inside the memo's misses
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert all(g == (9, "verify", reqs) for g in got)
    assert all(points == 16 for points, _ in tallies)
    # 4 keys + 4 shares + U + W, however the threads raced for them
    assert bls_suite._decode_validated.cache_info().currsize == 10
    bad = bytearray(frame)  # a bad point among cached ones
    bad[bad.index(reqs[3].payload[2].g1.to_bytes()) + 96] ^= 1
    with pytest.raises(DecodeError):
        serde.loads(bytes(bad), suite=suite)
    assert bls_suite._decode_validated.cache_info().currsize == 10


def test_scalar_ct_serde_cache_matches_recursive_encoder():
    """The pre-rendered `_serde_cache` memo the native KEM attaches must
    be byte-identical to what the recursive encoder emits — a wrong
    rendering would be a silent wire divergence."""
    import random

    from hbbft_tpu.crypto.keys import Ciphertext, SecretKey, scalar_ct_serde
    from hbbft_tpu.crypto.suite import ScalarSuite
    from hbbft_tpu.utils import serde

    suite = ScalarSuite()
    rng = random.Random(9)
    sk = SecretKey.random(rng, suite)
    for msg in (b"\x00" * 32, b"hello world", b""):
        ct = sk.public_key().encrypt(msg, rng)
        # recursive-path encoding of an equal ciphertext WITHOUT a memo
        bare = Ciphertext(ct.u, ct.v, ct.w, suite)
        want = serde.dumps(bare)
        got = scalar_ct_serde(
            ct.u.value.to_bytes(32, "big"), ct.v,
            ct.w.value.to_bytes(32, "big"),
        )
        assert got == want
        # and the memo'd object round-trips identically
        assert serde.dumps(ct) == want
        assert serde.loads(want, suite=suite) == bare


def test_native_scan_decode_matches_pure_decoder():
    """The C token scan + builder must ACCEPT exactly what the recursive
    decoder accepts (same objects) and REJECT exactly what it rejects —
    checked over round-trips of representative structures, truncations,
    and byte-flip corruptions of real encodings."""
    import random

    from hbbft_tpu.crypto.keys import SecretKey
    from hbbft_tpu.crypto.suite import ScalarSuite
    from hbbft_tpu.utils import serde

    lib = serde._native_scan(b"\x00")
    if lib is None:
        import pytest

        pytest.skip("native engine unavailable")

    suite = ScalarSuite()
    rng = random.Random(5)
    sk = SecretKey.random(rng, suite)
    ct = sk.public_key().encrypt(b"payload bytes", rng)
    samples = [
        None, True, False, 0, 1, -1, 2**300, -(2**300),
        b"", b"abc", "txt", "ünicode",
        (1, (2, b"x"), [None, True]), {"k": 1, 2: (3,)}, [],
        ct, (ct, ct), {"ct": ct},
        sk.public_key(),
    ]

    pure_loads = _pure_loads

    encodings = []
    for obj in samples:
        try:
            enc = serde.dumps(obj)
        except serde.EncodeError:
            continue
        encodings.append(enc)
        assert serde.loads(enc, suite=suite if obj is ct else None) is not None or obj is None
        # native result equals pure result exactly
        assert serde.loads(enc) == pure_loads(enc)

    # corruption sweep: every truncation point of a short encoding plus
    # byte flips across a ciphertext encoding — accept/reject must agree
    rng2 = random.Random(7)
    enc = serde.dumps((1, b"ab", "c", ct))
    for cut in range(len(enc)):
        data = enc[:cut]
        try:
            want = pure_loads(data)
        except serde.DecodeError:
            want = "ERR"
        try:
            got = serde.loads(data)
        except serde.DecodeError:
            got = "ERR"
        assert (got == "ERR") == (want == "ERR"), cut
        if want != "ERR":
            assert got == want
    for _ in range(300):
        i = rng2.randrange(len(enc))
        data = enc[:i] + bytes([enc[i] ^ (1 << rng2.randrange(8))]) + enc[i + 1:]
        try:
            want = pure_loads(data)
        except serde.DecodeError:
            want = "ERR"
        try:
            got = serde.loads(data)
        except serde.DecodeError:
            got = "ERR"
        assert (got == "ERR") == (want == "ERR"), i
        if want != "ERR":
            assert got == want


def test_depth_and_memo_boundaries_match_both_paths():
    """Depth 64 accepted, 65 rejected — by BOTH decoders (the native
    scanner takes the limits as arguments, so a constant edit cannot
    make them diverge); and a memo'd ciphertext nested near MAX_DEPTH
    falls back to the recursive encoder so dumps never emits bytes
    loads rejects."""
    import random

    from hbbft_tpu.crypto.keys import SecretKey
    from hbbft_tpu.crypto.suite import ScalarSuite
    from hbbft_tpu.utils import serde

    pure_loads = _pure_loads

    def nested(depth):
        return b"\x06\x00\x00\x00\x01" * depth + b"\x00"

    ok = nested(serde.MAX_DEPTH)  # value at depth MAX_DEPTH: accepted
    bad = nested(serde.MAX_DEPTH + 1)
    assert pure_loads(ok) == serde.loads(ok)  # both accept, same value
    for data in (bad,):
        import pytest

        with pytest.raises(serde.DecodeError):
            pure_loads(data)
        with pytest.raises(serde.DecodeError):
            serde.loads(data)

    # memo near the depth limit: round-trip must hold whenever dumps
    # succeeds
    suite = ScalarSuite()
    rng = random.Random(3)
    ct = SecretKey.random(rng, suite).public_key().encrypt(b"x" * 8, rng)
    assert "_serde_cache" in ct.__dict__
    obj = ct
    for _ in range(serde.MAX_DEPTH - 2):
        obj = (obj,)
    enc = serde.dumps(obj)  # deepest legal nesting for the ct subtree
    assert serde.loads(enc, suite=suite) is not None
    try:
        serde.dumps(((obj,),))
        deeper_ok = True
    except serde.EncodeError:
        deeper_ok = False
    assert not deeper_ok  # encoder refuses past the limit either way


def test_transport_boundary_unpackers_reject_malformed():
    """The live-wire message codecs (wire.py "transport-boundary types")
    are stricter than the in-process handlers; pin each reject branch by
    dumping a structurally-valid-but-semantically-bad object (frozen
    dataclasses construct anything) and asserting loads() refuses it."""
    import pytest

    from hbbft_tpu.ops.merkle import Proof
    from hbbft_tpu.protocols.binary_agreement import AbaMessage, TermMsg
    from hbbft_tpu.protocols.bool_set import BoolSet
    from hbbft_tpu.protocols.broadcast import EchoMsg, ReadyMsg, ValueMsg
    from hbbft_tpu.protocols.dynamic_honey_badger import DhbMessage
    from hbbft_tpu.protocols.honey_badger import DECRYPT, SUBSET, HbMessage
    from hbbft_tpu.protocols.sbv_broadcast import BValMsg
    from hbbft_tpu.protocols.sender_queue import SqMessage
    from hbbft_tpu.protocols.subset import BC, SubsetMessage
    from hbbft_tpu.utils import serde

    good_proof = Proof(b"leaf", 0, (b"h" * 32,), b"r" * 32)
    good_subset = SubsetMessage(1, BC, ValueMsg(good_proof))
    good_hb = HbMessage(0, SUBSET, None, good_subset)

    bad = [
        ReadyMsg(b"short-root"),                      # root not 32 bytes
        ReadyMsg("r" * 32),                           # root not bytes
        EchoMsg(b"not-a-proof"),                      # proof wrong type
        ValueMsg(None),
        Proof(b"v", -1, (), b"r" * 32),               # negative index
        Proof(b"v", 0, (b"short",), b"r" * 32),       # path hash not 32B
        BValMsg(1),                                   # int, not bool
        AbaMessage(-1, TermMsg(True)),                # negative round
        AbaMessage(0, b"junk"),                       # content wrong type
        SubsetMessage(1, "neither", TermMsg(True)),   # bad kind
        SubsetMessage(1, BC, AbaMessage(0, TermMsg(True))),  # ba inner in bc
        HbMessage(0, SUBSET, 3, good_subset),         # subset with proposer
        HbMessage(0, DECRYPT, 3, good_subset),        # wrong decrypt inner
        HbMessage(-1, SUBSET, None, good_subset),     # negative epoch
        HbMessage(0, "nope", None, good_subset),      # bad kind
        DhbMessage(-1, good_hb),                      # negative era
        DhbMessage(0, good_subset),                   # inner not HbMessage
        SqMessage("nope", 1),                         # unknown kind
        SqMessage("epoch_started", (0,)),             # not a 2-tuple
        SqMessage("epoch_started", (0, -1)),          # negative epoch
        SqMessage("epoch_started", (0, True)),        # bool is not an epoch
        SqMessage("algo", good_subset),               # not a Dhb/Hb message
        SqMessage("join_plan", b"forged"),            # not a JoinPlan
    ]
    for obj in bad:
        enc = serde.dumps(obj)
        with pytest.raises(serde.DecodeError):
            serde.loads(enc)
        assert serde.try_loads(enc) is None

    # BoolSet's constructor forbids mask 4, so hand-assemble the struct
    # frame: STRUCT "bools" + fields tuple(1) + int 4.
    raw = bytes(
        [0x10, 5] + list(b"bools") + [0x06, 0, 0, 0, 1]
        + [0x03, 0, 0, 0, 0, 1, 4]
    )
    with pytest.raises(serde.DecodeError):
        serde.loads(raw)
    # sanity: valid masks decode
    assert serde.loads(serde.dumps(BoolSet.both())) == BoolSet.both()
    assert serde.loads(serde.dumps(good_hb)) == good_hb
