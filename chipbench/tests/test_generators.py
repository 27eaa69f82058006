"""Traffic from the seed: the same seed the same bytes, and the construction's
expected verdicts are the program's oracle's and the plain reference's."""

import itertools
import json
import os

import pytest

from chipbench.generators import sig_share_rounds as gen
from chipbench.reference.verify import Reference

from .conftest import ROOT

BIG_SEED = 2**31 + 12345


def _load(kind, name):
    with open(os.path.join(ROOT, "chipbench", kind, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def coin16():
    return _load("configs", "coin16")


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
    from hbbft_tpu.crypto.backend import EagerBackend
    from hbbft_tpu.crypto.bls.suite import BLSSuite

    params = _load("traffic", "byz5of16")["params"]
    keys = gen.make_keys(coin16, params, BIG_SEED)
    flush = gen.make_flush(coin16, params, BIG_SEED, 1, keys)
    assert len(flush.requests) == 16 and flush.expected.count(False) == 5
    assert EagerBackend(BLSSuite()).verify_batch(flush.requests) == flush.expected
    reference = Reference()
    assert [reference.verify(*w) for w in flush.wire] == flush.expected


def test_wrong_kinds_are_dealt_in_turn_and_judged_false(coin16):
    from hbbft_tpu.crypto.backend import EagerBackend
    from hbbft_tpu.crypto.bls.suite import BLSSuite

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
    assert EagerBackend(BLSSuite()).verify_batch(flush.requests) == flush.expected
    reference = Reference()
    assert [reference.verify(*w) for w in flush.wire] == flush.expected
    with pytest.raises(ValueError):
        gen.make_flush(coin16, dict(params, wrong_kinds=["off_curve"]), 1, 1, keys)
