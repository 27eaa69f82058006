"""The decrypt phase: the program against the benchmark's plain reference, at
a small size on the CPU.

``chipbench``'s ``decrypt_flushes`` flushes of its tests' deployment ``hb4``
(a ciphertext check and two decryption shares, one request wrong) go through
``TpuBackend.verify_batch`` with the two flush programs replaced by a host
evaluation of the legs that ``_build_legs`` made for the group being checked,
their right-hand points as ``_rhs_points`` resolved them under the dispatched
scan: the oracle's scalar multiplications, its r-torsion check on every marked row
and its product of pairings.  So leg construction for ``dec_share`` and
``ciphertext``, the shapes (the floor; a chunk's shape handed down to its
groups), bisection and the verdict logic under test are the program's own, and no XLA flush program is compiled.  Every answer is
compared with ``chipbench.kinds.<kind>.verify`` on that request's wire bytes.
The slow tier makes the same comparison on the real kernels.

The generator's own two tests (the oracle, the plain reference and the
construction agree; the reference's ciphertext is the program's) run here
too, in the tier-1 suite, where PR 29 could only leave them under
``chipbench/tests``.
"""

import os
import sys

import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import kinds  # noqa: E402
from chipbench.generators import decrypt_flushes  # noqa: E402
from chipbench.reference import verify as V  # noqa: E402
from chipbench.reference.verify import Reference  # noqa: E402
from hbbft_tpu.crypto.backend import EagerBackend  # noqa: E402
from hbbft_tpu.crypto.bls import curve as oc  # noqa: E402
from hbbft_tpu.crypto.bls import pairing as op  # noqa: E402
from hbbft_tpu.crypto.bls.suite import BLSSuite  # noqa: E402
from hbbft_tpu.crypto.tpu import backend as B  # noqa: E402
from hbbft_tpu.crypto.tpu import curve as dc  # noqa: E402

HB4 = {"name": "hb4", "threshold": 1, "validators": 4}
BIG_SEED = 2**31 + 12345
SEEDS = [2**31 + 30, 3100003001]


def _by_reference(flush):
    reference = Reference()
    return [
        kinds.load(kind).verify(reference, *wire)
        for kind, wire in zip(flush.kinds, flush.wire)
    ]


def _by_oracle(flush):
    return EagerBackend(BLSSuite()).verify_batch(flush.requests)


def _flush(params, seed, index=1):
    keys = decrypt_flushes.make_keys(HB4, params, seed)
    return decrypt_flushes.make_flush(HB4, params, seed, index, keys)


# -- the program on host-evaluated legs ---------------------------------------

def legs_hold(g2e, g1e, rhs):
    """What the two programs compute from one group's legs: every marked
    point in the r-torsion, and
    ``e(g1, sum c Q) * prod_l e(sum_{leg l} c P, rhs_l) == 1``."""
    if not all(oc.g2_in_subgroup(p) for _, p, chk in g2e if chk):
        return False
    if not all(oc.g1_in_subgroup(p) for _, p, _, chk in g1e if chk):
        return False
    gen_leg = oc.jac_identity(oc.FQ2_OPS)
    for c, p, _ in g2e:
        gen_leg = oc.jac_add(oc.FQ2_OPS, gen_leg, oc.jac_mul(oc.FQ2_OPS, p, c))
    sums = [oc.jac_identity(oc.FQ_OPS) for _ in rhs]
    for c, p, leg, _ in g1e:
        sums[leg] = oc.jac_add(oc.FQ_OPS, sums[leg], oc.jac_mul(oc.FQ_OPS, p, c))
    pairs = [(oc.jac_to_affine(oc.FQ_OPS, oc.G1_GEN), oc.jac_to_affine(oc.FQ2_OPS, gen_leg))]
    pairs += [
        (oc.jac_to_affine(oc.FQ_OPS, s), oc.jac_to_affine(oc.FQ2_OPS, q))
        for s, q in zip(sums, rhs)
    ]
    return op.multi_pairing_is_one(pairs)


def host_kernels(monkeypatch):
    """Replace the two programs by :func:`legs_hold` on the legs of the most
    recent ``_build_legs``, with the right-hand points of the most recent
    ``_rhs_points`` (a group prepared ahead is built after the pair stage of
    the check before it was called, and its points are resolved after its own
    scan's dispatch, so at a pair stage's call both are its own).  Returns the
    shapes asked for."""
    legs = []
    launched = []
    honest = B.TpuBackend._build_legs
    honest_points = B.TpuBackend._rhs_points

    def build_legs(self, reqs, coeffs):
        legs[:] = honest(self, reqs, coeffs)
        # nothing is hashed before the scan is dispatched
        assert {type(p) for p in legs[2]} <= {bytes, tuple}
        return tuple(legs)

    def rhs_points(self, rhs):
        assert rhs is legs[2]
        legs[2] = honest_points(self, rhs)
        assert all(isinstance(p, tuple) for p in legs[2])
        return legs[2]

    def scan_kernel(n1, n2, nl):
        launched.append((n1, n2, nl))
        return lambda *args: (
            jnp.asarray(True),
            dc.identity(dc.G1_OPS, (1 + nl,)),
            dc.identity(dc.G2_OPS, (1 + nl,)),
        )

    def pair_kernel(n_pairs):
        return lambda lhs, rhs: jnp.asarray(legs_hold(*legs))

    monkeypatch.setattr(B.TpuBackend, "_build_legs", build_legs)
    monkeypatch.setattr(B.TpuBackend, "_rhs_points", rhs_points)
    monkeypatch.setattr(B, "_scan_kernel", scan_kernel)
    monkeypatch.setattr(B, "_pair_kernel", pair_kernel)
    monkeypatch.setattr(B, "_compile_pair_kernel_early", lambda n_pairs: None)
    return launched


def _params(wrong_kind):
    params = {"requests": 3, "ciphertext_checks": 1, "payload_bytes": 24}
    if wrong_kind:
        params.update(wrong=1, wrong_kinds=[wrong_kind])
    return params


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("wrong_kind", [None, "next_key", "identity", "other_w"])
def test_the_program_answers_a_decrypt_flush_as_the_plain_reference(
    monkeypatch, wrong_kind, seed
):
    flush = _flush(_params(wrong_kind), seed)
    assert flush.kinds == ["ciphertext", "dec_share", "dec_share"]
    launched = host_kernels(monkeypatch)
    backend = B.TpuBackend(BLSSuite())

    def no_oracle(reqs):
        raise AssertionError("the oracle was asked for a verdict")

    backend._eager.verify_batch = no_oracle
    got = backend.verify_batch(flush.requests)
    assert got == _by_reference(flush) == flush.expected
    assert got.count(False) == (1 if wrong_kind else 0)
    # the flush and every group of it, the lone check among them
    assert set(launched) == {(32, 16, 2)}
    assert len(launched) == (1 if wrong_kind is None else 5 if got[0] else 3)


# -- a flush over the floor: the shape handed down (PR 35) ----------------------

HB18 = {"name": "hb18", "threshold": 5, "validators": 18}


@pytest.mark.parametrize("wrong_kind", [None, "next_key", "identity", "other_w"])
def test_a_flush_over_the_floor_and_its_groups_run_in_one_program(monkeypatch, wrong_kind):
    """The smallest decrypt flush that crosses a bucket: the check and 17
    shares, 35 G1 rows, ``scan(64,16,2)``, whose halves (17, 18, 8, 9, ...
    rows) the rule up to PR 34 sent to ``scan(32,16,2)``.  The chunk's shape
    is handed down, so the flush and every group bisection makes of it ask
    for that one program, and the answers are the plain reference's."""
    params = {"requests": 18, "ciphertext_checks": 1, "payload_bytes": 24}
    if wrong_kind:
        params.update(wrong=1, wrong_kinds=[wrong_kind])
    seed = 3500003502
    keys = decrypt_flushes.make_keys(HB18, params, seed)
    flush = decrypt_flushes.make_flush(HB18, params, seed, 1, keys)
    assert flush.kinds == ["ciphertext"] + ["dec_share"] * 17
    launched = host_kernels(monkeypatch)
    backend = B.TpuBackend(BLSSuite())

    def no_oracle(reqs):
        raise AssertionError("the oracle was asked for a verdict")

    backend._eager.verify_batch = no_oracle
    got = backend.verify_batch(flush.requests)
    assert got == _by_reference(flush) == flush.expected
    assert got.count(False) == (1 if wrong_kind else 0)
    assert set(launched) == {(64, 16, 2)}
    counters = backend.metrics.counters
    assert len(launched) == counters["crypto.tpu.checks"]
    # the point at infinity is a well-formed point and is bisected like the
    # others; every group below the flush is smaller than it: all of them
    # were handed its shape
    assert (len(launched) > 1) == (wrong_kind is not None)
    assert counters.get("crypto.tpu.groups_handed_shape", 0) == len(launched) - 1


@pytest.mark.slow
def test_the_real_kernels_answer_decrypt_flushes_as_the_plain_reference():
    """The same comparison through ``scan(32,16,2)`` and ``pair(3)`` as XLA
    compiles them for the CPU (minutes, cold)."""
    backend = B.TpuBackend(BLSSuite())
    for wrong_kind in (None, "next_key", "identity", "other_w"):
        flush = _flush(_params(wrong_kind), SEEDS[0])
        got = backend.verify_batch(flush.requests)
        assert got == _by_reference(flush) == flush.expected, wrong_kind


# -- the generator's own cases (chipbench/tests/test_generators.py) -----------

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
    flush = _flush(params, BIG_SEED)
    checks = params.get("ciphertext_checks", 0)
    assert flush.kinds == ["ciphertext"] * checks + ["dec_share"] * 3
    assert flush.expected == expected
    assert _by_oracle(flush) == flush.expected
    assert _by_reference(flush) == flush.expected
    # what the client sends is what the reference judged
    for req, kind, wire in zip(flush.requests, flush.kinds, flush.wire):
        assert req.kind == kind and kinds.load(kind).wire_of(req) == wire
    first = 0 if checks else 1  # where (U, V, W) begin
    assert len(flush.wire[0][first + 1]) == params.get("payload_bytes", 32)
    # one ciphertext: every share carries the same (U, V, W), the check its (U, V)
    carried = {w[1:4] for k, w in zip(flush.kinds, flush.wire) if k == "dec_share"}
    assert len(carried) == 1
    assert flush.wire[0][first:first + 2] == next(iter(carried))[:2]
    again = _flush(params, BIG_SEED)
    assert again.wire == flush.wire and again.expected == flush.expected
    fresh = _flush(params, BIG_SEED, index=2)
    assert fresh.wire[0][first] != flush.wire[0][first]  # a fresh ciphertext


def test_the_references_ciphertext_is_the_programs():
    """The reference's hash input on seeded U, V is ``Ciphertext.hash_input``,
    its ciphertext verifies in the program, and the program decrypts it from
    threshold + 1 of the reference's shares."""
    from hbbft_tpu.crypto.bls.suite import G1Elem, G2Elem
    from hbbft_tpu.crypto.keys import Ciphertext, DecryptionShare, PublicKeySet
    from hbbft_tpu.crypto.poly import Commitment

    suite = BLSSuite()
    keys = decrypt_flushes.make_keys(HB4, {"requests": 3}, BIG_SEED)
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
