"""The scan program's G2 stage under its ``cond``: the predicate on what
``_pack`` makes of real requests, the wiring of the two branches, and the
skipped branch's outputs against the full one's.

No flush program is compiled: the predicate is read as numpy, the wiring off
a jaxpr with the two scans replaced by stand-ins, the shapes by
``jax.eval_shape``.  The real kernels' two branches against each other and
against ``BatchedBackend`` are the slow tier's (``tests/test_tpu_crypto.py``).
"""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hbbft_tpu.crypto import flush_shapes
from hbbft_tpu.crypto.backend import VerifyRequest, _batch_coefficients
from hbbft_tpu.crypto.bls.suite import BLSSuite
from hbbft_tpu.crypto.keys import DecryptionShare, SecretKeySet, SignatureShare
from hbbft_tpu.crypto.tpu import backend as B
from hbbft_tpu.crypto.tpu import curve as dc


@pytest.fixture(scope="module")
def material():
    """kind -> requests: 15 decryption shares on one ciphertext, its check,
    signature shares on one document, and the point at infinity as a
    signature share and as a decryption share."""
    suite = BLSSuite()
    rng = random.Random(36)
    sks = SecretKeySet.random(1, rng, suite)
    pks = sks.public_keys()
    ct = pks.public_key().encrypt(bytes(range(64)), rng)
    pk0 = pks.public_key_share(0)
    return suite, {
        "dec": [
            VerifyRequest.dec_share(
                pks.public_key_share(i), ct, sks.secret_key_share(i).decryption_share(ct)
            )
            for i in range(15)
        ],
        "check": [VerifyRequest.ciphertext(ct)],
        "sig": [
            VerifyRequest.sig_share(
                pks.public_key_share(i), b"doc", sks.secret_key_share(i).sign(b"doc")
            )
            for i in range(2)
        ],
        "sig_identity": [
            VerifyRequest.sig_share(pk0, b"doc", SignatureShare(suite.g2_identity(), suite))
        ],
        "dec_identity": [
            VerifyRequest.dec_share(pk0, ct, DecryptionShare(suite.g1_identity(), suite))
        ],
    }


# name -> (the kinds of ``material`` a group holds, the predicate)
PREDICATE_CASES = {
    "padding_only": ((), False),
    "one_sig_share": (("sig",), True),
    "one_ciphertext": (("check",), True),
    "a_lone_identity_share": (("sig_identity",), True),
    "a_dec_share_burst": (("dec",), False),
    "a_burst_and_its_check": (("check", "dec"), True),
    "a_lone_identity_dec_share": (("dec_identity",), False),
    "a_burst_and_a_sig_share": (("dec", "sig"), True),
}


def _packed(suite, reqs):
    backend = B.TpuBackend(suite)
    g2e, g1e, rhs = backend._build_legs(reqs, _batch_coefficients(suite, reqs))
    n1, n2, nl = flush_shapes.scan_shape(reqs, len(g1e), len(g2e), max(len(rhs), 1))
    return g2e, backend._pack(g1e, g2e, n1, n2, nl)


@pytest.mark.parametrize("case", sorted(PREDICATE_CASES))
def test_the_predicate_on_what_pack_makes_of_a_group(material, case):
    """False exactly where every G2 lane is padding; the host's reading (no
    G2 entry) is the same answer."""
    suite, by_kind = material
    kinds, want = PREDICATE_CASES[case]
    reqs = [r for kind in kinds for r in by_kind[kind]]
    g2e, args = _packed(suite, reqs)
    inf, chk = np.asarray(args[4][3]), np.asarray(args[7])
    assert bool(B._any_g2_row(inf, chk)) is want
    assert bool(g2e) is want
    # every entry _build_legs makes is checked: why the two readings agree
    assert all(check == 1 for _, _, check in g2e)
    if case == "a_lone_identity_share":
        # a REAL row that is the point at infinity: the identity flag alone
        # would read "padding", the check flag says otherwise
        assert inf.all() and chk.sum() == 1
    # the scalars of a padding lane are 0, as the skipped branch assumes
    real = len(g2e)
    assert not np.asarray(args[5])[real:].any() and not np.asarray(args[6])[real:].any()


def _eqns(jaxpr):
    """Every equation of a jaxpr, with those of its sub-jaxprs, as (the
    ``cond`` equations around it, the equation)."""
    def walk(jaxpr, conds):
        for eqn in jaxpr.eqns:
            yield conds, eqn
            inner = conds + (eqn,) if eqn.primitive.name == "cond" else conds
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub, inner)

    return list(walk(jaxpr, ()))


def _scan_args(n1, n2, nl):
    spec = jax.ShapeDtypeStruct

    def pts(ops, batch):
        return tuple(spec(x.shape, x.dtype) for x in dc.identity(ops, batch))

    return (
        pts(dc.G1_OPS, (n1,)), spec((n1, dc.ENDO_NBITS), jnp.int32),
        spec((n1,), jnp.int32), spec((nl, n1), jnp.int32),
        pts(dc.G2_OPS, (n2,)), spec((n2, dc.G2_SCAN_NBITS), jnp.int32),
        spec((n2, dc.G2_SCAN_NBITS), jnp.int32), spec((n2,), jnp.int32),
        pts(dc.G1_OPS, ()),
    )


def cond_wiring(jaxpr):
    """(the ``cond`` equations, the primitives under each one's branches as
    [skipped branch, full branch], the primitives outside every ``cond``)."""
    eqns = _eqns(jaxpr)
    conds = [eqn for _, eqn in eqns if eqn.primitive.name == "cond"]
    inside = [
        [{e.primitive.name for _, e in _eqns(branch.jaxpr)} for branch in cond.params["branches"]]
        for cond in conds
    ]
    outside = {eqn.primitive.name for around, eqn in eqns if not around}
    return conds, inside, outside


def test_the_g2_stage_is_one_cond_and_nothing_of_it_lies_outside(monkeypatch):
    """The two scans replaced by stand-ins that leave a primitive of their
    own in the jaxpr (the G2 stage's ``population_count``, the G1 scan's
    ``clz``): exactly one ``cond``, the stage in its true branch alone, the
    G1 scan outside it, and the kernel's nine arguments."""
    def g1_scan(base, bits):
        marked = (base[0], base[1], base[2], base[3] | jax.lax.clz(bits[:, 0]))
        return marked, base

    def g2_stage(g2_pts, bits_s, bits_q):
        flags = jax.lax.population_count(bits_s[:, 0] | bits_q[:, 0])
        return flags == 0, tuple(x[0] for x in g2_pts)

    monkeypatch.setattr(dc, "scalar_mul_rlc_g1", g1_scan)
    monkeypatch.setattr(B, "_g2_stage", g2_stage)
    kernel = B._scan_kernel.__wrapped__(16, 16, 2)  # past the cache: a trace of stand-ins
    args = _scan_args(16, 16, 2)
    assert len(args) == 9
    conds, inside, outside = cond_wiring(jax.make_jaxpr(kernel)(*args).jaxpr)
    assert len(conds) == 1
    (skipped, full), = inside
    assert "population_count" in full and "population_count" not in skipped | outside
    assert "clz" in outside and "clz" not in skipped | full
    # the skipped branch is constants: no loop, no field product
    assert not skipped & {"scan", "while", "mul", "dot_general"}
    # the predicate is the program's own: a reduction over the G2 rows
    assert "reduce_or" in outside


def test_the_skipped_branch_has_the_full_branchs_shapes_and_dtypes():
    """``lax.cond`` would refuse at trace time what this catches in a
    quarter of a minute: the real stage, shapes only."""
    args = _scan_args(16, 16, 2)[4:7]
    full = jax.eval_shape(B._g2_stage, *args)
    assert jax.eval_shape(B._g2_stage_skipped, *args) == full
    sub2, gen_leg = full
    assert (sub2.shape, sub2.dtype) == ((16,), jnp.bool_)
    assert [x.shape for x in gen_leg] == [(2, 36)] * 3 + [()]


def test_the_skipped_branch_answers_as_sixteen_padding_rows_would():
    """All true, and the G2 identity with its flag set: the row the pair
    program skips."""
    n2 = 16
    pts = dc.g2_to_dev([B._IDENT2] * n2)
    bits = jnp.zeros((n2, dc.G2_SCAN_NBITS), jnp.int32)
    sub2, gen_leg = B._g2_stage_skipped(pts, bits, bits)
    assert np.asarray(sub2).all() and sub2.shape == (n2,)
    assert int(gen_leg[3]) == 1
    assert dc.g2_from_dev(gen_leg) == B._IDENT2
    assert not bool(B._any_g2_row(np.asarray(pts[3]), np.zeros(n2, np.int32)))
