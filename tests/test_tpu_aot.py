"""Ahead-of-time compiles for a described (not attached) TPU v5e.

The TPU compiler is installed wherever jax[tpu] is, so the kernels of
the flush path can be lowered and compiled for one v5e chip on a box
that has none: a tiling the chip refuses, a program that does not fit
its memory, a 64-bit op that slipped in — all raise here, at no chip
time.  Nothing runs, so nothing here says anything about results or
speed; what the compiler emits can be counted, though, and the field
ops' kernel counts are held under ceilings.

Only one process at a time may load the TPU library, so the topology is
described inside a fixture (never at import or collection time), all
cases live in this one file, and every compile happens in the test's
own process with the persistent compile cache off (an entry written for
a described chip cannot be read back without one).

The fast cases are what every flush is made of (seconds each), the join
between a flush's two programs among them.  The three flush programs
chip_smoke.py runs are minutes each: ``slow``.
"""

import random
import time

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from hbbft_tpu.crypto.tpu import curve as dcurve
from hbbft_tpu.crypto.tpu import fq
from hbbft_tpu.crypto.tpu import pairing as dpairing

ROWS = 2048  # one production chunk (TpuBackend.CHUNK)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cache_off():
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _spec(tree, sharding):
    """Shapes (with the chip's sharding) of a pytree of arrays/shapes."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


def _g1(n):
    limbs = jax.ShapeDtypeStruct((n, fq.NL), jnp.int32)
    return (limbs, limbs, limbs, jax.ShapeDtypeStruct((n,), jnp.int32))


def _g2(n):
    limbs = jax.ShapeDtypeStruct((n, 2, fq.NL), jnp.int32)
    return (limbs, limbs, limbs, jax.ShapeDtypeStruct((n,), jnp.int32))


def _keccak_case():
    from hbbft_tpu.ops.jaxops import keccak_pallas as kp

    def run(state):
        return kp._keccak_f_cols(state, interpret=False, blk=kp._BLK)

    # two grid steps at the block width the code uses
    return run, (jax.ShapeDtypeStruct((50, 2 * kp._BLK), jnp.uint32),)


def _mont_mul_case():
    limbs = jax.ShapeDtypeStruct((ROWS, fq.NL), jnp.int32)
    return fq.mont_mul, (limbs, limbs)


def _g1_add_case():
    return (
        lambda p, q: dcurve.add_safe(dcurve.G1_OPS, p, q),
        (_g1(ROWS), _g1(ROWS)),
    )


def _g2_add_case():
    return (
        lambda p, q: dcurve.add_safe(dcurve.G2_OPS, p, q),
        (_g2(ROWS), _g2(ROWS)),
    )


@pytest.mark.parametrize(
    "case",
    [_keccak_case, _mont_mul_case, _g1_add_case, _g2_add_case],
    ids=["keccak_pallas_blk", "fq_mont_mul_2048", "g1_add_2048", "g2_add_2048"],
)
def test_kernel_compiles_for_v5e(case, one_chip, cache_off):
    fn, shapes = case()
    compiled = jax.jit(fn).lower(*_spec(shapes, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.generated_code_size_in_bytes > 0
    if case is _keccak_case:
        # the Pallas kernel itself, not an XLA fallback
        assert "tpu_custom_call" in compiled.as_text()


def _entry_fusions(hlo_text):
    """``fusion`` instructions of the ENTRY computation of an optimized
    HLO module: the kernels one call launches, loops aside."""
    entry = hlo_text.split("\nENTRY ", 1)[1].split("\n}", 1)[0]
    return sum(" fusion(" in line for line in entry.splitlines())


# Fusions in one call, on a described v5e (cache off).  The device time of
# a step follows its kernel count at 0.15-0.21 us a kernel, not its
# arithmetic, so these counts say how far the limb normalisation's fusion
# reaches; no span or counter sees it at run time.  Counted 2026-10-17:
#   before (a carry round padding its shifted carry back, the fold's
#   excess read out per row and broadcast back): add 10, sub 23,
#   mont_mul 25, Fq12 sqr 344;
#   now (a carry round is slices of one padded array, a fold stage one
#   constant matmul): add 3, sub 6, mont_mul 8, Fq12 sqr 114.
_FUSION_CEILINGS = [
    ("fq_add", fq.add, 2, (36, fq.NL), 4),
    ("fq_sub", fq.sub, 2, (36, fq.NL), 8),
    ("fq_mont_mul", fq.mont_mul, 2, (36, fq.NL), 10),
    ("fq12_sqr", dpairing.sqr, 1, (6, 2, fq.NL), 140),
]


@pytest.mark.parametrize(
    "name,fn,arity,shape,ceiling",
    _FUSION_CEILINGS,
    ids=[c[0] for c in _FUSION_CEILINGS],
)
def test_field_op_launches_few_kernels_on_v5e(
    name, fn, arity, shape, ceiling, one_chip, cache_off
):
    arg = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    compiled = jax.jit(fn).lower(*[arg] * arity).compile()
    n = _entry_fusions(compiled.as_text())
    assert 0 < n <= ceiling, f"{name}: {n} fusions, ceiling {ceiling}"


def test_join_program_compiles_for_v5e_into_the_pair_programs_arguments(
    one_chip, cache_off
):
    """``_join_kernel(3)`` on what one chunk's check hands it (the scan's
    three left-hand rows and its generator-leg row, the two right-hand
    points put on the device meanwhile): the module a trace shows as
    ``jit_hbbft_join_3``, whose result is ``_pair_kernel(3)``'s two
    arguments.  A stubbed scan's ``1 + legs`` rows join to the same."""
    from hbbft_tpu.crypto.tpu import backend as B

    join = B._join_kernel(3)
    shapes = ([_g1(3)], [_g2(1)], [_g2(2)])
    lowered = join.lower(*_spec(shapes, one_chip))
    assert lowered.as_text().split("\n", 1)[0].startswith("module @jit_hbbft_join_3 ")
    assert lowered.compile().memory_analysis().generated_code_size_in_bytes > 0
    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), (_g1(3), _g2(3)))
    for gen_rows in (1, 3):
        got = jax.eval_shape(join, [_g1(3)], [_g2(gen_rows)], [_g2(2)])
        assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), got) == want
    # several chunks, padded to the bucket
    got = jax.eval_shape(B._join_kernel(16), [_g1(3)] * 4, [_g2(1)] * 4, [_g2(2)] * 4)
    assert {int(a.shape[0]) for a in jax.tree_util.tree_leaves(got)} == {16}


def _sig_share_reqs(n):
    """``n`` signature shares on one document (8 signatures reused, as
    chip_smoke.py builds a chunk)."""
    from hbbft_tpu.crypto.backend import VerifyRequest
    from hbbft_tpu.crypto.bls.suite import BLSSuite
    from hbbft_tpu.crypto.keys import SecretKeySet

    suite = BLSSuite()
    sks = SecretKeySet.random(2, random.Random(7), suite)
    pks = sks.public_keys()
    msg = b"aot flush document"
    sig = [sks.secret_key_share(k).sign(msg) for k in range(8)]
    return suite, [
        VerifyRequest.sig_share(pks.public_key_share(i % 8), msg, sig[i % 8])
        for i in range(n)
    ]


def _dec_share_reqs(n):
    """``n`` decryption shares of distinct signers on one ciphertext with a
    proposal of 4000 bytes: the flush of the benchmark's ``hb16.decrypt``
    (15) and ``wan104.decrypt`` (103)."""
    from hbbft_tpu.crypto.backend import VerifyRequest
    from hbbft_tpu.crypto.bls.suite import BLSSuite
    from hbbft_tpu.crypto.keys import SecretKeySet

    suite = BLSSuite()
    rng = random.Random(7)
    sks = SecretKeySet.random(2, rng, suite)
    pks = sks.public_keys()
    ct = pks.public_key().encrypt(rng.randbytes(4000), rng)
    return suite, [
        VerifyRequest.dec_share(
            pks.public_key_share(i), ct, sks.secret_key_share(i).decryption_share(ct)
        )
        for i in range(n)
    ]


@pytest.mark.slow
@pytest.mark.parametrize(
    "make,n_requests,shape",
    [
        (_sig_share_reqs, 16, (16, 16, 2)),
        (_sig_share_reqs, ROWS, (ROWS, ROWS, 2)),
        (_dec_share_reqs, 15, (32, 16, 2)),
        (_dec_share_reqs, 103, (256, 16, 2)),
    ],
    ids=["sig_share_16", f"sig_share_{ROWS}", "dec_share_15", "dec_share_103"],
)
def test_flush_programs_compile_for_v5e(make, n_requests, shape, one_chip, cache_off):
    """The programs of chip_smoke.py and of the benchmark's cells:
    ``_scan_kernel(16,16,2)``, ``_scan_kernel(2048,2048,2)``, the decrypt
    bursts' ``_scan_kernel(32,16,2)`` (N = 16) and ``_scan_kernel(256,16,2)``
    (N = 104), each lowered from ``_scan_prep``'s
    arguments (the right-hand points are not among them), and (once)
    ``_pair_kernel(3)`` on what ``_join_kernel(3)`` makes of the scan's
    result.  Every scan program's optimized HLO holds a ``conditional`` (the
    G2 stage, run only where a flush brings a G2 row).  Prints seconds and
    ``memory_analysis()`` for each (run with ``-s``);
    with ``JAX_ENABLE_X64=0`` it compiles what the worker compiles."""
    from hbbft_tpu.crypto.tpu import backend as B

    suite, reqs = make(n_requests)
    (n1, n2, nl), args, rhs = B.TpuBackend(suite)._scan_prep(reqs)
    assert (n1, n2, nl) == shape and len(args) == 9 and len(rhs) <= nl
    programs = [
        (f"hbbft_scan_{n1}_{n2}_{nl}", B._scan_kernel(n1, n2, nl), args)
    ]
    if shape == (16, 16, 2):
        _, lhs, gen_leg = jax.eval_shape(B._scan_kernel(n1, n2, nl), *args)
        n_pairs = int(lhs[3].shape[0])
        assert n_pairs == 3 and int(gen_leg[3].shape[0]) == 1
        pairs = jax.eval_shape(B._join_kernel(n_pairs), [lhs], [gen_leg], [_g2(nl)])
        programs.append(
            (f"hbbft_pair_{n_pairs}", B._pair_kernel(n_pairs), pairs)
        )
    for name, kernel, shapes in programs:
        t0 = time.perf_counter()
        lowered = kernel.lower(*_spec(shapes, one_chip))
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        # the name a device trace's module carries (chipbench reads it)
        assert lowered.as_text().split("\n", 1)[0].startswith(
            f"module @jit_{name} "
        )
        mem = compiled.memory_analysis()
        if name.startswith("hbbft_scan_"):
            # the G2 stage's ``cond`` is still a branch on the chip (XLA did
            # not turn it into a select, which would run both sides)
            assert " conditional(" in compiled.as_text()
        print(
            f"\nAOT v5e {name} x64={jax.config.jax_enable_x64}: "
            f"lower {t1 - t0:.0f} s, whole compile {t2 - t0:.0f} s, "
            f"code {mem.generated_code_size_in_bytes} B, "
            f"temp {mem.temp_size_in_bytes} B, "
            f"args {mem.argument_size_in_bytes} B"
        )
        assert mem.generated_code_size_in_bytes > 0
