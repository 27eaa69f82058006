"""The flush programs' size: every loop holds ONE copy of its step body.

Set-up of a ``tpu`` worker is mostly tracing, lowering and loading the scan
and pair programs, and it grows with their size (PERF.md section 5), so
their size is held here, on the CPU, with no compile: the jaxpr equations
of ``_scan_kernel`` and ``_pair_kernel`` counted recursively, a loop's or
a branch's body once.

Counts before the loops held one body each (2026-10-15): ``scan(16,16,2)``
277,716, ``scan(2048,2048,2)`` 405,382 (the tree sums were unrolled, one
level per doubling of the lanes), ``pair(3)`` 318,887, with 64-bit types
off.  After (2026-10-16, under this suite's settings, 64-bit types on):
114,066, 98,682 and 108,200; the wide scan is the smaller, as its check
chains' sums fold without a level loop (``curve.tree_sum``).  Tracing the
three takes 40-70 s.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.extend import core as jcore

from hbbft_tpu.crypto.tpu import backend as B
from hbbft_tpu.crypto.tpu import curve as dc
from hbbft_tpu.crypto.tpu import fq

BEFORE = {
    "scan_16_16_2": 277_716,
    "scan_2048_2048_2": 405_382,
    "pair_3": 318_887,
}


def _sub_jaxprs(value):
    if isinstance(value, jcore.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jcore.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def _eqns(jaxpr) -> int:
    return sum(
        1 + sum(_eqns(sub) for v in e.params.values() for sub in _sub_jaxprs(v))
        for e in jaxpr.eqns
    )


def _spec(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _points(n, elem):
    return (_spec(n, *elem),) * 3 + (_spec(n),)


def _size(name: str) -> int:
    kind, *dims = name.split("_")
    if kind == "pair":
        (n,) = map(int, dims)
        fn = B._pair_kernel(n)
        args = (_points(n, (fq.NL,)), _points(n, (2, fq.NL)))
    else:
        n1, n2, legs = map(int, dims)
        fn = B._scan_kernel(n1, n2, legs)
        args = (
            _points(n1, (fq.NL,)), _spec(n1, dc.ENDO_NBITS), _spec(n1),
            _spec(legs, n1), _points(n2, (2, fq.NL)),
            _spec(n2, dc.G2_SCAN_NBITS), _spec(n2, dc.G2_SCAN_NBITS),
            _spec(n2), (_spec(fq.NL),) * 3 + (_spec(),),
        )
    (call,) = jax.make_jaxpr(fn)(*args).jaxpr.eqns  # the jit's one call
    return _eqns(call.params["jaxpr"].jaxpr)


@pytest.fixture(scope="module")
def sizes():
    return {name: _size(name) for name in BEFORE}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_a_program_is_under_half_its_unrolled_size(sizes, name):
    assert sizes[name] < BEFORE[name] // 2, sizes


def test_the_scan_programs_size_does_not_grow_with_its_lanes(sizes):
    small, large = sizes["scan_16_16_2"], sizes["scan_2048_2048_2"]
    assert large <= small * 1.1, sizes
