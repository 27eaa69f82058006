"""Shape buckets of a device flush: which compiled programs a flush, or a
group that bisection makes of it, runs in.

Pure Python, no jax, so a process that must not touch the chip (a
deployment's warm-up planner, the benchmark's parent process) can ask which
programs a mix of requests lands in.  ``crypto/tpu/backend.py`` takes its
buckets from here (:func:`scan_shape`, :func:`group_shape` and
:func:`pairs_bucket`) and from nowhere else.

The rule in one paragraph.  :func:`scan_shape` decides ``(n1, n2, legs)``
once a chunk (a flush of up to ``TpuBackend.CHUNK`` requests), from the
chunk's own rows, and :func:`group_shape` hands that triple down to every
group bisection cuts from the chunk, so a flush of ANY size and all its
groups run in one scan program and one pair program.  The floors of
:func:`g1_floor` decide what is left to them: the program of a flush of up
to 16 requests, which is thereby the same for every such flush of a phase
(a 16-node network's bursts, a share that arrives alone, a lone check).
"""

from __future__ import annotations

from typing import Any, Sequence, Tuple

from hbbft_tpu.crypto.backend import SIG_SHARE


def bucket(n: int, floor: int = 16) -> int:
    """Round up to a power of two (with a floor) to bound recompiles.

    The floor matters for small flushes: all of them pad to the same
    shape and reuse one compiled kernel instead of compiling a fresh
    kernel per flush size (bisection's groups take their chunk's shape,
    :func:`group_shape`).  So the floor counts REQUESTS, not rows: it is
    the rows that 16 requests bring (a 16-node network's burst), which
    for G1 depends on their phase (:func:`g1_floor`); a G2 row comes one
    a request at most, floor 16; legs have a floor of their own
    (:func:`scan_shape`)."""
    b = floor
    while b < n:
        b *= 2
    return b


def g1_floor(reqs: Sequence[Any]) -> int:
    """The G1 bucket's floor for a flush: 16 requests times the G1 rows a
    request of its phase's widest kind brings.  The decrypt phase
    (``dec_share``: the share and the negated key share, two rows;
    ``ciphertext``: ``-U``, one row, the check of the ciphertext whose
    shares follow) has the floor 32, the coin's ``sig_share`` (one row) 16.
    Read off the flush's own requests (their ``kind``) and nothing else, so
    every decrypt flush of up to 16 requests, a lone share or the lone
    check among them, runs in ``scan(32, 16, legs)``, and a coin round in
    ``scan(16, 16, legs)`` as it always did.  A group that bisection makes
    of a flush does not come here: it takes its flush's shape
    (:func:`group_shape`)."""
    return 16 if all(r.kind == SIG_SHARE for r in reqs) else 32


def scan_shape(
    reqs: Sequence[Any], g1_rows: int, g2_rows: int, legs: int
) -> Tuple[int, int, int]:
    """``(n1, n2, nl)`` of the scan program ``jit_hbbft_scan_<n1>_<n2>_<nl>``
    that a chunk of ``reqs`` runs in, whose legs hold that many real rows:
    a group's OWN shape, which is its program's only where nothing above it
    hands one down (:func:`group_shape`).

    Legs become pairing-product pairs (a Miller loop each, even when
    identity-padded), so their floor is low: 2.  The other side of it is
    one scan program, a cold compile of minutes, per distinct legs
    bucket (2/4/8 under bisection of a flush on several documents),
    which benchmarks/warm_crypto_cache.py and the persistent cache cover
    for the CPU test tier."""
    return (
        bucket(max(g1_rows, 1), floor=g1_floor(reqs)),
        bucket(max(g2_rows, 1)),
        bucket(max(legs, 1), floor=2),
    )


def group_shape(
    chunk: Tuple[int, int, int], own: Tuple[int, int, int]
) -> Tuple[int, int, int]:
    """The shape a group that bisection cut from a chunk is prepared in:
    the ``chunk``'s, whatever the group's ``own`` (:func:`scan_shape` on
    its own rows) would be.

    A group is a subset of its chunk's requests, so its rows and legs fit
    (checked: a shape that does not hold the group is a bug in the
    caller).  A flush and everything bisection makes of it thus need ONE
    scan program and one pair program, whose compile (minutes each) or
    cache read (100-121 s) a validator pays once, in warm-up, and never
    in the middle of an epoch because one of f Byzantine senders sent a
    wrong share.  The price is padding: a group's check runs the chunk's
    lanes and legs (a scan's device time hardly grows with its rows, a
    padded leg is a whole Miller loop) and the host packs the chunk's
    rows for it."""
    if any(o > c for o, c in zip(own, chunk)):
        raise ValueError(f"a group of shape {own} was cut from a chunk of {chunk}")
    return tuple(chunk)


def pairs_bucket(n: int) -> int:
    """Pair-count bucket of ``jit_hbbft_pair_<pairs>``: exact for small
    counts, multiples of 8 above.

    Small flushes (one chunk: 1 + n_legs = 3/5/9 pairs) keep their exact
    size: every padded pair is a real 63-step Miller loop per execution.
    Multi-chunk combines pad to a multiple of 8 so the compile count
    stays bounded; padded pairs are identity pairs (factor 1 via the
    skip mask).
    """
    return n if n <= 9 else (n + 7) // 8 * 8
