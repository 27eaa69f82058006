"""Shape buckets of a device flush: which compiled programs a flush, or a
group that bisection makes of it, runs in.

Pure Python, no jax, so a process that must not touch the chip (a
deployment's warm-up planner, the benchmark's parent process) can ask which
programs a mix of requests lands in.  ``crypto/tpu/backend.py`` takes its
buckets from here (:func:`scan_shape` and :func:`pairs_bucket`) and from
nowhere else.
"""

from __future__ import annotations

from typing import Any, Sequence, Tuple

from hbbft_tpu.crypto.backend import SIG_SHARE


def bucket(n: int, floor: int = 16) -> int:
    """Round up to a power of two (with a floor) to bound recompiles.

    The floor matters for bisection: all small sub-batches pad to the
    same shape and reuse one compiled kernel instead of compiling a
    fresh kernel per subset size.  So the floor counts REQUESTS, not
    rows: it is the rows that 16 requests bring (a 16-node network's
    burst), which for G1 depends on their phase (:func:`g1_floor`); a
    G2 row comes one a request at most, floor 16; legs have a floor of
    their own (:func:`scan_shape`)."""
    b = floor
    while b < n:
        b *= 2
    return b


def g1_floor(reqs: Sequence[Any]) -> int:
    """The G1 bucket's floor for a flush or one of bisection's groups: 16
    requests times the G1 rows a request of its phase's widest kind
    brings.  The decrypt phase (``dec_share``: the share and the negated
    key share, two rows; ``ciphertext``: ``-U``, one row, the check of the
    ciphertext whose shares follow) has the floor 32, the coin's
    ``sig_share`` (one row) 16.  Read off the group's own requests (their
    ``kind``) and nothing else, so a decrypt burst of up to 16 requests and
    every group bisection makes of it, down to a lone share or the lone
    check, run in ``scan(32, 16, legs)``, and a coin round and its groups
    in ``scan(16, 16, legs)`` as they always did."""
    return 16 if all(r.kind == SIG_SHARE for r in reqs) else 32


def scan_shape(
    reqs: Sequence[Any], g1_rows: int, g2_rows: int, legs: int
) -> Tuple[int, int, int]:
    """``(n1, n2, nl)`` of the scan program ``jit_hbbft_scan_<n1>_<n2>_<nl>``
    that a group of ``reqs`` runs in, whose legs hold that many real rows.

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
