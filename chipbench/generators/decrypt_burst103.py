"""Generator ``decrypt_burst103``: ``decrypt_flushes``' traffic at N = 104, for
a program that keeps a burst of 103 decryption shares and every group
bisection makes of it in ONE scan program.

The flushes are ``decrypt_flushes``' own, byte for byte (``make_keys`` and
``make_flush`` are that module's).  What this module adds is the refusal
``decrypt_burst`` makes for a burst of 15, made for one of 103: when the
traffic file that names it is loaded (``Cell.__init__`` imports it), before a
worker or a helper is started, the run of a program that cannot keep the
burst in one program ends at once, with the reason on one line and the exit
code of a workload that cannot be loaded, in place of a run that is cut.

Why refuse.  A configuration that uses this generator names two programs
(``wan104``: ``scan(256,16,2)`` and ``pair(3)``).  A program whose groups
take the bucket of their OWN rows (every commit up to ffe9a29: its floor
keeps a burst in one program only up to 16 requests) answers the same
traffic correctly, but in four: 103 shares are 206 G1 rows,
``scan(256,16,2)``, and the halves the probe's bisection makes of them 104,
52, 26, ... rows, ``scan(128,16,2)``, ``scan(64,16,2)``, ``scan(32,16,2)``.
Each is traced and read back in set-up (100-121 s warm, 7-10 minutes of
compile cold: chip runs of PR 25-34, PERF.md), 600 s and more against the
360 s a run may take; the driver cuts such a run, a ``chipbench/run.py``
that is cut leaves its worker on the chip (PERF.md section 7), and PR 30 was
refused for exactly that.

What is asked of the program: ``hbbft_tpu.crypto.flush_shapes`` (no jax: this
process never imports it), where ``TpuBackend._scan_prep`` takes every scan
program's shape from: ``scan_shape(reqs, g1_rows, g2_rows, legs)`` for the
burst's own and ``group_shape(chunk, own)`` for the shape a group of it is
prepared in.  With them the burst, with the ciphertext's check and without,
and every group a halving bisection cuts from it, down to a lone share and
the lone check, have to land in one scan program.  The check is of that
answer and not of the rule behind it: a later program that keeps the burst
in one program by another rule behind the same two functions passes.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Callable, List, Optional, Sequence, Tuple

# the rows and legs a request brings to the scan program, by kind
from chipbench.generators.decrypt_burst import LEGS, ROWS
from chipbench.generators.decrypt_flushes import make_flush, make_keys

__all__ = ["make_flush", "make_keys", "scan_programs"]

Shape = Tuple[int, int, int]
BURST_SHARES = 103


def _what(kinds: Sequence[str]) -> str:
    what = f"{list(kinds).count('dec_share')} dec_share"
    return what + (" with the ciphertext check" if "ciphertext" in kinds else "")


def _halves(kinds: Sequence[str]) -> List[Sequence[str]]:
    """Every group a halving bisection can cut from a flush of ``kinds``,
    as ``TpuBackend._bisect`` halves: ``g[: len(g) // 2]`` and the rest."""
    if len(kinds) < 2:
        return []
    low, high = kinds[: len(kinds) // 2], kinds[len(kinds) // 2 :]
    return [low, high] + _halves(low) + _halves(high)


def scan_programs(
    scan_shape: Callable[..., Shape],
    group_shape: Optional[Callable[[Shape, Shape], Shape]] = None,
) -> List[Tuple[str, Shape]]:
    """``(what, (n1, n2, legs))``, once a distinct pair: the burst first, the
    burst behind its ciphertext check, then every group bisection cuts from
    either.  A flush's shape is the program's ``scan_shape`` on its own
    rows; a group's is ``group_shape(its flush's, scan_shape on its own)``
    and, for a program without that function (``None``), its own."""

    def own(kinds: Sequence[str]) -> Shape:
        return tuple(scan_shape(
            [SimpleNamespace(kind=k) for k in kinds],
            sum(ROWS[k][0] for k in kinds),
            sum(ROWS[k][1] for k in kinds),
            max(LEGS[k] for k in kinds),
        ))

    burst = ["dec_share"] * BURST_SHARES
    flushes = [burst, ["ciphertext"] + burst]
    out = [(_what(flush), own(flush)) for flush in flushes]
    for flush, (_, chunk) in zip(flushes, list(out)):
        for group in _halves(flush):
            shape = own(group) if group_shape is None else tuple(group_shape(chunk, own(group)))
            if (_what(group), shape) not in out:
                out.append((_what(group), shape))
    return out


def hold_to_one_scan_program(
    scan_shape: Callable[..., Shape],
    group_shape: Optional[Callable[[Shape, Shape], Shape]] = None,
) -> None:
    (burst, program), *rest = scan_programs(scan_shape, group_shape)
    others = sorted({got for _, got in rest if got != program})
    if others:
        what = next(w for w, got in rest if got == others[0])
        raise ValueError(
            f"the program under test prepares {burst} for scan{program} and the "
            f"groups bisection makes of it for {len(others)} more scan programs "
            f"({what} for scan{others[0]}), each traced and compiled in set-up; "
            "this traffic is for a program that keeps a burst and its bisection "
            "groups in one"
        )


def _the_programs_shapes():
    try:
        from hbbft_tpu.crypto.flush_shapes import group_shape, scan_shape
    except ImportError as e:
        raise ValueError(
            "the program under test has no hbbft_tpu.crypto.flush_shapes."
            f"group_shape ({e}): a group that bisection makes of a flush takes "
            f"the bucket of its own rows, so a burst of {BURST_SHARES} dec_share "
            "and its groups need scan(256,16,2), scan(128,16,2), scan(64,16,2) "
            "AND scan(32,16,2); this traffic is for a program that keeps them in one"
        ) from None
    return scan_shape, group_shape


hold_to_one_scan_program(*_the_programs_shapes())
