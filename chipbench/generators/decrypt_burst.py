"""Generator ``decrypt_burst``: ``decrypt_flushes``' traffic, for a program
that keeps a decrypt burst and every group of it in ONE scan program.

The flushes are ``decrypt_flushes``' own, byte for byte (``make_keys`` and
``make_flush`` are that module's).  What this module adds is a refusal, made
when the traffic file that names it is loaded (``Cell.__init__`` imports
it), before a worker or a helper is started: the run of a program that
cannot keep the burst in one program ends at once, with the reason on one
line and the exit code of a workload that cannot be loaded, in place of a
run that is cut.

Why refuse.  A configuration that uses this generator names two programs
(``hb16``: ``scan(32,16,2)`` and ``pair(3)``).  A program whose G1 bucket
floor counts rows (every commit up to 50005b9) answers the same traffic
correctly, but in three: the probe's bisection hands it groups of 8 shares or
fewer, 16 G1 rows, ``scan(16,16,2)``.  That third program makes a run 447 s
long with a warm compile cache (``setup_s`` 422.99 s) and 1195 s with an
empty one, which is how a cell's first run starts (``setup_s`` 1167.84 s),
against the 360 s a run may take and 343-348 s with two (chip runs of PR 30,
PERF.md section 6).  The driver's check of PR 30 cut that run of the parent;
a ``chipbench/run.py`` that is cut leaves its worker on the chip (PERF.md
section 7), and PR 30 was refused as ``process_left_running``.  Such a
program cannot run this deployment inside the time a run is given, and is
told so here.

What is asked of the program: ``hbbft_tpu.crypto.flush_shapes.scan_shape``
(no jax: this process never imports it), the function ``TpuBackend._scan_prep``
takes every scan program's shape from.  With it every group a burst of up to
15 shares can be cut into, with the ciphertext's check or without, down to a
lone share and the lone check, has to land in the scan program of the whole
burst.  The check is of that answer and not of the rule behind it: a later
program that keeps the burst in one program by another rule passes.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Callable, List, Tuple

from chipbench.generators.decrypt_flushes import make_flush, make_keys

__all__ = ["make_flush", "make_keys", "scan_programs"]

#: (G1 rows, G2 rows) a request brings to the scan program: a decryption
#: share and the negated key share; a ciphertext check's ``-U`` and ``W``.
ROWS = {"dec_share": (2, 0), "ciphertext": (1, 1)}
#: Legs of a group on one ciphertext: ``H(U,V)``, and ``W`` once it holds a share.
LEGS = {"dec_share": 2, "ciphertext": 1}
BURST_SHARES = 15


def scan_programs(scan_shape: Callable[..., Tuple[int, int, int]]):
    """``(what, (n1, n2, legs))`` for the burst and every group of it, the
    burst first, by the program's ``scan_shape(reqs, g1_rows, g2_rows, legs)``."""
    groups: List[List[str]] = [["dec_share"] * BURST_SHARES]
    groups.append(["ciphertext"] + groups[0])
    groups += [["dec_share"] * n for n in range(1, BURST_SHARES)]
    groups += [["ciphertext"] + ["dec_share"] * n for n in range(BURST_SHARES)]
    out = []
    for kinds in groups:
        reqs = [SimpleNamespace(kind=k) for k in kinds]
        what = f"{kinds.count('dec_share')} dec_share"
        if "ciphertext" in kinds:
            what += " with the ciphertext check"
        shape = scan_shape(
            reqs,
            sum(ROWS[k][0] for k in kinds),
            sum(ROWS[k][1] for k in kinds),
            max(LEGS[k] for k in kinds),
        )
        out.append((what, tuple(shape)))
    return out


def hold_to_one_scan_program(scan_shape: Callable[..., Tuple[int, int, int]]) -> None:
    (burst, program), *rest = scan_programs(scan_shape)
    for what, got in rest:
        if got != program:
            raise ValueError(
                f"the program under test prepares {what} for scan{got} and "
                f"{burst} for scan{program}: two scan programs, each traced "
                "and compiled in set-up; this traffic is for a program that "
                "keeps a burst and its bisection groups in one"
            )


def _the_programs_scan_shape():
    try:
        from hbbft_tpu.crypto.flush_shapes import scan_shape
    except ImportError as e:
        raise ValueError(
            "the program under test has no hbbft_tpu.crypto.flush_shapes."
            f"scan_shape ({e}): its scan buckets count rows, so a decrypt "
            "burst and its bisection groups need scan(32,16,2) AND "
            "scan(16,16,2); this traffic is for a program that keeps them in one"
        ) from None
    return scan_shape


hold_to_one_scan_program(_the_programs_scan_shape())
