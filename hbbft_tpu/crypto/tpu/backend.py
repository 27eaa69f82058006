"""TpuBackend: the north-star CryptoBackend (BASELINE.json:5).

Same random-linear-combination batch verification as
:class:`hbbft_tpu.crypto.backend.BatchedBackend` — identical Fiat-Shamir
coefficients, identical leg algebra, bisection fallback on failure — but
the heavy group algebra runs on the accelerator, in a scan program and a
pair program with a join of a few rows between them:

* every share/key/ciphertext point is scaled by its 128-bit RLC
  coefficient with a batched LSB-first double-and-add scan that
  SIMULTANEOUSLY computes the endomorphism-check chain (``[x^2]P`` on
  G1, ``[|x|]Q`` on G2 — both fit the same 128-bit width) off the same
  doubling chain — the subgroup (r-torsion) check for wire-sourced
  points runs on device as the standard phi/psi endomorphism tests
  (``bls.curve.g1_in_subgroup`` notes), batched, instead of as
  per-request Python scalar-mults on the host,
* per-leg sums are masked tree reductions,
* the 1 + L pairing-product legs run through the batched Miller loop and
  one shared final exponentiation.

Kernel shapes are bucketed to powers of two so recompilation is bounded;
compiled kernels are cached per (n_g1, n_g2, n_legs) bucket.  The bucket is
decided once a chunk, by the chunk's own rows, and every group bisection
cuts from the chunk is prepared in it (``crypto/flush_shapes.py``), so a
flush of any size and its fault isolation run in one scan program and one
pair program.

Multi-chip: with ``shard=True`` and more than one visible device, the
batch axis is laid out over a data-parallel ``jax.sharding.Mesh`` — the
scalar-mul scans run fully parallel per shard and XLA inserts the
collectives for the tree reductions (SURVEY.md §2 parallelism note:
batching over the share dimension IS this framework's parallelism
axis).  It has run on virtual CPU devices only; no chip run has used it.

Replaces the per-share CPU pairing checks of upstream
``threshold_crypto`` (``src/lib.rs`` verify paths; SURVEY.md §2 #14).
"""

from __future__ import annotations

import threading
from collections import Counter
from functools import lru_cache
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hbbft_tpu.crypto.backend import (
    DEC_SHARE,
    SIG_SHARE,
    CryptoBackend,
    EagerBackend,
    VerifyRequest,
    _batch_coefficients,
    request_well_formed,
)
from hbbft_tpu.crypto.bls import curve as ocurve
from hbbft_tpu.crypto.bls.suite import BLSSuite
from hbbft_tpu.crypto.flush_shapes import group_shape as _group_shape
from hbbft_tpu.crypto.flush_shapes import pairs_bucket as _pairs_bucket
from hbbft_tpu.crypto.flush_shapes import scan_shape as _scan_shape
from hbbft_tpu.crypto.tpu import curve as dcurve
from hbbft_tpu.crypto.tpu import pairing as dpairing
from hbbft_tpu.utils import canonical_bytes
from hbbft_tpu.utils.metrics import Metrics

NBITS = 128  # RLC coefficient width

# The points at infinity as oracle Jacobian tuples: what pads a bucket.
_IDENT1 = (1, 1, 0)
_IDENT2 = ((1, 0), (1, 0), (0, 0))


def _any_g2_row(g2_inf, g2_chk):
    """The scan program's predicate for its G2 stage: some G2 row is not
    the point at infinity, or is checked.  False exactly where every row
    is padding (:meth:`TpuBackend._pack`: ``_IDENT2``, coefficient 0,
    ``check`` 0); works on numpy arrays as on traced ones."""
    return ((g2_inf == 0) | (g2_chk != 0)).any()


def _g2_stage(g2_pts, g2_bits_s, g2_bits_q):
    """The SCAN stage's G2 half: (``sub2``, the r-torsion verdict of every
    row; ``gen_leg``, the sum of the scaled rows, which the generator pairs
    with)."""
    with jax.named_scope("scan_g2"):
        scaled2, chain2 = dcurve.scalar_mul_rlc_g2(g2_pts, g2_bits_s, g2_bits_q)
    with jax.named_scope("subgroup"):
        sub2 = dcurve.endo_subgroup_eq(dcurve.G2_OPS, g2_pts, chain2)
    with jax.named_scope("leg_sums"):
        gen_leg = dcurve.tree_sum(dcurve.G2_OPS, scaled2)
    return sub2, gen_leg


def _g2_stage_skipped(g2_pts, g2_bits_s, g2_bits_q):
    """What :func:`_g2_stage` gives where every row is the point at
    infinity and unchecked, without computing it: a multiple of the point
    at infinity is the point at infinity, so ``gen_leg`` is the identity
    (flag 1: the pair program skips ``e(g1, O)`` = 1), and no row can fail
    a check nobody asked for, so ``sub2`` is all true."""
    n_g2 = g2_pts[3].shape[0]
    return jnp.ones((n_g2,), dtype=bool), dcurve.identity(dcurve.G2_OPS)


@lru_cache(maxsize=32)
def _scan_kernel(n_g1: int, n_g2: int, n_legs: int):
    """Compiled SCAN stage for one shape bucket (per-row work).

    Inputs (all device arrays):
      g1 pts (n_g1 batched G1 Jacobian+flag), g1 bits (n_g1, ENDO_NBITS
      = 128; the RLC coefficient), g1 subgroup-check mask (n_g1,), g1
      leg one-hot (n_legs, n_g1); g2 pts / bits / mask (n_g2 …) — the
      generator leg; the G1 generator.
    Returns (sub_ok, lhs, gen_leg): the aggregate subgroup verdict for
    every masked wire-sourced point (batched r-torsion on device), the
    (1 + n_legs) left-hand G1 points of the pairing pairs this chunk
    contributes (the generator, then each leg's sum) and, as one row, the
    G2 sum the generator pairs with.  The G2 points the leg sums pair
    with are not arguments: nothing here computes on them, so the host
    hashes them while this program runs (:meth:`TpuBackend._rhs_prep`) and
    :func:`_join_kernel` puts them behind ``gen_leg``.  The pairing
    itself is the separate :func:`_pair_kernel` stage so several chunks'
    pairs can share ONE batched Miller loop + final exponentiation.

    The G2 stage (:func:`_g2_stage`: the 65-step scan, the r-torsion
    verdicts, the generator leg's sum) sits under ONE ``jax.lax.cond`` on a
    predicate the program computes from its own arguments: some G2 row is
    "not the point at infinity, or checked".  Where none is (every row is
    padding, as in every flush of ``dec_share`` alone, which brings no G2
    row) the other branch, :func:`_g2_stage_skipped`, returns what the
    stage returns for such rows without computing it.  The predicate is
    BOTH halves: a real G2 row that is the point at infinity (a share a
    Byzantine signer sends) carries ``check`` = 1 and takes the full stage,
    so nobody narrows it to the identity flag alone; and it is read on the
    device from the rows themselves, so nobody moves the choice to the
    host (no flag, no per-kind test, no second program).

    The jitted function is named ``hbbft_scan_<n_g1>_<n_g2>_<n_legs>``, so
    a device trace's module is ``jit_hbbft_scan_...`` whatever a refactor
    renumbers, and its stages sit under the ``jax.named_scope``s
    ``scan_g1``, ``scan_g2``, ``subgroup`` and ``leg_sums`` (metadata of
    the ops: the compiled code is the same with and without them).
    """

    def run(
        g1_pts, g1_bits, g1_chk, seg,
        g2_pts, g2_bits_s, g2_bits_q, g2_chk, gen_pt,
    ):
        # The scans (dcurve "static-endo flush scans" notes): G1 is
        # one LSB-first shared-doubling scan with the [x^2]P check-chain
        # adds unrolled at x^2's 17 static set bits; G2 splits each RLC
        # coefficient as c = q·|x| + s against the psi endomorphism —
        # a 65-step two-scalar scan, run only where a G2 row is real
        # (_scan_kernel's docstring).  Soundness: the psi(Q) = [x]Q
        # identity the decomposition relies on IS the subgroup check
        # verified in this same kernel (fail-closed; see dcurve notes).
        # Equivalence + soundness pinned in tests/test_bls.py and
        # tests/test_tpu_crypto.py.
        with jax.named_scope("scan_g1"):
            scaled1, chain1 = dcurve.scalar_mul_rlc_g1(g1_pts, g1_bits)
        sub2, gen_leg = jax.lax.cond(
            _any_g2_row(g2_pts[3], g2_chk),
            _g2_stage, _g2_stage_skipped,
            g2_pts, g2_bits_s, g2_bits_q,
        )
        with jax.named_scope("subgroup"):
            sub1 = dcurve.endo_subgroup_eq(dcurve.G1_OPS, g1_pts, chain1)
            sub_ok = (
                jnp.all(sub1 | (g1_chk == 0)) & jnp.all(sub2 | (g2_chk == 0))
            )
        with jax.named_scope("leg_sums"):
            leg_sums = []
            for l in range(n_legs):
                masked = dcurve.select(
                    seg[l], scaled1,
                    dcurve.identity(dcurve.G1_OPS, (n_g1,)), dcurve.G1_OPS,
                )
                leg_sums.append(dcurve.tree_sum(dcurve.G1_OPS, masked))
        # Pair list: (gen, gen_leg) + (leg_sum_l, rhs_l), the rhs_l joined
        # on later.
        lhs = tuple(
            jnp.stack([gen_pt[c]] + [p[c] for p in leg_sums]) for c in range(4)
        )
        return sub_ok, lhs, tuple(jnp.stack([gen_leg[c]]) for c in range(4))

    run.__name__ = run.__qualname__ = f"hbbft_scan_{n_g1}_{n_g2}_{n_legs}"
    return jax.jit(run)


@lru_cache(maxsize=32)
def _pair_kernel(n_pairs: int):
    """Compiled PAIR stage: batched Miller loop over ``n_pairs`` pairing
    pairs + ONE shared final exponentiation -> product == 1.  The jitted
    function is named ``hbbft_pair_<n_pairs>`` (a trace's module
    ``jit_hbbft_pair_...``); its two stages are the ``jax.named_scope``s
    ``miller_loop`` and ``final_exp`` of ``pairing_product_is_one``."""

    def run(lhs, rhs):
        return dpairing.pairing_product_is_one(lhs, rhs)

    run.__name__ = run.__qualname__ = f"hbbft_pair_{n_pairs}"
    return jax.jit(run)


@lru_cache(maxsize=32)
def _join_kernel(n_pairs: int):
    """Compiled JOIN: one or more chunks' scan outputs and right-hand
    points as the PAIR stage's two arguments of ``n_pairs`` rows.  A
    chunk's pairs are (generator, its ``gen_leg``) and (leg sum l, right-
    hand point l); chunks follow one another and identity pairs pad to the
    bucket.  Of a chunk's ``gen_leg`` the FIRST row is taken: a stubbed
    scan may return more.  Data movement only, all four coordinates in one
    launch; named ``hbbft_join_<n_pairs>`` (a trace's module
    ``jit_hbbft_join_...``: neither a scan nor a pair module)."""

    def run(lhs_parts, gen_legs, rhs_parts):
        lhs = [list(coords) for coords in zip(*lhs_parts)]
        rhs = [[] for _ in range(4)]
        for gen_leg, pts in zip(gen_legs, rhs_parts):
            for c in range(4):
                rhs[c] += [gen_leg[c][:1], pts[c]]
        pad = n_pairs - sum(int(p[3].shape[0]) for p in lhs_parts)
        if pad:
            for side, ops in ((lhs, dcurve.G1_OPS), (rhs, dcurve.G2_OPS)):
                for c, x in enumerate(dcurve.identity(ops, (pad,))):
                    side[c].append(x)
        return (
            tuple(jnp.concatenate(xs) for xs in lhs),
            tuple(jnp.concatenate(xs) for xs in rhs),
        )

    run.__name__ = run.__qualname__ = f"hbbft_join_{n_pairs}"
    return jax.jit(run)


#: PAIR-stage compiles started ahead of their first use, by pair count.
_EARLY_PAIR_COMPILES: Dict[int, threading.Thread] = {}


def _compile_pair_kernel_early(n_pairs: int) -> None:
    """Cold start: compile the PAIR stage on a thread while the caller
    compiles its SCAN stage.

    Each stage is minutes of XLA compile (measured for a v5e: 8-10 min
    each, CHANGES.md PR 25) and a flush runs them one after the other,
    so a cold flush used to wait for the sum; XLA compiles outside the
    GIL, so it now waits for the longer one.  Identity pairs compile the
    same ``(n_pairs,)``-shaped program the flush will call and their
    product is 1.  Once per process and pair count; a warm persistent
    cache makes it a deserialization.  :meth:`TpuBackend._check_parts`
    joins the thread before it calls the kernel, so one program is never
    compiled twice at once; if the compile fails here the flush's own
    call raises the same error.
    """
    if n_pairs in _EARLY_PAIR_COMPILES:
        return

    def work() -> None:
        lhs = dcurve.identity(dcurve.G1_OPS, (n_pairs,))
        rhs = dcurve.identity(dcurve.G2_OPS, (n_pairs,))
        _pair_kernel(n_pairs)(lhs, rhs)

    thread = threading.Thread(
        target=work, name=f"pair-compile-{n_pairs}", daemon=True
    )
    _EARLY_PAIR_COMPILES[n_pairs] = thread
    thread.start()


def _shard_mesh(max_devices: int = 16):
    """Data-parallel mesh over the largest power-of-two device prefix.

    Capped at the kernel's minimum batch bucket (floor 16 in ``flush_shapes.bucket``)
    so the batch axis is always divisible by the mesh — a 32-way mesh
    over a 16-row bucket would make ``device_put`` raise on every small
    flush.
    """
    from jax.sharding import Mesh

    devs = jax.devices()
    n = 1
    while n * 2 <= min(len(devs), max_devices):
        n *= 2
    if n == 1:
        return None
    return Mesh(np.array(devs[:n]).reshape(n), axis_names=("dp",))


class _Group(list):
    """The requests of one of bisection's groups, with the scan shape of
    the chunk they were cut from: what :meth:`TpuBackend._scan_prep` pads
    the group to (``flush_shapes.group_shape``).  A list that carries it,
    because ``_scan_prep`` takes the requests and nothing else."""

    def __init__(self, reqs, chunk_shape: Tuple[int, int, int]) -> None:
        super().__init__(reqs)
        self.chunk_shape = chunk_shape


class TpuBackend(CryptoBackend):
    """RLC batch verification with the group algebra on the accelerator.

    ``shard=True`` lays the batch axis over all visible devices
    data-parallel; default is single-device.

    ``metrics`` takes the backend's spans (:meth:`Metrics.span`: a timer
    each and, under an open profiler session, an event on its clock) and
    counters, each counter at its span's boundary so that the ``stats`` op
    reads what a trace reads.  One aggregate check is one device verdict:
    ``crypto.tpu.check`` (args ``rows``, ``depth``: 0 the flush's own,
    +1 per bisection level) around, in this order, its
    ``crypto.tpu.scan_dispatch``, ``crypto.tpu.rhs_prep`` (``legs``,
    ``hashed``), ``crypto.tpu.pair_dispatch`` (``pairs``: the join program,
    then the pair program) and ``crypto.tpu.verdict_sync`` (the host blocked
    on the device).  The scan program reads none of the G2 points its leg
    sums pair with, so it is dispatched before they exist: ``rhs_prep``
    hashes each distinct leg that brings no point (one
    ``crypto.tpu.hash_to_g2`` (``bytes``) a document or ciphertext, however
    many requests share it; a ciphertext's ``W`` comes ready) and puts the
    points on the device while the scan runs.  The
    ``crypto.tpu.scan_prep`` (``rows``, ``n1``, ``n2``, ``legs``, ``handed``:
    1 where the shape is the chunk's, handed down to a group whose own
    bucket is smaller, else 0; ``g2``: 0 where the group brings no G2 row,
    so the scan program skips its G2 stage, else 1; inside it
    ``crypto.tpu.coefficients``, ``crypto.tpu.build_legs``,
    ``crypto.tpu.pack``: everything the scan program reads) before the
    dispatches is the check's own: the flush's, or that of a bisection
    level's first group.  One
    between ``pair_dispatch`` and ``verdict_sync`` is the NEXT group's of
    the level, prepared while the device runs this check (its ``rows``
    says whose; a level's last check holds none).  Beside the checks
    ``crypto.tpu.well_formed`` (``requests``) and one ``crypto.tpu.leaf``
    per request convicted by its own one-row check: a group of one whose
    check fails is answered False on the device's word, as one that
    passes is answered True (the coefficient is a unit mod r, so the
    one-row check is the verification equation itself; no oracle call).
    Counters: ``crypto.tpu.checks``, ``crypto.tpu.checks_failed``,
    ``crypto.tpu.rows`` (requests summed over checks),
    ``crypto.tpu.g1_rows`` and ``crypto.tpu.g2_rows`` (real rows of every
    ``scan_prep``), ``crypto.tpu.rows_padded`` (bucket rows less real
    rows, G1 and G2 summed), ``crypto.tpu.groups_handed_shape`` (the
    ``scan_prep``s with ``handed`` 1), ``crypto.tpu.g2_stage_skipped`` (those
    with ``g2`` 0), ``crypto.tpu.hash_to_g2_calls``,
    ``crypto.tpu.rhs_hashed`` (legs hashed in an ``rhs_prep``, so after
    their group's scan was dispatched), ``crypto.tpu.leaves``,
    ``crypto.tpu.prepared_ahead`` (``scan_prep``s that ran between a
    check's ``pair_dispatch`` and its ``verdict_sync``),
    ``crypto.tpu.requests.<kind>`` (well-formed requests that entered a
    flush, by kind; once a flush, not again in its groups).
    A flush of several chunks dispatches every chunk's scan, then makes
    every chunk's ``rhs_prep``, before any verdict, so there ``scan_prep``,
    ``scan_dispatch`` and ``rhs_prep`` lie beside the checks, not inside
    them: a check is then the combined pair stage and its sync, and after a
    failure one more per chunk.
    """

    def __init__(
        self,
        suite: BLSSuite | None = None,
        shard: bool = False,
        metrics: Metrics | None = None,
    ) -> None:
        self.suite = suite or BLSSuite()
        self.metrics = metrics if metrics is not None else Metrics()
        # The pure-Python oracle on the same suite: no flush calls it (a
        # verdict is the device's); the stubbed-kernel harnesses of tests
        # and chipbench/tests answer through it.
        self._eager = EagerBackend(self.suite)
        self._mesh = _shard_mesh() if shard else None

    # -- leg construction (host, cheap): mirrors backend._rlc_pairs ----

    def _build_legs(self, reqs: Sequence[VerifyRequest], coeffs: Sequence[int]):
        """Returns (g2_entries, g1_entries, rhs).

        g2_entries: list of (scalar, oracle G2 jac, check) summed against
        the G1 generator.  g1_entries: (scalar, oracle G1 jac, leg_id,
        check).  rhs[leg_id]: the G2 point each G1 leg pairs with, once a
        distinct leg: an oracle G2 jac where the request brings it (a
        ciphertext's ``W``), else the ``bytes`` whose hash to G2 it is (a
        document, a ciphertext's hash input).  Nothing is hashed here:
        :meth:`_rhs_points` does that once the scan is dispatched.
        ``check`` = 1 marks wire-sourced points that need the device-side
        r-torsion check (shares, ciphertext points); locally-derived
        points (public-key shares, hash-to-curve outputs) are exempt.
        """
        g2_entries: List[Tuple[int, Any, int]] = []
        g1_entries: List[Tuple[int, Any, int, int]] = []
        rhs: List[Any] = []
        leg_of: Dict[bytes, int] = {}

        def leg(key: bytes, point: Any) -> int:
            if key not in leg_of:
                leg_of[key] = len(rhs)
                rhs.append(point)
            return leg_of[key]

        for r, c in zip(reqs, coeffs):
            if r.kind == SIG_SHARE:
                pk, msg, share = r.payload
                g2_entries.append((c, share.g2.jac, 1))
                l = leg(canonical_bytes(b"m", msg), bytes(msg))
                g1_entries.append((c, (-pk.g1).jac, l, 0))
            elif r.kind == DEC_SHARE:
                pk, ct, share = r.payload
                l = leg(canonical_bytes(b"c", ct.hash_input()), ct.hash_input())
                g1_entries.append((c, share.g1.jac, l, 1))
                lw = leg(canonical_bytes(b"w", ct.w.to_bytes()), ct.w.jac)
                g1_entries.append((c, (-pk.g1).jac, lw, 0))
            else:
                (ct,) = r.payload
                g2_entries.append((c, ct.w.jac, 1))
                l = leg(canonical_bytes(b"c", ct.hash_input()), ct.hash_input())
                # -U is in the subgroup iff U is.
                g1_entries.append((c, (-ct.u).jac, l, 1))
        return g2_entries, g1_entries, rhs

    def _rhs_points(self, rhs: Sequence[Any]) -> List[Any]:
        """:meth:`_build_legs`'s ``rhs`` with every deferred leg hashed:
        oracle G2 jacs, one a leg."""
        points = []
        for point in rhs:
            if isinstance(point, bytes):
                self.metrics.count("crypto.tpu.hash_to_g2_calls")
                with self.metrics.span("crypto.tpu.hash_to_g2", bytes=len(point)):
                    point = self.suite.hash_to_g2(point).jac
            points.append(point)
        return points

    def _aggregate_ok(
        self,
        reqs: Sequence[VerifyRequest],
        depth: int = 0,
        prepared=None,
        ahead: Sequence[VerifyRequest] | None = None,
    ):
        """One aggregate check of a whole flush or of one of bisection's
        groups: scan, right-hand points, pair stage, verdict.  ``prepared``
        is this group's :meth:`_scan_prep` where the check before it made
        it; ``ahead`` the requests of the group checked next, prepared
        here once both programs are dispatched, while the device runs
        them.  Returns (verdict, the scan shape the check ran in, what was
        prepared ahead or None)."""
        with self.metrics.span("crypto.tpu.check", rows=len(reqs), depth=depth):
            if prepared is None:
                prepared = self._scan_prep(reqs)
            scan = self._scan_dispatch(prepared, alone=True)
            ok_dev = self._check_parts([self._rhs_prep(prepared, scan)])
            if ahead is not None:
                ahead = self._scan_prep(ahead)
                self.metrics.count("crypto.tpu.prepared_ahead")
            return self._verdict(ok_dev, len(reqs)), prepared[0], ahead

    def _verdict(self, ok_dev, rows: int) -> bool:
        """The host's wait for a dispatched pair stage's verdict; counts
        the check that this ends."""
        with self.metrics.span("crypto.tpu.verdict_sync"):
            ok = bool(ok_dev)
        self.metrics.count("crypto.tpu.checks")
        self.metrics.count("crypto.tpu.rows", rows)
        if not ok:
            self.metrics.count("crypto.tpu.checks_failed")
        return ok

    def _scan_dispatch(self, prepared, alone: bool = False):
        """Dispatch the SCAN kernel on one chunk's :meth:`_scan_prep`;
        returns (sub_ok, lhs, gen_leg) device values WITHOUT forcing a host
        sync, so independent chunks pipeline on device and the host goes
        on to :meth:`_rhs_prep` under the running program.  ``alone``: this
        chunk is the whole check, so its own pair count is the PAIR
        stage's (several chunks combine into a bucket only
        :meth:`verify_batch` knows)."""
        (n1, n2, nl), args, _ = prepared
        if alone and self._mesh is None:
            _compile_pair_kernel_early(_pairs_bucket(1 + nl))
        with self.metrics.span("crypto.tpu.scan_dispatch"):
            return _scan_kernel(n1, n2, nl)(*args)

    def _rhs_prep(self, prepared, scan):
        """The host's work under a dispatched scan: hash the chunk's
        deferred legs (:meth:`_rhs_points`) and put its ``nl`` right-hand
        G2 points on the device.  Returns the chunk's part for
        :meth:`_check_parts`: (sub_ok, lhs, gen_leg, rhs points)."""
        (_, _, nl), _, rhs = prepared
        hashed = sum(isinstance(point, bytes) for point in rhs)
        with self.metrics.span("crypto.tpu.rhs_prep", legs=len(rhs), hashed=hashed):
            points = self._rhs_points(rhs)
            self.metrics.count("crypto.tpu.rhs_hashed", hashed)
            pts = dcurve.g2_to_dev(points + [_IDENT2] * (nl - len(points)))
            if self._mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as PS

                repl = NamedSharding(self._mesh, PS())
                pts = tuple(jax.device_put(c, repl) for c in pts)
        return (*scan, pts)

    def _scan_prep(self, reqs: Sequence[VerifyRequest]):
        """Host prep for one chunk, everything the scan program reads:
        returns ((n1, n2, nl), kernel args, the legs' right-hand points as
        :meth:`_build_legs` gives them, for :meth:`_rhs_prep`).  Split from
        :meth:`_scan_dispatch` so that bisection prepares a group while
        the device checks the one before it.  The shape is the requests'
        own (``flush_shapes.scan_shape``) for a chunk, and for a
        :class:`_Group` the one its chunk hands down."""
        with self.metrics.span("crypto.tpu.scan_prep", rows=len(reqs)) as note:
            with self.metrics.span("crypto.tpu.coefficients"):
                coeffs = _batch_coefficients(self.suite, reqs)
            with self.metrics.span("crypto.tpu.build_legs"):
                g2e, g1e, rhs = self._build_legs(reqs, coeffs)
            own = _scan_shape(reqs, len(g1e), len(g2e), len(rhs))
            n1, n2, nl = shape = (
                _group_shape(reqs.chunk_shape, own) if isinstance(reqs, _Group) else own
            )
            handed = int(shape != own)
            # Every G2 entry is checked, so "no entry" is the scan
            # program's own predicate (_any_g2_row), read on the host.
            note(n1=n1, n2=n2, legs=nl, handed=handed, g2=int(bool(g2e)))
            self.metrics.count("crypto.tpu.groups_handed_shape", handed)
            self.metrics.count("crypto.tpu.g1_rows", len(g1e))
            self.metrics.count("crypto.tpu.g2_rows", len(g2e))
            self.metrics.count("crypto.tpu.g2_stage_skipped", int(not g2e))
            self.metrics.count(
                "crypto.tpu.rows_padded", n1 - len(g1e) + n2 - len(g2e)
            )
            with self.metrics.span("crypto.tpu.pack"):
                args = self._pack(g1e, g2e, n1, n2, nl)
        return (n1, n2, nl), args, rhs

    def _pack(self, g1e, g2e, n1: int, n2: int, nl: int):
        """The legs as the SCAN kernel's arguments: limbs, bit planes and
        masks, padded to the buckets and put on the device."""
        g1_pts = dcurve.g1_to_dev(
            [p for _, p, _, _ in g1e] + [_IDENT1] * (n1 - len(g1e))
        )
        g1_bits = dcurve.scalars_to_bits_lsb(
            [s for s, _, _, _ in g1e] + [0] * (n1 - len(g1e)), dcurve.ENDO_NBITS
        )
        g1_chk = np.zeros(n1, dtype=np.int32)
        seg = np.zeros((nl, n1), dtype=np.int32)
        for i, (_, _, l, chk) in enumerate(g1e):
            seg[l, i] = 1
            g1_chk[i] = chk
        g2_pts = dcurve.g2_to_dev(
            [p for _, p, _ in g2e] + [_IDENT2] * (n2 - len(g2e))
        )
        sq = [dcurve.decompose_g2_scalar(s) for s, _, _ in g2e]
        sq += [(0, 0)] * (n2 - len(g2e))
        g2_bits_s = dcurve.scalars_to_bits(
            [s for s, _ in sq], dcurve.G2_SCAN_NBITS
        )
        g2_bits_q = dcurve.scalars_to_bits(
            [q for _, q in sq], dcurve.G2_SCAN_NBITS
        )
        g2_chk = np.zeros(n2, dtype=np.int32)
        for i, (_, _, chk) in enumerate(g2e):
            g2_chk[i] = chk
        gen_pt = dcurve.g1_to_dev([ocurve.G1_GEN])
        gen_pt = tuple(x[0] for x in gen_pt)
        g1_chk = jnp.asarray(g1_chk)
        seg = jnp.asarray(seg)
        g2_chk = jnp.asarray(g2_chk)
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as PS

            batch = NamedSharding(self._mesh, PS("dp"))
            seg_sh = NamedSharding(self._mesh, PS(None, "dp"))
            repl = NamedSharding(self._mesh, PS())

            def put(x, sh):
                return jax.device_put(x, sh)

            g1_pts = tuple(put(c, batch) for c in g1_pts)
            g2_pts = tuple(put(c, batch) for c in g2_pts)
            g1_bits = put(g1_bits, batch)
            g2_bits_s = put(g2_bits_s, batch)
            g2_bits_q = put(g2_bits_q, batch)
            g1_chk = put(g1_chk, batch)
            g2_chk = put(g2_chk, batch)
            seg = put(seg, seg_sh)
            gen_pt = tuple(put(c, repl) for c in gen_pt)
        return (
            g1_pts, g1_bits, g1_chk, seg,
            g2_pts, g2_bits_s, g2_bits_q, g2_chk, gen_pt,
        )

    def _check_parts(self, parts) -> Any:
        """Combine one or more chunks' parts (:meth:`_rhs_prep`: sub_ok,
        lhs, gen_leg, rhs points) into a single device verdict: the JOIN
        program lines their pairs up, then a batched Miller loop over ALL
        pairs + ONE final exponentiation, AND of every chunk's subgroup bit.

        Soundness of the cross-chunk product check: each chunk is an RLC
        with Fiat-Shamir coefficients committed to that chunk's request
        contents, so the combined product == 1 test is one RLC over the
        union with blockwise-committed coefficients — an adversary must
        still grind the hash for an exact mod-r cancellation across the
        union (the same 2^-128-class bound as a single chunk; defects
        from duplicated content ADD with equal coefficients, they cannot
        cancel).  On any False the caller re-checks per chunk, so
        verdicts are identical to the per-chunk path.
        """
        n = sum(int(p[1][3].shape[0]) for p in parts)
        b = _pairs_bucket(n)
        with self.metrics.span("crypto.tpu.pair_dispatch", pairs=b):
            lhs, rhs = _join_kernel(b)(
                [p[1] for p in parts], [p[2] for p in parts], [p[3] for p in parts]
            )
            early = _EARLY_PAIR_COMPILES.get(b)
            if early is not None:
                early.join()
            ok = _pair_kernel(b)(lhs, rhs)
            for p in parts:
                ok = ok & p[0]
            return ok

    # -- public API ----------------------------------------------------

    # Rows of one scan program: a larger flush is split into chunks of
    # this many requests, each with its own Fiat-Shamir coefficients
    # (:meth:`_check_parts` has why their pairs may still share one pair
    # stage).  No chip run under this repo's benchmark has sized it; the
    # cell that will is ``sign10k.chunk`` (PERF.md section 7 item 2).
    CHUNK = 2048

    def verify_batch(self, reqs: Sequence[VerifyRequest]) -> List[bool]:
        reqs = list(reqs)
        if not reqs:
            return []
        out = [False] * len(reqs)
        # Host: structure + on-curve only; the r-torsion checks run
        # batched inside the flush kernel (subgroup=False here).
        with self.metrics.span("crypto.tpu.well_formed", requests=len(reqs)):
            idxs = [
                i
                for i, r in enumerate(reqs)
                if request_well_formed(self.suite, r, subgroup=False)
            ]
        for kind, n in Counter(reqs[i].kind for i in idxs).items():
            self.metrics.count("crypto.tpu.requests." + kind, n)
        chunks = [idxs[s : s + self.CHUNK] for s in range(0, len(idxs), self.CHUNK)]
        if not chunks:
            return out
        if len(chunks) == 1:
            ok, shape, _ = self._aggregate_ok([reqs[i] for i in idxs])
            if ok:
                for i in idxs:
                    out[i] = True
            else:
                self._bisect(reqs, idxs, out, shape)
            return out
        # Dispatch every chunk's SCAN kernel before syncing on anything:
        # jax dispatch is async, so the device pipelines the chunks and
        # the host pays one round-trip total instead of one per chunk.
        # Then hash every chunk's legs, under the running scans.
        dispatched = []
        for c in chunks:
            prepared = self._scan_prep([reqs[i] for i in c])
            dispatched.append((prepared, self._scan_dispatch(prepared)))
        scans = [self._rhs_prep(prepared, scan) for prepared, scan in dispatched]

        def check(parts, rows: int) -> bool:
            with self.metrics.span("crypto.tpu.check", rows=rows, depth=0):
                return self._verdict(self._check_parts(parts), rows)

        # Fast path: ALL chunks' pairs through one batched Miller loop +
        # one final exponentiation (fixed pairing cost paid once per
        # flush, not once per chunk — _check_parts notes).
        if check(scans, len(idxs)):
            for i in idxs:
                out[i] = True
            return out
        for c, (prepared, _), part in zip(chunks, dispatched, scans):
            if check([part], len(c)):
                for i in c:
                    out[i] = True
            else:
                self._bisect(reqs, c, out, prepared[0])
        return out

    def _bisect(
        self,
        all_reqs: List[VerifyRequest],
        idxs: List[int],
        out: List[bool],
        shape: Tuple[int, int, int],
    ) -> None:
        """Bisection fallback — the caller knows idxs' aggregate FAILED, in
        the scan program of ``shape``: the chunk's, in which every group
        below is prepared too (:class:`_Group`), so that isolating a wrong
        share compiles and loads nothing the flush did not.

        Level by level: a level is the halves of every group that failed
        on the level above, checked in order.  Both halves of a failed
        group are always checked, so while the device runs one group's
        programs the host prepares the next one's arguments; only a
        level's first group is prepared with the device idle, because the
        level is not known before the last verdict of the one above.  A
        group of one whose check fails is convicted by it (class
        docstring): every verdict is the device's."""
        failed = [idxs]
        depth = 1  # how many splits lie above the level's groups
        while failed:
            groups: List[List[int]] = []
            for g in failed:
                if len(g) == 1:
                    self.metrics.count("crypto.tpu.leaves")
                    with self.metrics.span("crypto.tpu.leaf"):
                        out[g[0]] = False
                else:
                    groups += [g[: len(g) // 2], g[len(g) // 2 :]]
            batches = [_Group((all_reqs[i] for i in g), shape) for g in groups]
            failed = []
            prepared = None
            for g, batch, ahead in zip(groups, batches, batches[1:] + [None]):
                ok, _, prepared = self._aggregate_ok(batch, depth, prepared, ahead)
                if ok:
                    for i in g:
                        out[i] = True
                else:
                    failed.append(g)
            depth += 1
