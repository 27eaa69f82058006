"""Batched BLS12-381 base-field arithmetic for TPU (JAX, int32 limbs).

This is the device half of the crypto plane (SURVEY.md §7): the hot
pairing-check algebra — replacing upstream ``threshold_crypto``'s pure-Rust
``pairing`` backend (SURVEY.md §2 #14) — expressed as vectorized int32
limb arithmetic that XLA can tile over a TPU's VPU/MXU.

Representation
--------------
An Fq element is ``(..., NL)`` int32 limbs, little-endian, radix
``2^B = 2^11``, ``NL = 36`` limbs (396 bits), ALL LIMBS NONNEGATIVE in
``[0, 4096]``; values are in Montgomery form (``x·R mod P``, R = 2^396)
and only canonicalized on host at the boundary.

Design rules (each independently forced by TPU constraints):

* **11-bit limbs**: products (< 2^24) and 36-term convolution sums
  (< 2^29.2) fit int32 lanes — TPUs have no 64-bit integer path.
* **Nonnegative limbs**: with limbs >= 0, a bound on the VALUE bounds
  every limb's contribution, so dropping provably-zero high limbs after
  a carry is sound.  (Signed/borrow representations admit "ghost" ±1
  top limbs compensated by lower limbs of the other sign — those made
  bounded-round carry propagation unsound; this was learned the hard
  way.)  Subtraction therefore goes through a limb-wise complement:
  ``a - b ≡ a + (CVEC - b) + DELTA  (mod P)`` where CVEC has every limb
  4095 (so the limb subtraction never borrows) and DELTA ≡ -CVEC (mod P).
* **R = 2^396 ≫ P (15 spare bits)**: Montgomery SOS reduction lands far
  below 2^396, so redundant limbs never need an exact (sequential,
  rippling) normalization on device.
* **Value folding**: ops that grow values re-fold bits above 2^385
  through ``2^385 mod P`` (≈ 0.7P, shrinking ≈5 bits per stage); the
  number of stages is chosen statically per op from its worst-case bound.
* **A carry round is slices of one array**: the input is padded once and
  every round reads windows of it, never a ``pad`` or ``concatenate`` of
  an intermediate.  XLA on the TPU materialises a shifted intermediate,
  at two kernels a round, and a step's device time follows its kernel
  count (a few hundred ns each), not its arithmetic.  Likewise a fold
  stage is a constant matmul, not a per-row scalar broadcast back.

Invariant (every public op requires and guarantees):
    limbs in [0, 4096],  value in [0, 2^385.9).
``mont_mul`` tolerates values < 2^386 and returns < 2^382.5.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from hbbft_tpu.crypto.bls.fields import P

B = 11
NL = 36
MASK = (1 << B) - 1
R_BITS = B * NL  # 396
R = 1 << R_BITS
R2 = (R * R) % P
NPRIME = (-pow(P, -1, R)) % R  # P * NPRIME ≡ -1 (mod R)
FOLD_AT = B * (NL - 1)  # 385: the value-fold boundary (limb 35's weight)

I32 = jnp.int32


def to_limbs_np(x: int, n: int = NL) -> np.ndarray:
    """Host: nonnegative int -> strict little-endian limbs."""
    assert 0 <= x < (1 << (B * n)), "value does not fit"
    out = np.empty(n, dtype=np.int32)
    for i in range(n):
        out[i] = x & MASK
        x >>= B
    return out


def from_limbs_int(a) -> int:
    """Host: limbs -> Python int value."""
    arr = np.asarray(a).astype(object).reshape(-1)
    acc = 0
    for i, v in enumerate(arr):
        acc += int(v) << (B * i)
    return acc


# Precomputed constants (strict limbs).
P_LIMBS = to_limbs_np(P)
NPRIME_LIMBS = to_limbs_np(NPRIME)
ONE_MONT = to_limbs_np(R % P)  # Montgomery form of 1
FOLD385 = to_limbs_np((1 << FOLD_AT) % P, n=NL - 1)
ZERO = np.zeros(NL, dtype=np.int32)
# Subtraction complement: CVEC has every limb 2^15-1 (>= any loose limb,
# and >= the raw 6-term coefficient sums the Fq12 layer feeds in);
# DELTA ≡ -value(CVEC) (mod P); both strict-limb constants.
CVEC = np.full(NL, 32767, dtype=np.int32)
_CVEC_VAL = from_limbs_int(CVEC)
DELTA = to_limbs_np((-_CVEC_VAL) % P)


def to_mont_np(x: int) -> np.ndarray:
    """Host: canonical int mod P -> Montgomery-form strict limbs."""
    return to_limbs_np((x % P) * R % P)


_LIMB_WEIGHTS = (1 << np.arange(B, dtype=np.int32))


def ints_to_limbs_batch(vals) -> np.ndarray:
    """Host: list of nonnegative ints (< 2^396) -> (N, NL) int32 limbs.

    Vectorized via bytes + unpackbits — the per-value Python limb loop
    (to_limbs_np) costs ~36 iterations each and dominates host->device
    conversion at firehose batch sizes.
    """
    if not vals:
        return np.zeros((0, NL), dtype=np.int32)
    limit = 1 << (B * NL)
    for v in vals:
        assert 0 <= v < limit, "value does not fit"
    data = np.frombuffer(
        b"".join(v.to_bytes(50, "little") for v in vals), dtype=np.uint8
    ).reshape(len(vals), 50)
    bits = np.unpackbits(data, axis=1, bitorder="little")[:, : B * NL]
    return (
        bits.reshape(len(vals), NL, B).astype(np.int32) * _LIMB_WEIGHTS
    ).sum(axis=2, dtype=np.int32)


def to_mont_batch(vals) -> np.ndarray:
    """Host: canonical ints mod P -> (N, NL) Montgomery limbs."""
    return ints_to_limbs_batch([(v % P) * R % P for v in vals])


def from_mont_int(a) -> int:
    """Host: Montgomery limbs -> canonical int mod P."""
    return (from_limbs_int(a) * pow(R, -1, P)) % P


def _pad_limbs(x: jnp.ndarray, below: int, above: int) -> jnp.ndarray:
    """Zero limbs added below and above the limb axis."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(below, above)])


def _carry(x: jnp.ndarray, rounds: int, width: int | None = None) -> jnp.ndarray:
    """Redistribute nonneg limbs down to [0, 4096]; value preserved.

    The result has ``n + rounds + 1`` limbs for an input of ``n`` (or
    its lowest ``width``).  Carries travel one limb per round, so limb j
    of the result reads input limbs j - rounds .. j only: the input is
    padded ONCE with ``rounds`` zero guard limbs below and ``rounds + 1``
    above, and ``ws[k]`` is its window starting at guard offset k.  One
    round maps windows k, k + 1 to ``(ws[k + 1] & MASK) + (ws[k] >> B)``
    (each limb keeps its low bits and takes the carry of the limb below;
    below limb 0 a guard, whose carry is 0); after ``rounds`` rounds the
    one window left is the result.  The carry out of the top limb is read
    by no window, and the top limb never receives a carry (carries start
    in the input's ``n`` limbs and the ``rounds + 1`` zero limbs above
    absorb them), so value conservation is structural, and all quantities
    stay nonnegative (input limbs must be >= 0).  Every round is
    elementwise on slices of the one padded array: nothing shifted is
    materialised between rounds (module docstring).
    """
    if width is None:
        width = x.shape[-1] + rounds + 1
    x = _pad_limbs(x, rounds, rounds + 1)
    ws = [x[..., k : k + width] for k in range(rounds + 1)]
    for _ in range(rounds):
        ws = [(ws[k + 1] & MASK) + (ws[k] >> B) for k in range(len(ws) - 1)]
    return ws[0]


def _fold_mat() -> np.ndarray:
    """(NL + 2, NL - 1) constant of one fold stage: the identity on limbs
    0..34 and, for the excess limb 35 + k, ``FOLD385 · 2^(11k)``.  Row
    37's entries do not fit int32 and are kept modulo 2^32, as the
    products ``e · FOLD385`` they stand for wrap; that limb is 0 for
    every value < 2^407, so the product is exact in range."""
    M = np.zeros((NL + 2, NL - 1), dtype=np.int64)
    M[: NL - 1] = np.eye(NL - 1, dtype=np.int64)
    for k in range(3):
        M[NL - 1 + k] = FOLD385.astype(np.int64) << (B * k)
    return ((M + (1 << 31)) % (1 << 32) - (1 << 31)).astype(np.int32)


_FOLD_MAT = _fold_mat()


def _fold(x: jnp.ndarray, stages: int) -> jnp.ndarray:
    """Fold value bits above 2^385 back in via 2^385 mod P, ``stages``
    times, then truncate to NL limbs.  Requires nonneg limbs (post-carry)
    and value < 2^398; each stage shrinks the excess ~5 bits, and the
    final truncation is provably lossless for value < 2^396.

    A stage is one constant matmul (``_FOLD_MAT``: the low limbs plus
    the excess ``e = limbs 35..37`` times FOLD385, in the style of
    ``_conv_const``) and a 2-round carry: no per-row scalar is read out
    and broadcast back across the limbs.  The last stage's carry makes
    the NL limbs kept and no more."""
    for s in range(stages):
        k = min(x.shape[-1], NL + 2)
        folded = jnp.matmul(x[..., :k], jnp.asarray(_FOLD_MAT[:k]))
        x = _carry(folded, rounds=2, width=NL if s == stages - 1 else None)
    return x


def _conv(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Limb convolution: (..., NL) x (..., NL) -> (..., 2*NL-1).

    Skew-reshape formulation (round 5): the anti-diagonal sums
    ``out[k] = sum_{i+j=k} a_i b_j`` are computed by padding each outer
    row to width 2*NL and reflattening with stride 2*NL-1, which shifts
    row i right by exactly i (flat index i*2NL + j re-read as
    i*(2NL-1) + (i+j)); one axis sum then yields the convolution.  This
    replaces the round-1 scatter matmul ``(.., NL^2) @ (NL^2, 2NL-1)``
    — ~92k MACs per field mul, the dominant FLOP term of every pairing
    kernel — with the same 1,296 products plus a 36-row sum (~24x fewer
    lane ops), still a handful of XLA ops (no unrolled slice-updates,
    no gathers), so the big pairing graphs stay compilable.  Bounds are
    unchanged: identical integer sums, products < 2^24, 36-term
    anti-diagonal sums < 2^29.2.
    """
    outer = a[..., :, None] * b[..., None, :]
    batch = outer.shape[:-2]
    padded = jnp.pad(
        outer, [(0, 0)] * (outer.ndim - 2) + [(0, 0), (0, NL)]
    )
    flat = padded.reshape(*batch, NL * 2 * NL)
    skewed = flat[..., : NL * (2 * NL - 1)].reshape(*batch, NL, 2 * NL - 1)
    # dtype pinned: under x64 jnp.sum promotes int32 accumulation to
    # int64, which TPU lanes don't have; the 36-term sums are < 2^29.2
    # so int32 accumulation is exact.
    return jnp.sum(skewed, axis=-2, dtype=I32)


def _conv_mat(b_limbs: np.ndarray) -> np.ndarray:
    """(NL, 2*NL-1) Toeplitz matrix M[i, i:i+NL] = b for a CONSTANT
    operand: the convolution becomes one small (.., NL) @ (NL, 2NL-1)
    matmul (~2.6k MACs) instead of outer + skew-sum (~3.9k lane ops)."""
    M = np.zeros((NL, 2 * NL - 1), dtype=np.int32)
    for i in range(NL):
        M[i, i : i + NL] = b_limbs
    return M


_NPRIME_MAT = _conv_mat(NPRIME_LIMBS)
_P_MAT = _conv_mat(P_LIMBS)


def _conv_const(a: jnp.ndarray, mat: np.ndarray) -> jnp.ndarray:
    """Limb convolution with a constant operand (Toeplitz matmul)."""
    return jnp.matmul(a, jnp.asarray(mat))


_LANE0 = np.eye(1, NL, dtype=np.int32)[0]  # 1 at limb 0


def mont_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Montgomery product a·b·R^-1 (mod P), batched.

    SOS with redundant nonneg limbs: T = a·b; m ≡ T·N' (mod R);
    t = (T + m·P)/R.  The division is exact in value; the carried low
    part's value is exactly corr·R with corr in {0, 1, 2} (redundant m),
    read off limb 35.  Inputs: value < 2^386.  Output: value < 2^382.5.
    """
    t = _carry(_conv(a, b), rounds=3)
    m = _carry(_conv_const(t[..., :NL], _NPRIME_MAT), rounds=3, width=NL)
    mp = _conv_const(m, _P_MAT)  # 2*NL - 1 limbs, under t's 2*NL + 3
    full = _carry(t + _pad_limbs(mp, 0, t.shape[-1] - mp.shape[-1]), rounds=3)
    # value(full[:NL]) = corr·R exactly; limb 35 sits in
    # [2048·corr - 3, 2048·corr].  corr is added to limb NL as a lane of
    # a window (the window's lane 0 is limb 35), not as a per-row scalar.
    corr = ((full[..., NL - 1 : 2 * NL - 1] + 3) >> B) * jnp.asarray(_LANE0)
    return _carry(full[..., NL : 2 * NL] + corr, rounds=1, width=NL)


def mont_sqr(a: jnp.ndarray) -> jnp.ndarray:
    return mont_mul(a, a)


def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return _fold(_carry(a + b, rounds=2), stages=1)


def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a - b (mod P) via the borrow-free complement (module docstring).
    Accepts limbs up to 2^15-1 (raw coefficient sums), value < 2^389."""
    d = a + (jnp.asarray(CVEC) - b) + jnp.asarray(DELTA)
    return _fold(_carry(d, rounds=2), stages=3)


def neg(a: jnp.ndarray) -> jnp.ndarray:
    d = (jnp.asarray(CVEC) - a) + jnp.asarray(DELTA)
    return _fold(_carry(d, rounds=2), stages=3)


def small_mul(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """Multiply by a small positive constant (k <= 16)."""
    assert 0 < k <= 16
    return _fold(_carry(a * k, rounds=2), stages=1)


def normalize(a: jnp.ndarray) -> jnp.ndarray:
    """Re-settle into the invariant range; value unchanged mod P."""
    return _fold(_carry(a, rounds=2), stages=1)


def is_zero(a: jnp.ndarray) -> jnp.ndarray:
    """Value ≡ 0 (mod P)?  Batched, device-side (sequential scans; keep
    out of hot loops — flag-carrying point code avoids needing this).

    One Montgomery shrink pass maps a (value < 2^386) to a value
    ≡ a (mod P) in [0, 2.1P); that is ≡ 0 mod P iff it equals 0, P, or
    2P — test each exactly.
    """
    v = mont_mul(a, jnp.asarray(ONE_MONT))
    acc = _is_exact_zero(v)
    for k in (1, 2):
        acc = acc | _is_exact_zero(v - jnp.asarray(to_limbs_np(k * P)))
    return acc


def _is_exact_zero(x: jnp.ndarray) -> jnp.ndarray:
    """Exact value==0 test via sequential carry scan (NL tiny steps).
    Input limbs may be signed here (difference of nonneg vectors)."""

    def step(c, limb):
        s = limb + c
        return s >> B, s & MASK

    carry0 = jnp.zeros(x.shape[:-1], dtype=I32)
    xs = jnp.moveaxis(x, -1, 0)
    final_c, lows = jax.lax.scan(step, carry0, xs)
    return (final_c == 0) & jnp.all(lows == 0, axis=0)


def pow_fixed(a: jnp.ndarray, e: int) -> jnp.ndarray:
    """a^e (Montgomery), e a fixed Python int.

    Fixed 4-bit windows MSB-first: 14 table muls + per window 4 squarings
    and one table mul — ~490 sequential muls for e = P-2 vs ~762 for the
    bit-at-a-time scan this replaced.  The window values are static
    (derived from e at trace time) but the table gather stays inside the
    scan so the graph is one small scan body, not 380 unrolled ops.
    """
    if e == 0:
        return jnp.broadcast_to(jnp.asarray(ONE_MONT), a.shape)
    W = 4
    nwin = (e.bit_length() + W - 1) // W
    wins = [(e >> (W * (nwin - 1 - i))) & 15 for i in range(nwin)]
    pows = [jnp.broadcast_to(jnp.asarray(ONE_MONT), a.shape), a]
    for _ in range(2, 16):
        pows.append(mont_mul(pows[-1], a))
    table = jnp.stack(pows)  # (16, ..., NL)
    acc = table[wins[0]]  # static index

    def step(acc, w):
        acc = mont_sqr(mont_sqr(mont_sqr(mont_sqr(acc))))
        t = jax.lax.dynamic_index_in_dim(table, w, 0, keepdims=False)
        return jnp.where(w > 0, mont_mul(acc, t), acc), None

    acc, _ = jax.lax.scan(step, acc, jnp.asarray(wins[1:], dtype=I32))
    return acc


def inv(a: jnp.ndarray) -> jnp.ndarray:
    """Field inverse by Fermat: a^(P-2). ~570 muls — use sparingly."""
    return pow_fixed(a, P - 2)


def rand_elems(rng: np.random.Generator, shape=()) -> jnp.ndarray:
    """Host helper: random canonical Montgomery elements for tests."""
    flat = int(np.prod(shape)) if shape else 1
    outs = [
        to_mont_np(int.from_bytes(rng.bytes(48), "big") % P) for _ in range(flat)
    ]
    arr = np.stack(outs).reshape(*shape, NL) if shape else outs[0]
    return jnp.asarray(arr)
