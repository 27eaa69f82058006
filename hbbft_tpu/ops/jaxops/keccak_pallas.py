"""Pallas TPU kernel for batched Keccak-f[1600] (SHA3-256 data plane).

Reference behavior: ``tiny-keccak`` SHA3-256 as used by the reference's
Merkle module (SURVEY.md §2 #4).  The jnp implementation
(:mod:`hbbft_tpu.ops.jaxops.keccak`) emits ~3k separate XLA ops per
permutation; this kernel runs the whole permutation fused in VMEM, one
grid step per batch tile, so a Merkle level over 10k shards is a single
`pallas_call` with no HBM round-trips between rounds.

Layout: the 25 x 64-bit state lives as 50 uint32 *rows* of shape
(50, batch) — row 2i is lane i's low half, row 2i+1 the high half — so
every elementwise op rides full 8x128 VPU tiles along the batch axis.

On CPU (tests) the kernel runs in interpret mode; on TPU it compiles
through Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from hbbft_tpu.ops.jaxops.keccak import RATE, _RHO, _ROUND_CONSTANTS

_BLK = 512  # batch tile (lanes axis); multiple of 128


def _rotl_pair(lo, hi, r: int):
    r %= 64
    if r == 0:
        return lo, hi
    if r == 32:
        return hi, lo
    if r < 32:
        return (
            (lo << r) | (hi >> (32 - r)),
            (hi << r) | (lo >> (32 - r)),
        )
    r -= 32
    return (
        (hi << r) | (lo >> (32 - r)),
        (lo << r) | (hi >> (32 - r)),
    )


def _keccak_kernel(state_ref, out_ref):
    """state_ref/out_ref: (50, BLK) uint32 in VMEM."""
    lo = [state_ref[2 * i, :] for i in range(25)]
    hi = [state_ref[2 * i + 1, :] for i in range(25)]
    for rc in _ROUND_CONSTANTS:
        c_lo = [lo[x] ^ lo[x + 5] ^ lo[x + 10] ^ lo[x + 15] ^ lo[x + 20] for x in range(5)]
        c_hi = [hi[x] ^ hi[x + 5] ^ hi[x + 10] ^ hi[x + 15] ^ hi[x + 20] for x in range(5)]
        for x in range(5):
            r_lo, r_hi = _rotl_pair(c_lo[(x + 1) % 5], c_hi[(x + 1) % 5], 1)
            d_lo = c_lo[(x + 4) % 5] ^ r_lo
            d_hi = c_hi[(x + 4) % 5] ^ r_hi
            for y in range(5):
                lo[x + 5 * y] = lo[x + 5 * y] ^ d_lo
                hi[x + 5 * y] = hi[x + 5 * y] ^ d_hi
        b_lo = [None] * 25
        b_hi = [None] * 25
        for x in range(5):
            for y in range(5):
                nx, ny = y, (2 * x + 3 * y) % 5
                r_lo, r_hi = _rotl_pair(lo[x + 5 * y], hi[x + 5 * y], _RHO[x][y])
                b_lo[nx + 5 * ny] = r_lo
                b_hi[nx + 5 * ny] = r_hi
        for y in range(5):
            row_lo = [b_lo[x + 5 * y] for x in range(5)]
            row_hi = [b_hi[x + 5 * y] for x in range(5)]
            for x in range(5):
                lo[x + 5 * y] = row_lo[x] ^ (~row_lo[(x + 1) % 5] & row_lo[(x + 2) % 5])
                hi[x + 5 * y] = row_hi[x] ^ (~row_hi[(x + 1) % 5] & row_hi[(x + 2) % 5])
        lo[0] = lo[0] ^ jnp.uint32(rc & 0xFFFFFFFF)
        hi[0] = hi[0] ^ jnp.uint32(rc >> 32)
    for i in range(25):
        out_ref[2 * i, :] = lo[i]
        out_ref[2 * i + 1, :] = hi[i]


def _keccak_f_cols(state: jnp.ndarray, interpret: bool, blk: int) -> jnp.ndarray:
    n = state.shape[1]
    pad = (-n) % blk
    if pad:
        state = jnp.pad(state, ((0, 0), (0, pad)))
    padded = state.shape[1]
    out = pl.pallas_call(
        _keccak_kernel,
        out_shape=jax.ShapeDtypeStruct((50, padded), jnp.uint32),
        grid=(padded // blk,),
        # a typed zero: under x64 (the test setting) a Python 0 becomes an
        # i64 block index, which Mosaic refuses to compile for the TPU
        in_specs=[pl.BlockSpec((50, blk), lambda i: (np.int32(0), i))],
        out_specs=pl.BlockSpec((50, blk), lambda i: (np.int32(0), i)),
        interpret=interpret,
    )(state)
    return out[:, :n]


_keccak_f_cols_jit = jax.jit(
    functools.partial(_keccak_f_cols, interpret=False, blk=_BLK)
)


def keccak_f_cols(state: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """(50, batch) uint32 column-major states -> permuted states.

    ``batch`` is padded to a multiple of the tile internally.  Interpret
    mode (CPU tests) runs the interpreter eagerly — jitting the
    interpreter's expansion produces an XLA graph whose LLVM compile
    time is unbounded in practice.
    """
    if interpret:
        # One grid step over the whole (small, test-sized) batch.
        return _keccak_f_cols(state, interpret=True, blk=max(state.shape[1], 1))
    return _keccak_f_cols_jit(state)


def keccak_f(state: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """Drop-in for jaxops.keccak.keccak_f: (..., 25, 2) uint32 states."""
    lead = state.shape[:-2]
    flat = state.reshape((-1, 50)).T  # (50, batch)
    out = keccak_f_cols(flat, interpret=interpret)
    return out.T.reshape(lead + (25, 2))


def sha3_256_block(padded: np.ndarray, interpret: bool = False) -> np.ndarray:
    """(batch, RATE) padded blocks -> (batch, 32) digests (Pallas path)."""
    batch = padded.shape[0]
    as_u32 = padded.reshape(batch, RATE // 4, 4).astype(np.uint32)
    vals = as_u32[..., 0] | (as_u32[..., 1] << 8) | (as_u32[..., 2] << 16) | (
        as_u32[..., 3] << 24
    )
    state = np.zeros((50, batch), dtype=np.uint32)
    for i in range(RATE // 8):
        state[2 * i] = vals[:, 2 * i]
        state[2 * i + 1] = vals[:, 2 * i + 1]
    out = np.asarray(keccak_f_cols(jnp.asarray(state), interpret=interpret))
    dig = np.zeros((batch, 32), dtype=np.uint8)
    for i in range(4):
        for half in range(2):
            v = out[2 * i + half]
            for b in range(4):
                dig[:, 8 * i + 4 * half + b] = (v >> (8 * b)) & 0xFF
    return dig


def sha3_256_multi(padded: np.ndarray, interpret: bool = False) -> np.ndarray:
    """(batch, n_blocks*RATE) padded messages -> (batch, 32) digests.

    Multi-block sponge: XOR-absorb each block into the (50, batch)
    column state and run the fused Pallas permutation per block.
    """
    from hbbft_tpu.ops.jaxops.keccak import block_words, digest_from_state

    padded = np.asarray(padded, dtype=np.uint8)
    batch, total = padded.shape
    nb = total // RATE
    state = jnp.zeros((50, batch), dtype=jnp.uint32)
    for b in range(nb):
        words = block_words(padded[:, b * RATE : (b + 1) * RATE])  # (batch, 17, 2)
        cols = np.zeros((50, batch), dtype=np.uint32)
        cols[0 : 2 * (RATE // 8) : 2] = words[:, :, 0].T
        cols[1 : 2 * (RATE // 8) : 2] = words[:, :, 1].T
        state = keccak_f_cols(state ^ jnp.asarray(cols), interpret=interpret)
    out = np.asarray(state)  # (50, batch)
    lanes = np.stack([out[0::2].T, out[1::2].T], axis=-1)  # (batch, 25, 2)
    return digest_from_state(lanes)


def sha3_256_batch(msgs: np.ndarray, interpret: bool = False) -> np.ndarray:
    """Batched SHA3-256 via the Pallas permutation.

    (batch, m) uint8 -> (batch, 32) uint8; bit-identical to
    jaxops.keccak.sha3_256_batch and hashlib.  Single-block messages
    take the one-permutation path; longer ones absorb block by block.
    """
    from hbbft_tpu.ops.jaxops.keccak import pad_block, pad_multi

    msgs = np.asarray(msgs, dtype=np.uint8)
    if msgs.shape[1] <= RATE - 1:
        return sha3_256_block(pad_block(msgs), interpret=interpret)
    return sha3_256_multi(pad_multi(msgs), interpret=interpret)
