"""A decryption share ``(pk_bytes 97, U 97, V, W 193, share_bytes 97)`` of
the ciphertext ``(U, V, W)``: valid iff ``e(share, H(U, V)) == e(pk, W)``.
In the batch equation two 128-bit scalar multiplications in G1 (the share,
the key share) and one G1 subgroup check of the wire-sourced share; it pairs
with its ciphertext's hash and with its ciphertext's ``W``, not with the
generator.  A request carries its whole ciphertext on the wire
(hbbft_tpu/wire.py), which is counted as sent with every request."""

from chipbench.harness import work

SCAN_FQ_MULS = 2 * work.G1_SCALAR_MUL + work.G1_SUBGROUP_CHECK


def verify(reference, pk_bytes, u_bytes, v, w_bytes, share_bytes):
    return reference.dec_share(pk_bytes, u_bytes, v, w_bytes, share_bytes)


def pairs(pk_bytes, u_bytes, v, w_bytes, share_bytes):
    return (
        work.hashed_ciphertext_pair(u_bytes, v),
        work.ciphertext_w_pair(w_bytes),
    )


def sent(pk_bytes, u_bytes, v, w_bytes, share_bytes):
    own = len(pk_bytes) + len(u_bytes) + len(v) + len(w_bytes) + len(share_bytes)
    return own, ()


def wire_of(request):
    pk, ct, share = request.payload
    return (pk.to_bytes(), ct.u.to_bytes(), ct.v, ct.w.to_bytes(), share.to_bytes())
