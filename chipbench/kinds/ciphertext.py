"""A ciphertext check ``(U 97, V, W 193)``: valid iff
``e(g1, W) == e(U, H(U, V))``.  In the batch equation one 128-bit scalar
multiplication in G1 (``U``) and one in G2 (``W``) and a subgroup check in
each group, both points being wire-sourced; it pairs with the generator and
with its own hash."""

from chipbench.harness import work

SCAN_FQ_MULS = (
    work.G1_SCALAR_MUL + work.G2_SCALAR_MUL
    + work.G1_SUBGROUP_CHECK + work.G2_SUBGROUP_CHECK
)


def verify(reference, u_bytes, v, w_bytes):
    return reference.ciphertext(u_bytes, v, w_bytes)


def pairs(u_bytes, v, w_bytes):
    return (work.GENERATOR_PAIR, work.hashed_ciphertext_pair(u_bytes, v))


def sent(u_bytes, v, w_bytes):
    return len(u_bytes) + len(v) + len(w_bytes), ()


def wire_of(request):
    (ct,) = request.payload
    return (ct.u.to_bytes(), ct.v, ct.w.to_bytes())
