"""A signature share ``(pk_bytes 97, document, sig_bytes 193)``: valid iff
``e(pk, H(document)) == e(g1, sig)``.  In the batch equation one 128-bit
scalar multiplication in G1 (the key share) and one in G2 (the share), and
one G2 subgroup check of the wire-sourced share; it pairs with the generator
and with its document's hash."""

from chipbench.harness import work

SCAN_FQ_MULS = work.G1_SCALAR_MUL + work.G2_SCALAR_MUL + work.G2_SUBGROUP_CHECK


def verify(reference, pk_bytes, doc, sig_bytes):
    return reference.sig_share(pk_bytes, doc, sig_bytes)


def pairs(pk_bytes, doc, sig_bytes):
    return (work.GENERATOR_PAIR, work.document_pair(doc))


def sent(pk_bytes, doc, sig_bytes):
    return len(pk_bytes) + len(sig_bytes), (doc,)


def wire_of(request):
    pk, doc, share = request.payload
    return (pk.to_bytes(), doc, share.to_bytes())
