"""Generator ``decrypt_flushes``: one ciphertext's decrypt phase at one validator.

One flush holds, on a ciphertext that no other flush of the run uses, an
optional ciphertext check and the decryption shares of distinct signers on
it: what ``ThresholdDecrypt`` submits when a proposal's ciphertext comes out
of the subset and the others' shares arrive (upstream
``threshold_decrypt.rs``: one ``Ciphertext::verify``, then
``verify_decryption_share`` on each incoming share).

Parameters (a traffic file's ``params``, and its ``probe``):

* ``requests``: requests per flush, the ciphertext check included.
* ``ciphertext_checks``: 0 or 1 (default 0).  The check is request 0 and
  the shares follow it: the order in which a validator submits them.
* ``payload_bytes``: the length of ``V``, the encrypted proposal (default 32).
* ``wrong``: wrong requests per flush (0: a clean phase).
* ``wrong_kinds``: the kinds of the wrong requests (default ``["next_key"]``).
  ``other_w`` makes the ciphertext check wrong: it is sent with the ``W`` of
  another ciphertext (the same proposal encrypted again), a point of the
  r-torsion that fails only the pairing equation; the flush's shares still
  carry the flush's own, valid ciphertext.  The other kinds are dealt in
  turn to the wrong shares in rising order: ``next_key``, a valid share of
  the NEXT key index on the same ciphertext; ``identity``, the point at
  infinity.  As in ``sig_share_rounds``, a point off the curve or outside
  the r-torsion cannot be sent: the RPC server's decode refuses the frame.
* ``bisection_hit_nodes``: as ``sig_share_rounds`` pins it, over all the
  flush's requests: the wrong shares' positions are drawn from the seed
  among those that, with the ciphertext check's where that is wrong, make a
  halving bisection re-check exactly this many failing groups.

Keys come from the configuration: a degree-``threshold`` polynomial over the
scalar field drawn from the seed; signer ``i`` holds ``poly(i + 1)`` and the
proposals are encrypted to ``poly(0)``'s public key.  Everything is computed
with the benchmark's own plain arithmetic (chipbench/reference) and wrapped
into the program's request types; the wire bytes the plain reference
verifies are kept beside each request, as its kind takes them:
``(U, V, W)`` for ``ciphertext``, ``(pk_bytes, U, V, W, share_bytes)`` for
``dec_share``.
"""

from __future__ import annotations

import random
from typing import Any, Dict, List, NamedTuple

from chipbench.generators import Flush
from chipbench.generators.sig_share_rounds import hit_nodes, poly_at
from chipbench.reference import curve as C
from chipbench.reference import verify as V
from chipbench.reference.fields import R

SHARE_KINDS = ("next_key", "identity")


class Keys(NamedTuple):
    secrets: List[int]
    pk_jac: List[tuple]
    pk_bytes: List[bytes]
    master_pk: tuple


def _shape(params: Dict[str, Any]):
    """(requests, ciphertext checks, wrong shares, their kinds, whether the
    ciphertext check is the wrong one) of a flush with ``params``."""
    n = int(params["requests"])
    checks = int(params.get("ciphertext_checks", 0))
    wrong = int(params.get("wrong", 0))
    kinds = list(params.get("wrong_kinds") or ["next_key"])
    unknown = [k for k in kinds if k not in SHARE_KINDS + ("other_w",)]
    if unknown:
        raise ValueError(f"unknown kind of wrong request {unknown[0]!r}")
    if checks not in (0, 1) or n <= checks:
        raise ValueError("a flush has 0 or 1 ciphertext checks and a share at least")
    check_wrong = bool(wrong and "other_w" in kinds)
    if check_wrong and not checks:
        raise ValueError("other_w needs the flush's ciphertext check")
    share_kinds = [k for k in kinds if k in SHARE_KINDS]
    wrong_shares = wrong - check_wrong
    if wrong_shares and not share_kinds:
        raise ValueError(f"{wrong_shares} wrong shares and no kind for them")
    if wrong_shares and "next_key" in share_kinds and n - checks < 2:
        raise ValueError("next_key needs a second signer's share")
    return n, checks, wrong_shares, share_kinds, check_wrong


def make_keys(config: Dict[str, Any], params: Dict[str, Any], seed: int) -> Keys:
    rng = random.Random(f"chipbench decrypt keys {seed}")
    coeffs = [rng.randrange(R) for _ in range(int(config["threshold"]) + 1)]
    shares = int(params["requests"]) - int(params.get("ciphertext_checks", 0))
    signers = max(shares, int(config.get("validators", 0)))  # the probe's too
    secrets = [poly_at(coeffs, x) for x in range(signers + 1)]  # 0: the key set's
    pk_jac = [V.public_share(s) for s in secrets]
    return Keys(
        secrets[1:], pk_jac[1:], [V.g1_to_bytes(p) for p in pk_jac[1:]], pk_jac[0]
    )


def wrong_positions(params: Dict[str, Any], rng: random.Random) -> List[int]:
    """The flush's wrong requests, by position, in rising order."""
    n, checks, wrong_shares, _, check_wrong = _shape(params)
    fixed = [0] if check_wrong else []
    want = params.get("bisection_hit_nodes")
    while True:
        pos = fixed + sorted(rng.sample(range(checks, n), wrong_shares))
        if not pos or want is None or hit_nodes(n, pos) == int(want):
            return pos


def make_flush(
    config: Dict[str, Any],
    params: Dict[str, Any],
    seed: int,
    index: int,
    keys: Keys,
) -> Flush:
    """Flush ``index`` of the run with ``seed``; independent of every other
    index, so a pool can be built in any order or in parallel."""
    from hbbft_tpu.crypto.backend import VerifyRequest
    from hbbft_tpu.crypto.bls.suite import BLSSuite, G1Elem, G2Elem
    from hbbft_tpu.crypto.keys import Ciphertext, DecryptionShare, PublicKeyShare

    suite = BLSSuite()
    n, checks, _, share_kinds, _ = _shape(params)
    shares = n - checks
    if shares > len(keys.secrets):
        raise ValueError(f"{shares} shares a flush and keys for {len(keys.secrets)}")
    rng = random.Random(f"chipbench decrypt flush {seed} {index}")
    proposal = rng.randbytes(int(params.get("payload_bytes", 32)))
    ct = V.encrypt(keys.master_pk, proposal, rng.randrange(1, R))
    bad = wrong_positions(params, rng)
    dealt = {
        pos: share_kinds[k % len(share_kinds)]
        for k, pos in enumerate(p for p in bad if p >= checks)
    }

    def program_ct(w_jac) -> Any:
        return Ciphertext(G1Elem(ct.u), ct.v, G2Elem(w_jac), suite)

    own = program_ct(ct.w)
    requests, expected, wire, kinds = [], [], [], []
    if checks:
        sent_w, sent_w_bytes = ct.w, ct.w_bytes
        if 0 in bad:
            other = V.encrypt(keys.master_pk, proposal, rng.randrange(1, R))
            sent_w, sent_w_bytes = other.w, other.w_bytes
        requests.append(VerifyRequest.ciphertext(program_ct(sent_w)))
        expected.append(0 not in bad)
        wire.append((ct.u_bytes, ct.v, sent_w_bytes))
        kinds.append("ciphertext")
    share_jac = [V.decryption_share(s, ct.u) for s in keys.secrets[:shares]]
    for i in range(shares):
        kind = dealt.get(checks + i)
        if kind is None:
            share = share_jac[i]
        elif kind == "next_key":
            share = share_jac[(i + 1) % shares]
        else:
            share = C.jac_identity(C.FQ_OPS)
        requests.append(
            VerifyRequest.dec_share(
                PublicKeyShare(G1Elem(keys.pk_jac[i]), suite),
                own,
                DecryptionShare(G1Elem(share), suite),
            )
        )
        expected.append(kind is None)
        wire.append(
            (keys.pk_bytes[i], ct.u_bytes, ct.v, ct.w_bytes, V.g1_to_bytes(share))
        )
        kinds.append("dec_share")
    return Flush(requests, expected, wire, kinds)
