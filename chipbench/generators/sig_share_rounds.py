"""Generator ``sig_share_rounds``: rounds of signature shares on one document.

One flush is one round: ``requests`` shares of ``requests`` distinct signers
on a document that no other flush of the run uses (the worker caches
``hash_to_g2`` by document, and a real round always brings a new one).
``wrong`` of them are bad shares, of the kinds ``wrong_kinds`` lists.

Parameters (a traffic file's ``params``, and its ``probe``):

* ``requests``: shares per flush.
* ``wrong``: wrong shares per flush (0: a clean round).
* ``wrong_kinds``: the kinds of the wrong shares, dealt in turn to the
  wrong positions in rising order (default ``["next_key"]``):
  ``next_key``, a valid share of the NEXT key index, which is well-formed,
  in the subgroup, and fails only the pairing equation; ``identity``, the
  point at infinity, which decodes and is in every subgroup.  A share off
  the curve or outside the r-torsion cannot be sent: the RPC server's
  decode refuses the whole frame (PERF.md, section 4), so the call fails
  and no verdict comes back.
* ``bisection_hit_nodes``: with ``wrong`` > 0, every round's set of wrong
  positions is drawn from the seed among those that make a halving
  bisection over ``requests`` leaves re-check exactly this many failing
  groups of two or more.  It fixes the work per flush (one aggregate check
  for the whole round and two for each failing group), so seeds differ in
  positions, keys and documents, not in the amount of fault isolation.

Keys come from the configuration: a degree-``threshold`` polynomial over the
scalar field drawn from the seed; signer ``i`` holds ``poly(i + 1)``.

Everything is computed with the benchmark's own plain arithmetic
(chipbench/reference) and wrapped into the program's request types; the wire
bytes the plain reference verifies are kept beside each request, as kind
``sig_share`` takes them: ``(pk_bytes, document, share_bytes)``.
"""

from __future__ import annotations

import random
from typing import Any, Dict, List, NamedTuple, Sequence

from chipbench.generators import Flush
from chipbench.reference import curve as C
from chipbench.reference import verify as V
from chipbench.reference.fields import R


class Keys(NamedTuple):
    secrets: List[int]
    pk_jac: List[tuple]
    pk_bytes: List[bytes]


def poly_at(coeffs: Sequence[int], x: int) -> int:
    """The key polynomial (lowest coefficient first) at ``x``, mod r."""
    acc = 0
    for c in reversed(coeffs):
        acc = (acc * x + c) % R
    return acc


def make_keys(config: Dict[str, Any], params: Dict[str, Any], seed: int) -> Keys:
    rng = random.Random(f"chipbench keys {seed}")
    coeffs = [rng.randrange(R) for _ in range(int(config["threshold"]) + 1)]
    secrets = [poly_at(coeffs, i + 1) for i in range(int(params["requests"]))]
    pk_jac = [V.public_share(s) for s in secrets]
    return Keys(secrets, pk_jac, [V.g1_to_bytes(p) for p in pk_jac])


def hit_nodes(n: int, wrong: Sequence[int]) -> int:
    """Groups of two or more leaves that a halving bisection over ``n``
    leaves finds failing (the whole round included)."""
    bad = set(wrong)

    def walk(lo: int, hi: int) -> int:
        if hi - lo < 2 or not any(lo <= b < hi for b in bad):
            return 0
        mid = lo + (hi - lo) // 2
        return 1 + walk(lo, mid) + walk(mid, hi)

    return walk(0, n)


def wrong_positions(params: Dict[str, Any], rng: random.Random) -> List[int]:
    n, k = int(params["requests"]), int(params.get("wrong", 0))
    if k == 0:
        return []
    want = params.get("bisection_hit_nodes")
    while True:
        pos = sorted(rng.sample(range(n), k))
        if want is None or hit_nodes(n, pos) == int(want):
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
    from hbbft_tpu.crypto.keys import PublicKeyShare, SignatureShare

    suite = BLSSuite()
    n = int(params["requests"])
    rng = random.Random(f"chipbench flush {seed} {index}")
    doc = b"chipbench %s seed %d flush %d" % (
        str(config["name"]).encode(), seed, index,
    )
    kinds = list(params.get("wrong_kinds") or ["next_key"])
    bad = {
        pos: kinds[k % len(kinds)]
        for k, pos in enumerate(wrong_positions(params, rng))
    }
    h = C.hash_to_g2(doc)
    sig_jac = [V.sign(s, h) for s in keys.secrets]
    requests, expected, wire = [], [], []
    for i in range(n):
        kind = bad.get(i)
        if kind is None:
            share = sig_jac[i]
        elif kind == "next_key":
            share = sig_jac[(i + 1) % n]
        elif kind == "identity":
            share = C.jac_identity(C.FQ2_OPS)
        else:
            raise ValueError(f"unknown kind of wrong share {kind!r}")
        requests.append(
            VerifyRequest.sig_share(
                PublicKeyShare(G1Elem(keys.pk_jac[i]), suite),
                doc,
                SignatureShare(G2Elem(share), suite),
            )
        )
        expected.append(kind is None)
        wire.append((keys.pk_bytes[i], doc, V.g2_to_bytes(share)))
    return Flush(requests, expected, wire, ["sig_share"] * n)
