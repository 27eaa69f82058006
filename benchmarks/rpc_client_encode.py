"""Host CPU time of the RPC client's encode of one flush of each chipbench
traffic mix, in two arrivals:

- ``pickled``: the flush as the benchmark hands it to the client, every
  point Jacobian with z != 1 and no cached bytes (pickling drops them);
- ``wire``: as a node holds it, the shares, signatures and ciphertexts
  decoded from their wire bytes (``serde.loads``: bytes cached, z = 1) and
  the key shares as ``PublicKeySet.public_key_share`` evaluates them
  (Jacobian, no bytes).

For each it times the two encodes on fresh copies: ``two_dumps_ms``, a
probing ``serde.dumps`` of the batch and then the frame (the client's
encode before the batch conversion), and ``batch_one_dumps_ms``,
``_warm_affine_caches`` (one field inversion per group) and then the frame,
as ``RpcServiceClient._encode`` does; ``points`` is what the conversion
counts in ``crypto.rpc.encode_affine_points``.  Both frames are checked to
be the same bytes.  No device is used.

Usage, from the repo root:
    python benchmarks/rpc_client_encode.py [--seed N] [--reps N]
One JSON line per traffic mix and arrival.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import pickle
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELLS = (("wan104", "decrypt103"), ("hb16", "decrypt15"), ("coin16", "clean16"))


def _as_received(blob, suite):
    """The pickled batch with every payload but the key share decoded
    from wire bytes (those of another copy, so the key shares kept here
    stay without bytes)."""
    from hbbft_tpu.crypto.backend import VerifyRequest
    from hbbft_tpu.utils import serde

    reqs = pickle.loads(blob)
    wire = serde.loads(serde.dumps(tuple(pickle.loads(blob))), suite=suite)
    return [
        VerifyRequest(w.kind, (r.payload[0],) + tuple(w.payload[1:]))
        if len(r.payload) == 3
        else w
        for r, w in zip(reqs, wire)
    ]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=3043000001)
    ap.add_argument("--reps", type=int, default=7)
    args = ap.parse_args()

    import hbbft_tpu.wire  # noqa: F401  (registers the "vreq" serde struct)
    from hbbft_tpu.crypto.backend import _warm_affine_caches
    from hbbft_tpu.crypto.bls.suite import BLSSuite
    from hbbft_tpu.utils import serde

    suite = BLSSuite()
    for cfg_name, traffic in CELLS:
        with open(f"chipbench/configs/{cfg_name}.json") as f:
            cfg = json.load(f)
        with open(f"chipbench/traffic/{traffic}.json") as f:
            tr = json.load(f)
        gen = importlib.import_module(f"chipbench.generators.{tr['generator']}")
        keys = gen.make_keys(cfg, tr["params"], args.seed)
        reqs = gen.make_flush(cfg, tr["params"], args.seed, 1, keys).requests
        blob = pickle.dumps(reqs)
        for arrival in ("pickled", "wire"):
            old, new, points = [], [], None
            for _ in range(args.reps):
                copies = []
                for _ in range(2):
                    if arrival == "pickled":
                        copies.append(pickle.loads(blob))
                    else:
                        copies.append(_as_received(blob, suite))
                r = copies[0]
                t = time.perf_counter()
                serde.dumps(tuple(r))
                want = serde.dumps((1, "verify", tuple(r)))
                old.append(time.perf_counter() - t)
                r = copies[1]
                t = time.perf_counter()
                points = _warm_affine_caches(suite, r)
                got = serde.dumps((1, "verify", tuple(r)))
                new.append(time.perf_counter() - t)
                assert got == want, "the frame's bytes changed"
            print(json.dumps({
                "traffic": traffic, "arrival": arrival, "requests": len(reqs),
                "points": points,
                "two_dumps_ms": sorted(round(x * 1e3, 3) for x in old),
                "batch_one_dumps_ms": sorted(round(x * 1e3, 3) for x in new),
            }), flush=True)


if __name__ == "__main__":
    main()
