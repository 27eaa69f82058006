"""Sanitizer tier: the native engine under ASan/UBSan/TSan.

``make -C native asan|ubsan|tsan`` builds instrumented engine libraries;
``HBBFT_TPU_ENGINE_LIB`` (hbbft_tpu/native_engine.py) loads them in place
of the normal build.  Python itself is not instrumented, so the
sanitizer runtime must be LD_PRELOADed into the subprocess; each test
therefore drives a fresh interpreter rather than loading the lib here.

The driven workload is the small-N native epoch of the equivalence
suites (ASan/UBSan, default tier) and an ``engine_run_mt`` multi-thread
epoch (TSan, slow tier — the multicore worker rules in CLAUDE.md are
exactly what TSan checks mechanically).  The driver never imports jax:
the protocol plane is pure Python + the C++ engine, which keeps the
sanitized process small and the reports clean.
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "native")

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None or shutil.which("make") is None,
    reason="no C++ toolchain",
)

# One complete epoch at N=4 (one silent-faulty by default), asserting
# the correct nodes commit identical batch sequences — a miniature of
# tests/test_native_engine.py's fidelity contract, run for the
# sanitizer's benefit rather than for protocol coverage.
DRIVER = """
import sys
from hbbft_tpu import native_engine
assert native_engine.available(), "sanitized engine failed to load"
threads = int(sys.argv[1]) if len(sys.argv) > 1 else 0
kw = {"threads": threads} if threads else {}
nat = native_engine.NativeQhbNet(
    4, seed=1, batch_size=3, session_id=b"sanitizer", **kw
)
for i in range(4):
    nat.send_input(i, ("tx", i))
# chunk must batch MANY deliveries per engine call in threaded mode:
# engine_run_mt takes one generation per call of at most `chunk` queue
# items, and a generation with a single destination runs inline on the
# calling thread — chunk=1 would make the TSan run single-threaded and
# vacuous.  256 yields multi-destination generations (real worker
# threads) and the predicate still stops us within one chunk of the
# first batch (no QHB empty-epoch runaway).
nat.run_until(
    lambda e: all(len(e.nodes[i].outputs) >= 1 for i in e.correct_ids),
    chunk=1 if threads == 0 else 256,
)
keys = [
    [(b.era, b.epoch, b.contributions) for b in nat.nodes[i].outputs[:1]]
    for i in nat.correct_ids
]
assert all(k == keys[0] for k in keys), "correct nodes diverged"
print("SANITIZED-EPOCH-OK")

# A full era change drives the round-6 batch-digest entry points under
# the sanitizer: hbe_dkg_ack_check_batch / hbe_dkg_part_check_batch
# (registry copy-out + batched KEM/Horner), hbe_scalar_interp_sum /
# hbe_scalar_combine_unmask, and the shared ct-hash cache.
from hbbft_tpu.protocols.dynamic_honey_badger import Change
from hbbft_tpu.protocols.queueing_honey_badger import Input

keep = dict(nat.nodes[0].qhb.dhb.netinfo.public_key_map)
keep.pop(3)
for i in nat.correct_ids:
    nat.send_input(i, Input.change(Change.node_change(keep)))

def era_done(e):
    return all(
        any(b.change.kind == "complete" for b in e.nodes[i].outputs)
        for i in e.correct_ids
    )

rounds = 1
while not era_done(nat) and rounds < 12:
    for i in nat.correct_ids:
        nat.send_input(i, Input.user(("era-tx", rounds, i)))
    rounds += 1
    nat.run_until(
        lambda e, w=rounds: all(
            len(e.nodes[i].outputs) >= w for i in e.correct_ids
        ),
        chunk=1 if threads == 0 else 256,
    )
assert era_done(nat), "sanitized era change did not complete"
print("SANITIZED-ERA-OK")

# Round 7: a deferred-RLC epoch with corrupt COIN/DECRYPT shares from
# node 0 — every group containing one of its shares FAILS the RLC check
# and runs the bisection (rlc_assign_range down to per-item leaves,
# the CSR group scratch, the folded group continuations): the new
# branchy code most likely to hide an OOB, exercised under the
# sanitizer with verdicts ending in real fault entries.
import ctypes
from hbbft_tpu.native_engine import _TAMPER_CB

nat2 = native_engine.NativeQhbNet(
    4, seed=1, batch_size=3, session_id=b"sanitizer-rlc",
    rlc=True, flush_every=0,
)
lib, h = nat2.lib, nat2.handle
mod = nat2._suite.scalar_modulus

def corrupt(sender, mtype, era, epoch, proposer, rnd):
    if mtype not in (8, 10):  # BA_COIN / HB_DECRYPT
        return
    buf = (ctypes.c_uint8 * 32)()
    lib.hbe_tamper_share(h, buf)
    out = (2 * int.from_bytes(bytes(buf), "big") % mod).to_bytes(32, "big")
    ob = (ctypes.c_uint8 * 32).from_buffer_copy(out)
    lib.hbe_tamper_set_share(h, ob, 32)

cb = _TAMPER_CB(corrupt)
lib.hbe_set_tamper(h, cb)
lib.hbe_set_tampered(h, 0, 1)
# node 3 is silent-faulty (default f=1); nodes 1/2 are the honest
# observers whose fault logs must pin node 0's corrupt shares.
for i in nat2.correct_ids:
    nat2.send_input(i, ("rlc-tx", i))
nat2.run_until(
    lambda e: all(len(e.nodes[i].outputs) >= 1 for i in (1, 2)),
    chunk=256,
)
kinds = {k for i in (1, 2) for (_, k) in nat2.faults(i)}
assert "threshold_sign:invalid-share" in kinds, kinds
assert int(lib.hbe_prof_count(h, 11)) > 0, "RLC verdict pass never ran"
print("SANITIZED-RLC-BISECT-OK")

# Round 9: the message-boundary wire API on hostile input.  A cluster-
# mode engine produces real egress frames; every truncation and a bit-
# flip sweep of one goes through hbe_wire_classify (decode-only), and a
# mixed good/corrupt/short batch through hbe_node_ingest_frames — the
# byte-parsing surfaces a Byzantine peer reaches first, where an OOB
# read hides most easily.  Verdicts are parity-pinned elsewhere
# (tests/test_transport_native.py); the sanitizer's job here is the
# memory safety of the reject paths.
import random as _wrng
from hbbft_tpu.crypto.suite import ScalarSuite
from hbbft_tpu.native_engine import NativeNodeEngine
from hbbft_tpu.transport.cluster import build_netinfo

_suite = ScalarSuite()
node = NativeNodeEngine(
    0, build_netinfo(4, 1, 0, _suite, 0), seed=0, batch_size=3,
    session_id=b"san-wire",
)
node.handle_input(Input.user("wire-tx"))
node.run()
frames = []
node.drain_egress(lambda d, p: frames.append(p))
assert frames, "cluster-mode engine produced no egress"
payload = frames[0]
wl = node.lib
for cut in range(len(payload) + 1):
    wl.hbe_wire_classify(payload[:cut], cut)
rng9 = _wrng.Random(5)
mut = payload
for _ in range(500):
    i = rng9.randrange(len(payload))
    mut = payload[:i] + bytes([payload[i] ^ (1 << rng9.randrange(8))]) + payload[i + 1:]
    wl.hbe_wire_classify(mut, len(mut))
batch = [payload[: len(payload) // 2], b"", bytes([255]) * 9, mut, payload]
node.ingest([1, 2, 99, 0, 2], batch)  # 99 out of range, 0 = local: both bad
node.run()
assert node.stats()["bad_payload"] >= 2, node.stats()
print("SANITIZED-WIRE-OK")

# Round 20: the MSGB wire fast path on hostile input.  Real per-dest
# MSGB bodies from hbe_node_egress_drain_msgb come back through
# hbe_node_ingest_wire interleaved with structurally-corrupt records —
# claim mismatch, truncation, trailing garbage, an inflated count —
# the exact C walk where an OOB read hides; then a clamped max_body
# drain exercises the group-split path.  Verdict parity is pinned in
# tests/test_transport_native.py; the sanitizer's job here is the
# memory safety of the reject paths.
nodeb = NativeNodeEngine(
    0, build_netinfo(4, 1, 0, _suite, 0), seed=0, batch_size=3,
    session_id=b"san-msgb",
)
nodeb.handle_input(Input.user("msgb-tx"))
nodeb.run()
groups = []
nodeb.drain_egress_msgb(lambda d, nm, b: groups.append((nm, b)), 1 << 20)
assert any(nm > 1 for nm, _ in groups), "no MSGB groups drained"
gnm, gbody = next((nm, b) for nm, b in groups if nm > 1)
records = [
    (gnm, gbody),                                     # clean batch
    (gnm + 1, gbody),                                 # claim mismatch
    (gnm, gbody[: len(gbody) // 2]),                  # truncated
    (gnm, gbody + bytes([0, 7])),                     # trailing garbage
    (gnm + 9, (gnm + 9).to_bytes(4, "big") + gbody[4:]),  # inflated count
    (1, b""),                                         # empty body
    (0, gbody),                                       # MSGB bytes as MSG
]
before20 = nodeb.stats()
nodeb.ingest_wire([1, 2, 3, 1, 2, 3, 1], records)
nodeb.run()
after20 = nodeb.stats()
assert after20["handled"] - before20["handled"] >= gnm, after20
assert after20["bad_payload"] - before20["bad_payload"] >= 5, after20
nodeb.drain_egress_msgb(lambda d, nm, b: None, 1)  # clamped split drain
print("SANITIZED-MSGB-OK")

# Round 11: one mixed good/equivocating/corrupt ingest batch.  The
# chaos plane's equivocation/corrupt-share variants are VALID wire
# traffic (TamperingAdversary rewrites re-encoded over the same serde
# grammar) — the decoder must classify and ingest them interleaved with
# corrupt and truncated frames without the sanitizer noticing anything.
from hbbft_tpu.chaos.strategies import (
    EQUIVOCABLE_KINDS, SHARE_KINDS, tamper_payload,
)

rng11 = _wrng.Random(11)
node3 = NativeNodeEngine(
    0, build_netinfo(4, 1, 0, _suite, 0), seed=0, batch_size=3,
    session_id=b"san-chaos",
)
node3.handle_input(Input.user("chaos-tx"))
node3.run()
frames3 = []
node3.drain_egress(lambda d, p: frames3.append(p))
variants = []
for p in frames3:
    v = tamper_payload(p, rng11, _suite, EQUIVOCABLE_KINDS | SHARE_KINDS)
    if v is not None:
        variants.append(v)
assert variants, "no equivocable egress traffic produced"
for v in variants:
    assert int(wl.hbe_wire_classify(v, len(v))) > 0, "variant rejected"
good = frames3[0]
corrupt = bytes([good[0] ^ 0xFF]) + good[1:]
mixed = [
    good,
    variants[0],
    corrupt,
    variants[-1][: max(1, len(variants[-1]) // 2)],
    variants[0] + b"\\x00",  # trailing garbage: reject path
]
node3.ingest([1, 2, 3, 1, 2], mixed)
node3.run()
assert node3.stats()["handled"] >= 2, node3.stats()
print("SANITIZED-CHAOS-OK")

# Round 15: the vectorized field plane under the sanitizer, BOTH
# dispatch arms forced in-process (hbe_simd_force).  The kernel fuzz
# drives the AoS<->SoA conversion/normalization edges (odd tails,
# non-canonical congruent inputs, near-r values) where an OOB or
# carry bug hides; the epoch re-run pins cross-arm protocol identity
# under instrumentation.  On a non-IFMA host force(1) resolves to the
# scalar arm and this degenerates to scalar-vs-scalar (still a valid
# sanitizer pass of the batch plane).
import random as _frng

flib = nat.lib
mod_r = (0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001)
rng15 = _frng.Random(15)
# fixed index set for the cross-arm Lagrange comparison (the fuzz rng
# advances differently per arm; cross-arm identity needs equal inputs)
lag_idxs = _frng.Random(99).sample(range(200), 33)
arm_results = []
for arm in (0, 1):
    got = int(flib.hbe_simd_force(arm))
    for trial in range(6):
        n = rng15.choice([1, 3, 7, 8, 9, 17, 40])
        a = [rng15.randrange(mod_r) for _ in range(n)]
        b = [
            v + mod_r
            if rng15.random() < 0.4 and v + mod_r < (1 << 256)
            else v
            for v in (rng15.randrange(mod_r) for _ in range(n))
        ]
        ab = b"".join(x.to_bytes(32, "big") for x in a)
        bb = b"".join(x.to_bytes(32, "big") for x in b)
        out = (ctypes.c_uint8 * (32 * n))()
        flib.hbe_field_mul_batch(ab, bb, n, out)
        got_v = [
            int.from_bytes(bytes(out[32 * i : 32 * i + 32]), "big")
            for i in range(n)
        ]
        assert got_v == [(x * y) % mod_r for x, y in zip(a, b)], (arm, trial)
        o32 = (ctypes.c_uint8 * 32)()
        flib.hbe_field_dot(ab, bb, n, o32)
        assert int.from_bytes(bytes(o32), "big") == (
            sum(x * y for x, y in zip(a, b)) % mod_r
        ), (arm, trial)
    k = 33
    outl = (ctypes.c_uint8 * (32 * k))()
    flib.hbe_field_lagrange((ctypes.c_int32 * k)(*lag_idxs), k, outl)
    nat15 = native_engine.NativeQhbNet(
        4, seed=3, batch_size=3, session_id=b"sanitizer-simd", **kw
    )
    for i in nat15.correct_ids:
        nat15.send_input(i, ("simd-tx", i))
    nat15.run_until(
        lambda e: all(len(e.nodes[i].outputs) >= 1 for i in e.correct_ids),
        chunk=1 if threads == 0 else 256,
    )
    arm_results.append(
        (
            bytes(outl),
            [
                [
                    (b.era, b.epoch, b.contributions)
                    for b in nat15.nodes[i].outputs[:1]
                ]
                for i in nat15.correct_ids
            ],
        )
    )
    nat15.close()
flib.hbe_simd_force(-1)
assert arm_results[0] == arm_results[1], "SIMD arms diverged"
print("SANITIZED-SIMD-OK")

# Round 17: the epoch arena + batched sha3 plane.  The default arm
# (ARENA=1, every stage above) POISONS recycled blocks under ASan, so
# any use-after-reset in the epoch path already trips; here the
# free-every-epoch arm (HBBFT_TPU_ARENA=0, read at hbe_create) runs
# the opening script too — both reset models sanitized, first-batch
# output pinned identical.  The sha3 batch kernel is fuzzed at the
# SHA3-256 rate boundaries in both dispatch arms against hashlib (the
# x8 gather/scatter absorb in field_ifma.cpp is where an OOB hides).
import hashlib as _hl
import os as _os

for _arm in (0, 1):
    flib.hbe_simd_force(_arm)
    for _mlen in (0, 1, 135, 136, 137, 271, 272):
        for _cnt in (1, 7, 8, 9, 17):
            _msgs = [
                bytes((_arm * 31 + i + j) & 0xFF for j in range(_mlen))
                for i in range(_cnt)
            ]
            _out = (ctypes.c_uint8 * (32 * _cnt))()
            flib.hbe_sha3_batch(b"".join(_msgs), _mlen, _cnt, _out)
            for i in range(_cnt):
                assert (
                    bytes(_out[32 * i : 32 * i + 32])
                    == _hl.sha3_256(_msgs[i]).digest()
                ), (_arm, _mlen, _cnt, i)
flib.hbe_simd_force(-1)

_os.environ["HBBFT_TPU_ARENA"] = "0"
try:
    nat17 = native_engine.NativeQhbNet(
        4, seed=1, batch_size=3, session_id=b"sanitizer", **kw
    )
    for i in range(4):
        nat17.send_input(i, ("tx", i))
    nat17.run_until(
        lambda e: all(len(e.nodes[i].outputs) >= 1 for i in e.correct_ids),
        chunk=1 if threads == 0 else 256,
    )
    keys17 = [
        [(b.era, b.epoch, b.contributions) for b in nat17.nodes[i].outputs[:1]]
        for i in nat17.correct_ids
    ]
    assert keys17 == keys, "ARENA=0 arm diverged from the recycling arm"
    assert nat17.arena_stats()["recycle"] == 0
    nat17.close()
finally:
    _os.environ.pop("HBBFT_TPU_ARENA", None)
print("SANITIZED-ARENA-SHA3-OK")
"""


def _runtime(name: str) -> str:
    """Full path of the sanitizer runtime g++ links against."""
    out = subprocess.run(
        ["g++", f"-print-file-name={name}"],
        capture_output=True,
        text=True,
        check=True,
    ).stdout.strip()
    if not os.path.isabs(out) or not os.path.exists(out):
        pytest.skip(f"{name} runtime not installed")
    return out


def _build(target: str) -> str:
    res = subprocess.run(
        ["make", "-C", NATIVE, target],
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert res.returncode == 0, f"make {target} failed:\n{res.stderr[-4000:]}"
    lib = os.path.join(NATIVE, "build", f"libhbbft_engine_{target}.so")
    assert os.path.exists(lib)
    return lib


def _drive(lib: str, preload: str, extra_env: dict, threads: int = 0):
    env = {
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        # a minimal environment: the driver has no jax dependency
        "PYTHONPATH": REPO,
        "HBBFT_TPU_ENGINE_LIB": lib,
        "LD_PRELOAD": preload,
        **extra_env,
    }
    cmd = [sys.executable, "-c", DRIVER]
    if threads:
        cmd.append(str(threads))
    return subprocess.run(
        cmd, env=env, capture_output=True, text=True, timeout=600
    )


def test_asan_native_epoch():
    lib = _build("asan")
    res = _drive(
        lib,
        _runtime("libasan.so"),
        # Python's own allocations "leak" by ASan's lights; the engine
        # checks we care about are heap misuse, not the interpreter's
        # exit-time bookkeeping.
        {"ASAN_OPTIONS": "detect_leaks=0"},
    )
    assert res.returncode == 0, res.stderr[-4000:]
    assert "SANITIZED-EPOCH-OK" in res.stdout
    assert "SANITIZED-ERA-OK" in res.stdout
    assert "SANITIZED-RLC-BISECT-OK" in res.stdout
    assert "SANITIZED-MSGB-OK" in res.stdout
    assert "SANITIZED-SIMD-OK" in res.stdout
    assert "SANITIZED-CHAOS-OK" in res.stdout
    assert "SANITIZED-ARENA-SHA3-OK" in res.stdout
    assert "AddressSanitizer" not in res.stderr


def test_ubsan_native_epoch():
    lib = _build("ubsan")
    res = _drive(lib, _runtime("libubsan.so"), {})
    assert res.returncode == 0, res.stderr[-4000:]
    assert "SANITIZED-EPOCH-OK" in res.stdout
    assert "SANITIZED-ERA-OK" in res.stdout
    assert "SANITIZED-RLC-BISECT-OK" in res.stdout
    assert "SANITIZED-MSGB-OK" in res.stdout
    assert "SANITIZED-SIMD-OK" in res.stdout
    assert "SANITIZED-CHAOS-OK" in res.stdout
    assert "SANITIZED-ARENA-SHA3-OK" in res.stdout
    assert "runtime error" not in res.stderr


@pytest.mark.slow
def test_tsan_multithread_epoch():
    lib = _build("tsan")
    res = _drive(
        lib,
        _runtime("libtsan.so"),
        {"TSAN_OPTIONS": "report_thread_leaks=0"},
        threads=2,
    )
    assert res.returncode == 0, res.stderr[-4000:]
    assert "SANITIZED-EPOCH-OK" in res.stdout
    assert "SANITIZED-ERA-OK" in res.stdout
    assert "SANITIZED-RLC-BISECT-OK" in res.stdout
    assert "SANITIZED-MSGB-OK" in res.stdout
    assert "SANITIZED-SIMD-OK" in res.stdout
    assert "SANITIZED-ARENA-SHA3-OK" in res.stdout
    assert "WARNING: ThreadSanitizer" not in res.stderr
