"""The ``HBBFT_TPU_*`` environment-knob registry (HBX002 ground truth).

Every env knob the repo reads must have an entry here: its default, the
layer that owns it, and what flipping it means for an A/B run.  HBX002
(tools/lint/contracts.py) diffs this registry against every
``os.environ`` / ``getenv`` reference site in the tree — an
unregistered knob, a registered-but-unreferenced knob, or a stale
``docs/KNOBS.md`` is a finding.

To add a knob: add the ``Knob`` entry here, reference it in code, and
regenerate the doc (``python -m tools.lint --knobs-md >
docs/KNOBS.md``).  To retire one: delete the entry, delete every
reference, regenerate.  Half-measures trip the linter by design.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict


@dataclass(frozen=True)
class Knob:
    """One env knob: default, owning layer, and A/B semantics."""

    name: str
    default: str
    layer: str
    semantics: str


def _k(name: str, default: str, layer: str, semantics: str) -> Knob:
    return Knob(name, default, layer, semantics)


# Ordered: the generated docs/KNOBS.md table keeps this order.
KNOBS: Dict[str, Knob] = {
    k.name: k
    for k in (
        _k(
            "HBBFT_TPU_ARENA",
            "1 (on)",
            "native engine",
            "`0` makes the per-node epoch arena FREE its blocks at every "
            "watermark reset instead of recycling them (round-17 A/B "
            "arm).  Same containers, same carve order — outputs are "
            "byte-identical either way (docs/INVARIANTS.md \"epoch-state "
            "arena\"); only allocator traffic differs.  Read once at "
            "`hbe_create`.",
        ),
        _k(
            "HBBFT_TPU_COIN_RLC",
            "1 (on)",
            "native engine + TS/TD protocols",
            "`0` restores per-share scalar COIN/DECRYPT verification on "
            "the same build (round-7 A/B arm).  Outputs are identical "
            "either way — RLC is an optimization, never a semantics "
            "change (docs/INVARIANTS.md \"RLC byte-identity\").",
        ),
        _k(
            "HBBFT_TPU_COALESCE",
            "1 (on)",
            "transport (TcpTransport egress)",
            "`0` restores one MSG frame per protocol message (round-20 "
            "A/B arm).  On, each egress sweep packs a peer's pending "
            "payloads into batched `KIND_MSGB` frames (bounded by "
            "`max_frame_len`), acked per FRAME with batch-atomic "
            "consumption — `batches_sha` is identical either way, and "
            "mixed clusters interop because ingress always accepts both "
            "kinds (docs/TRANSPORT.md \"Message coalescing\").",
        ),
        _k(
            "HBBFT_TPU_CRYPTO_RPC_TIMEOUT_S",
            "30.0",
            "cryptoplane/proc_service (RPC clients)",
            "Seconds an `RpcServiceClient` waits on one crypto-service "
            "RPC round trip before re-verifying THAT flush on its local "
            "fallback backend (verdict-identical — the deferred-"
            "verification invariant).  Generous by design: the fallback "
            "exists for service death, not scheduler jitter on a loaded "
            "1-core box.",
        ),
        _k(
            "HBBFT_TPU_CRYPTO_SERVICE",
            "unset (spawn per cluster)",
            "cryptoplane/proc_service + transport clusters",
            "`host:port` of an externally-run crypto-plane service "
            "process.  When set, `LocalCluster(crypto=\"service-proc\")` "
            "and `ProcCluster(crypto=\"service-proc\")` attach to it "
            "instead of spawning an owned worker — the way one "
            "TpuBackend service (started once, warm cache) serves many "
            "benchmark runs.",
        ),
        _k(
            "HBBFT_TPU_CT_HASH_CACHE",
            "1 (on)",
            "native engine",
            "`0` disables the shared-payload DKG-ciphertext hash cache "
            "(`Engine::ct_hash_by_payload`), restoring the round-5 "
            "per-(node, proposer) re-hash for era-change A/Bs "
            "(BASELINE.md round 6).",
        ),
        _k(
            "HBBFT_TPU_CRYPTO_WINDOW_S",
            "0.002",
            "cryptoplane/proc_service (service worker)",
            "The service process's cross-client batching window: how "
            "long the first pending verify request holds the flush open "
            "for more nodes' requests to merge in.  Bigger = larger "
            "amortized backend batches at higher per-check latency (the "
            "arxiv 2407.12172 trade); `0` flushes as soon as the worker "
            "wakes.  Worker `--window-s` overrides.",
        ),
        _k(
            "HBBFT_TPU_DKG_BATCH",
            "1 (on)",
            "crypto/keys + sync_key_gen",
            "`0` restores the round-5 per-item DKG ack/row checks, "
            "A/B-ing the whole round-6 batch plane (vectorized "
            "generate/combines, Part batch check, ack predigest) on one "
            "build.",
        ),
        _k(
            "HBBFT_TPU_ENGINE_LIB",
            "unset (build in-tree)",
            "native_engine loader",
            "Absolute path to a pre-built engine shared library "
            "(sanitizer builds use this).  A set-but-unloadable path is "
            "a loud failure, never a silent fallback.",
        ),
        _k(
            "HBBFT_TPU_NO_NATIVE",
            "unset (native on)",
            "ops/native builder",
            "Any value disables building/loading the native ops "
            "library; pure-Python fallbacks take over.  Correctness "
            "arm, not a perf arm.",
        ),
        _k(
            "HBBFT_TPU_SENDMSG",
            "unset (auto: gather egress)",
            "transport",
            "`0` forces buffered per-frame egress instead of the "
            "sendmsg/vectored gather path.  Perf-neutral at N=16 thread "
            "mode on this box (BASELINE.md round 14) — it exists for "
            "A/B honesty, not as a tuning lever here.",
        ),
        _k(
            "HBBFT_TPU_SIMD",
            "unset (auto: cpuid)",
            "native field plane",
            "`0` pins the scalar Montgomery arm; `1` forces AVX-512 "
            "IFMA.  Arms are byte-identical by the SIMD dispatch "
            "identity invariant (docs/INVARIANTS.md); in-process flips "
            "use `hbe_simd_force(0|1|-1)`.",
        ),
        _k(
            "HBBFT_TPU_SKIP_BLS_ERA",
            "unset (test runs)",
            "tests (protocol tier)",
            "`1` skips the ~35 s real-BLS era-change test for quick "
            "protocol-tier loops (CLAUDE.md).",
        ),
    )
}


def generate_knobs_md() -> str:
    """The exact content of docs/KNOBS.md (HBX002 pins the committed
    file to this output byte-for-byte)."""
    lines = [
        "# HBBFT_TPU_* environment knobs",
        "",
        "<!-- GENERATED FILE — do not edit by hand.",
        "     Source of truth: tools/lint/knob_registry.py.",
        "     Regenerate: python -m tools.lint --knobs-md > docs/KNOBS.md -->",
        "",
        "Every environment knob the repo reads, with its default, owning",
        "layer, and A/B semantics.  The invariant linter (HBX002) keeps",
        "this file, the registry, and the reference sites in the code in",
        "three-way agreement: an unregistered knob, a dead registry",
        "entry, or a stale copy of this file fails `make lint`.",
        "",
    ]
    for k in KNOBS.values():
        lines.append(f"## `{k.name}`")
        lines.append("")
        lines.append(f"* **Default:** {k.default}")
        lines.append(f"* **Layer:** {k.layer}")
        lines.append(f"* **Semantics:** {k.semantics}")
        lines.append("")
    return "\n".join(lines)
