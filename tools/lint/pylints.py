"""Python AST lint rules for the documented hbbft_tpu invariants.

Rules (ids referenced from docs/INVARIANTS.md):

* HBT001 — every ``add_unsafe`` call in ``hbbft_tpu/crypto/tpu/`` needs
  a written safety argument: a ``# safety:`` comment on the call (or
  within two lines above it) or an enclosing function docstring that
  mentions ``safety``.
* HBT002 — a child :class:`Step` must not be reused after
  ``map_messages`` (it mutates in place; the old name now aliases the
  wrapped step).
* HBT003 — never ``jax.jit`` a function that constructs an
  interpret-mode ``pallas_call`` (the interpreter's expansion has
  unbounded XLA/LLVM compile time; CLAUDE.md environment gotchas).
* HBT004 — no accumulator chain updated *between* sequential
  ``lax.scan`` segments (XLA 0.9.0 "Unknown MLIR failure", bisected
  round 4; run one scan and reduce what it collected once after it —
  see ``scalar_mul_rlc_g1`` in ``crypto/tpu/curve.py``).
* HBT005 — wire-deserialization and verify-batch surfaces must reach a
  subgroup check on point inputs (CLAUDE.md: "wire-sourced points MUST
  get subgroup checks somewhere").
* HBT006 — every socket read in ``hbbft_tpu/`` honors the max-frame
  plumbing: ``.recv(...)`` must pass the shared ``RECV_CHUNK`` bound (or
  a literal <= 65536), so no syscall hands the process more untrusted
  bytes than the :class:`FrameDecoder` cap logic admits per read
  (docs/TRANSPORT.md; ``# lint: raw-recv`` escapes non-socket recv()s).

All rules work on (virtual) repo-relative paths, so tests can feed
fixture sources through :func:`lint_files` without touching disk.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from tools.lint import Finding

SAFETY_COMMENT_RE = re.compile(r"#\s*safety:", re.IGNORECASE)
NO_SUBGROUP_RE = re.compile(r"#\s*lint:\s*no-subgroup", re.IGNORECASE)
RAW_RECV_RE = re.compile(r"#\s*lint:\s*raw-recv", re.IGNORECASE)

#: recv() bound HBT006 accepts as a literal; matches framing.RECV_CHUNK.
MAX_RECV_LITERAL = 1 << 16


def _call_name(node: ast.expr) -> Optional[str]:
    """Bare name of a call target: ``foo`` and ``a.b.foo`` -> ``foo``."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


def _dotted(node: ast.expr) -> str:
    """Best-effort dotted name (``jax.lax.scan``); '' if not a name chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_scan_call(node: ast.AST) -> bool:
    return (
        isinstance(node, ast.Call)
        and _call_name(node.func) == "scan"
        and "lax" in _dotted(node.func)
    )


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _function_defs(tree: ast.AST) -> List[ast.FunctionDef]:
    return [
        n
        for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ]


# ---------------------------------------------------------------------------
# HBT001: add_unsafe safety annotations
# ---------------------------------------------------------------------------


def rule_add_unsafe_safety(path: str, src: str, tree: ast.AST) -> List[Finding]:
    if "crypto/tpu/" not in path.replace("\\", "/"):
        return []
    lines = src.splitlines()
    safety_lines = {
        i for i, line in enumerate(lines, 1) if SAFETY_COMMENT_RE.search(line)
    }

    findings: List[Finding] = []

    def docstring_covers(fn: ast.AST) -> bool:
        doc = ast.get_docstring(fn, clean=False) if isinstance(
            fn, (ast.FunctionDef, ast.AsyncFunctionDef)
        ) else None
        return bool(doc and "safety" in doc.lower())

    def visit(node: ast.AST, stack: Tuple[ast.AST, ...]) -> None:
        for child in ast.iter_child_nodes(node):
            child_stack = stack
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                child_stack = stack + (child,)
            if (
                isinstance(child, ast.Call)
                and _call_name(child.func) == "add_unsafe"
            ):
                covered = any(
                    ln in safety_lines
                    for ln in range(child.lineno - 2, child.lineno + 1)
                ) or any(docstring_covers(fn) for fn in stack)
                if not covered:
                    findings.append(
                        Finding(
                            "HBT001",
                            path,
                            child.lineno,
                            "add_unsafe call without a safety argument: add a"
                            " '# safety: ...' comment here or a 'safety'"
                            " argument in the enclosing docstring"
                            " (add_unsafe is WRONG for P == ±Q; CLAUDE.md"
                            " invariant)",
                        )
                    )
            visit(child, child_stack)

    visit(tree, ())
    return findings


# ---------------------------------------------------------------------------
# HBT002: no reuse of a child Step after map_messages
# ---------------------------------------------------------------------------


def rule_step_reuse(path: str, src: str, tree: ast.AST) -> List[Finding]:
    findings: List[Finding] = []

    for fn in _function_defs(tree):
        # Events within THIS function's immediate body (nested defs get
        # their own pass; their closures see names at call time, which
        # lexical order cannot rank — excluded to avoid false positives).
        own_nodes: List[ast.AST] = []

        def collect(node: ast.AST) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
                ):
                    continue
                own_nodes.append(child)
                collect(child)

        collect(fn)

        # map_messages calls on a simple name, excluding self-rebinding
        # (step = step.map_messages(...) leaves no stale alias behind).
        calls: List[Tuple[str, int]] = []
        for node in own_nodes:
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "map_messages"
                and isinstance(node.func.value, ast.Name)
            ):
                calls.append((node.func.value.id, node.lineno))
        if not calls:
            continue

        for name, call_line in calls:
            # >= call_line: the call statement's own assignment target
            # counts — 'step = step.map_messages(...)' rebinds the name
            # to the wrapped step, leaving no stale alias.
            stores = [
                n.lineno
                for n in own_nodes
                if isinstance(n, ast.Name)
                and n.id == name
                and isinstance(n.ctx, ast.Store)
                and n.lineno >= call_line
            ]
            rebound_at = min(stores) if stores else None
            for n in own_nodes:
                if (
                    isinstance(n, ast.Name)
                    and n.id == name
                    and isinstance(n.ctx, ast.Load)
                    and n.lineno > call_line
                    and (rebound_at is None or n.lineno < rebound_at)
                ):
                    findings.append(
                        Finding(
                            "HBT002",
                            path,
                            n.lineno,
                            f"'{name}' is reused after map_messages (line"
                            f" {call_line}): map_messages mutates the child"
                            " step in place; never reuse it (CLAUDE.md"
                            " invariant)",
                        )
                    )
    return findings


# ---------------------------------------------------------------------------
# HBT003: no jit of interpret-mode pallas_call constructors
# ---------------------------------------------------------------------------

_JIT_NAMES = {"jit", "pjit"}


def _interpret_kw(call: ast.Call) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == "interpret":
            return kw.value
    return None


def _pallas_interpret_status(fn: ast.AST) -> Optional[str]:
    """'capable' (interpret is an expression/param), 'always'
    (interpret=True literal), or None (no interpret-mode pallas_call)."""
    status = None
    for node in ast.walk(fn):
        if isinstance(node, ast.Call) and _call_name(node.func) == "pallas_call":
            kw = _interpret_kw(node)
            if kw is None:
                continue  # defaults to compiled mode
            if isinstance(kw, ast.Constant):
                if kw.value is True:
                    return "always"
                continue  # interpret=False literal
            status = "capable"
    return status


def rule_jit_interpret_pallas(path: str, src: str, tree: ast.AST) -> List[Finding]:
    status_by_name: Dict[str, str] = {}
    for fn in _function_defs(tree):
        st = _pallas_interpret_status(fn)
        if st is not None:
            # Prefer 'always' if any same-named def has it.
            prev = status_by_name.get(fn.name)
            status_by_name[fn.name] = (
                "always" if "always" in (st, prev) else st
            )
    findings: List[Finding] = []

    def flag(line: int, fname: str, how: str) -> None:
        findings.append(
            Finding(
                "HBT003",
                path,
                line,
                f"jit wraps '{fname}', which constructs an interpret-mode"
                f" pallas_call ({how}): jitting the interpreter's expansion"
                " has unbounded XLA/LLVM compile time (CLAUDE.md gotcha);"
                " pin interpret=False under jit, run interpret mode eagerly",
            )
        )

    def check_jit_arg(arg: ast.expr, line: int) -> None:
        if isinstance(arg, ast.Name) and arg.id in status_by_name:
            how = (
                "interpret=True"
                if status_by_name[arg.id] == "always"
                else "interpret not statically pinned False"
            )
            flag(line, arg.id, how)
        elif (
            isinstance(arg, ast.Call)
            and _call_name(arg.func) == "partial"
            and arg.args
            and isinstance(arg.args[0], ast.Name)
            and arg.args[0].id in status_by_name
        ):
            fname = arg.args[0].id
            kw = _interpret_kw(arg)
            pinned_false = (
                isinstance(kw, ast.Constant) and kw.value is False
            )
            if status_by_name[fname] == "always":
                flag(line, fname, "interpret=True")
            elif not pinned_false:
                flag(line, fname, "interpret not statically pinned False")

    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and _call_name(node.func) in _JIT_NAMES
            and node.args
        ):
            check_jit_arg(node.args[0], node.lineno)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                dec_name = (
                    _call_name(dec.func)
                    if isinstance(dec, ast.Call)
                    else _call_name(dec)
                )
                # @partial(jax.jit, static_argnums=...) — the standard
                # idiom for jitting with options — is a jit decorator.
                if (
                    dec_name == "partial"
                    and isinstance(dec, ast.Call)
                    and dec.args
                    and _call_name(dec.args[0]) in _JIT_NAMES
                ):
                    dec_name = _call_name(dec.args[0])
                if dec_name in _JIT_NAMES and _pallas_interpret_status(node):
                    how = (
                        "interpret=True"
                        if _pallas_interpret_status(node) == "always"
                        else "interpret not statically pinned False"
                    )
                    flag(node.lineno, node.name, how)
    return findings


# ---------------------------------------------------------------------------
# HBT004: cross-scan accumulator chains (the XLA 0.9.0 killer)
# ---------------------------------------------------------------------------


def rule_scan_accumulator(path: str, src: str, tree: ast.AST) -> List[Finding]:
    findings: List[Finding] = []

    for fn in _function_defs(tree):
        # Statements of this function only (nested defs excluded: a scan
        # inside a nested def does not run interleaved with our stmts).
        own_stmts: List[ast.stmt] = []

        def collect(node: ast.AST) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
                ):
                    continue
                if isinstance(child, ast.stmt):
                    own_stmts.append(child)
                collect(child)

        collect(fn)

        scan_stmts = [s for s in own_stmts if any(
            _is_scan_call(n) for n in ast.walk(s)
            if not isinstance(n, (ast.FunctionDef, ast.Lambda))
        )]
        if not scan_stmts:
            continue

        # Names that flow through any scan (carry in or out): those form
        # the scan dataflow and are exactly the SAFE pattern (pow_x_abs,
        # the run-length Miller loop).  The killer is a side accumulator
        # that bypasses the scans.
        scan_flow: Set[str] = set()
        for s in scan_stmts:
            for node in ast.walk(s):
                if _is_scan_call(node):
                    for arg in node.args:
                        scan_flow |= _names_in(arg)
            if isinstance(s, ast.Assign):
                for tgt in s.targets:
                    scan_flow |= _names_in(tgt)

        scan_lines = sorted(s.lineno for s in scan_stmts)
        loops_with_scans: List[ast.stmt] = [
            loop
            for loop in own_stmts
            if isinstance(loop, (ast.For, ast.While))
            and any(s in ast.walk(loop) for s in scan_stmts)
        ]
        multi_segment = len(scan_lines) >= 2 or bool(loops_with_scans)
        if not multi_segment:
            continue

        for stmt in own_stmts:
            if not isinstance(stmt, ast.Assign) or len(stmt.targets) != 1:
                continue
            tgt = stmt.targets[0]
            if not isinstance(tgt, ast.Name):
                continue
            val = stmt.value
            if not isinstance(val, ast.Call) or _is_scan_call(val):
                continue
            name = tgt.id
            arg_names: Set[str] = set()
            for a in list(val.args) + [kw.value for kw in val.keywords]:
                arg_names |= _names_in(a)
            if name not in arg_names or name in scan_flow:
                continue
            between = (
                len(scan_lines) >= 2
                and scan_lines[0] < stmt.lineno < scan_lines[-1]
            )
            in_scan_loop = any(
                stmt in ast.walk(loop) for loop in loops_with_scans
            )
            if between or in_scan_loop:
                findings.append(
                    Finding(
                        "HBT004",
                        path,
                        stmt.lineno,
                        f"accumulator '{name}' is updated between sequential"
                        " lax.scan segments without flowing through the scan"
                        " carry: XLA 0.9.0 dies with 'Unknown MLIR failure'"
                        " on this shape (bisected round 4). Run one scan,"
                        " collect what it yields and reduce once AFTER it"
                        " (see scalar_mul_rlc_g1 in crypto/tpu/curve.py)",
                    )
                )
    return findings


# ---------------------------------------------------------------------------
# HBT005: wire/backends must reach a subgroup check on point inputs
# ---------------------------------------------------------------------------

# Functions whose reachability satisfies the invariant.  _g1/_g2 are the
# wire.py funnels (suite-membership re-checks over elements the serde
# core already subgroup-checked in from_bytes); the rest are the real
# membership tests (host oracle and device mirror).
SUBGROUP_SINKS = {
    "is_g1",
    "is_g2",
    "g1_in_subgroup",
    "g2_in_subgroup",
    "in_subgroup_slow",
    "request_well_formed",
    "endo_subgroup_eq",
    "_g1",
    "_g2",
}

# Entry points that MUST reach a sink wherever they are defined.
SUBGROUP_ENTRY_NAMES = {"g1_from_bytes", "g2_from_bytes", "verify_batch"}

# Struct tags registered in wire.py, classified by whether the struct
# (transitively) carries group elements.  A NEW register_struct tag must
# be added to one of these sets — the linter fails on unknown tags so
# the classification (and, for point structs, the subgroup-check
# obligation) is decided consciously, not by default.
POINT_STRUCT_TAGS = {
    "ct", "sig", "pk", "comm", "bicomm", "change", "svote", "skg",
    "icontrib", "joinplan", "part", "ack",
    # crypto-plane RPC: the pk share's bare G1 plus nested share/ct
    # structs (each re-checked by its own unpacker; the bare G1 goes
    # through _g1's subgroup check in _unpack_verify_request)
    "vreq",
    # transport-boundary live-message tree (group elements ride in the
    # share leaves; envelopes delegate via isinstance of nested types)
    "sigshare", "decshare", "signmsg", "decmsg", "ba_coin", "ba",
    "subsetmsg", "hbmsg", "dhbmsg", "sqmsg",
}
NONPOINT_STRUCT_TAGS = {
    "encsched",
    # transport-boundary types with no group elements anywhere below
    "proof", "bc_value", "bc_echo", "bc_ready", "bc_echohash",
    "bc_candecode", "bools", "ba_bval", "ba_aux", "ba_conf", "ba_term",
}

# Types whose isinstance check counts as delegation: the value was
# decoded by its own registered unpacker (serde core dispatches nested
# structs), so its points were already validated there.
_POINT_TYPE_NAMES = {
    "Ciphertext", "Signature", "PublicKey", "PublicKeySet", "Commitment",
    "BivarCommitment", "Part", "Ack", "Change", "SignedVote",
    "SignedKeyGenMsg",
    "SignatureShare", "DecryptionShare", "SignMessage", "DecryptMessage",
    "CoinMsg", "AbaMessage", "SubsetMessage", "HbMessage", "DhbMessage",
}

_WIRE_MODULES = ("wire.py",)


def _matches(path: str, suffixes: Iterable[str]) -> bool:
    p = path.replace("\\", "/")
    return any(p.endswith(s) for s in suffixes)


def _trivial_body(fn: ast.FunctionDef) -> bool:
    """Protocol stubs: docstring and/or a bare ``...``/``pass``."""
    body = list(fn.body)
    if body and isinstance(body[0], ast.Expr) and isinstance(
        body[0].value, ast.Constant
    ) and isinstance(body[0].value.value, str):
        body = body[1:]
    if not body:
        return True
    return all(
        isinstance(s, ast.Pass)
        or (
            isinstance(s, ast.Expr)
            and isinstance(s.value, ast.Constant)
            and s.value.value is Ellipsis
        )
        or isinstance(s, ast.Raise)
        for s in body
    )


def _has_annotation(src: str, fn: ast.FunctionDef, regex: re.Pattern) -> bool:
    lines = src.splitlines()
    end = getattr(fn, "end_lineno", fn.lineno)
    lo = max(fn.lineno - 2, 1)
    return any(
        regex.search(lines[i - 1]) for i in range(lo, min(end, len(lines)) + 1)
    )


class _CallGraph:
    """Name-resolved call graph over a set of parsed modules.  Edges are
    by bare callee name (``x.foo()`` -> ``foo``): coarse, but sound for
    reachability-to-sink checks (over- rather than under-connects)."""

    def __init__(self) -> None:
        self.calls: Dict[str, Set[str]] = {}
        self.defs: Dict[str, List[Tuple[str, ast.FunctionDef, str]]] = {}

    def add_module(self, path: str, src: str, tree: ast.AST) -> None:
        for fn in _function_defs(tree):
            self.calls.setdefault(fn.name, set()).update(
                self._own_callees(fn)
            )
            self.defs.setdefault(fn.name, []).append((path, fn, src))

    def _own_callees(self, fn: ast.FunctionDef) -> Set[str]:
        return {
            name
            for node in ast.walk(fn)
            if isinstance(node, ast.Call)
            and (name := _call_name(node.func)) is not None
        }

    def reaches_sink(self, name: str) -> bool:
        return self._closure_hits_sink(self.calls.get(name, set()))

    def def_reaches_sink(self, fn: ast.FunctionDef) -> bool:
        """Reachability seeded from THIS def's own calls (same-named
        defs in other classes don't vouch for it)."""
        return self._closure_hits_sink(self._own_callees(fn))

    def _closure_hits_sink(self, seeds: Set[str]) -> bool:
        seen: Set[str] = set()
        work = list(seeds)
        while work:
            cur = work.pop()
            if cur in SUBGROUP_SINKS:
                return True
            if cur in seen:
                continue
            seen.add(cur)
            work.extend(self.calls.get(cur, ()))
        return False


def _wire_registrations(tree: ast.AST) -> List[Tuple[str, str, int]]:
    """(tag, unpack_function_name, lineno) per register_struct call."""
    out = []
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and _call_name(node.func) == "register_struct"
            and len(node.args) >= 4
        ):
            tag = node.args[0]
            unpack = node.args[3]
            if isinstance(tag, ast.Constant) and isinstance(tag.value, str):
                uname = unpack.id if isinstance(unpack, ast.Name) else None
                out.append((tag.value, uname or "", node.lineno))
    return out


def _delegates(graph: _CallGraph, fname: str) -> bool:
    """True if fname (or a same-module callee) funnels its group-bearing
    fields through isinstance checks against registered point types or
    the serde_group structural marker."""
    seen: Set[str] = set()
    work = [fname]
    while work:
        cur = work.pop()
        if cur in seen or cur not in graph.defs:
            continue
        seen.add(cur)
        for _path, fn, _src in graph.defs[cur]:
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    cname = _call_name(node.func)
                    if cname == "isinstance" and len(node.args) == 2:
                        types = node.args[1]
                        elts = (
                            types.elts
                            if isinstance(types, ast.Tuple)
                            else [types]
                        )
                        for t in elts:
                            tn = _call_name(t) or (
                                t.id if isinstance(t, ast.Name) else None
                            )
                            if tn in _POINT_TYPE_NAMES:
                                return True
                    elif cname == "hasattr" and len(node.args) == 2:
                        marker = node.args[1]
                        if (
                            isinstance(marker, ast.Constant)
                            and marker.value == "serde_group"
                        ):
                            return True
                    elif cname in graph.calls:
                        work.append(cname)
    return False


def rule_subgroup_checks(files: Dict[str, ast.AST], sources: Dict[str, str]) -> List[Finding]:
    findings: List[Finding] = []
    graph = _CallGraph()
    # EVERY analyzed module joins the graph: a new Suite/backend added
    # anywhere must reach a check "wherever it is defined" — a fixed
    # module list would silently exempt future implementations.
    for path, tree in files.items():
        graph.add_module(path, sources[path], tree)

    # (a) from_bytes / verify_batch entry points reach a sink.
    for name in SUBGROUP_ENTRY_NAMES:
        for path, fn, src in graph.defs.get(name, ()):
            if _trivial_body(fn):
                continue
            if _has_annotation(src, fn, NO_SUBGROUP_RE):
                continue
            if not graph.def_reaches_sink(fn):
                findings.append(
                    Finding(
                        "HBT005",
                        path,
                        fn.lineno,
                        f"'{name}' never reaches a subgroup/membership check"
                        f" (one of {sorted(SUBGROUP_SINKS)}): wire-sourced"
                        " points MUST get subgroup checks somewhere"
                        " (CLAUDE.md invariant). Annotate '# lint:"
                        " no-subgroup (<why>)' only for groups with no"
                        " torsion to confine (e.g. prime-field scalars)",
                    )
                )

    # (b) wire.py struct registry: classified tags; point tags validate.
    for path, tree in files.items():
        if not _matches(path, _WIRE_MODULES):
            continue
        for tag, uname, lineno in _wire_registrations(tree):
            if tag in NONPOINT_STRUCT_TAGS:
                continue
            if tag not in POINT_STRUCT_TAGS:
                findings.append(
                    Finding(
                        "HBT005",
                        path,
                        lineno,
                        f"register_struct tag '{tag}' is not classified in"
                        " tools/lint/pylints.py (POINT_STRUCT_TAGS /"
                        " NONPOINT_STRUCT_TAGS): decide whether the struct"
                        " carries group elements and record it",
                    )
                )
                continue
            if not uname:
                continue
            ok = graph.reaches_sink(uname) or _delegates(graph, uname)
            if not ok:
                findings.append(
                    Finding(
                        "HBT005",
                        path,
                        lineno,
                        f"unpacker '{uname}' for point struct '{tag}'"
                        " neither reaches a subgroup/membership check nor"
                        " delegates via isinstance against a registered"
                        " point type: Byzantine-authored points would"
                        " construct unchecked",
                    )
                )
    return findings


# ---------------------------------------------------------------------------
# HBT006: socket reads honor the max-frame plumbing
# ---------------------------------------------------------------------------


def rule_bounded_recv(path: str, src: str, tree: ast.AST) -> List[Finding]:
    """Every ``.recv(...)`` call in the product tree must be bounded by
    the shared ``RECV_CHUNK`` constant (or an int literal within it).

    The frame decoder enforces ``max_frame_len`` per frame, but the
    *syscall* is the first place untrusted bytes enter the process — an
    unbounded or over-large recv would let a peer make one event-loop
    iteration buffer arbitrary data before any frame check runs.  The
    escape comment ``# lint: raw-recv`` exists for recv()s that are not
    socket reads of untrusted peers.
    """
    if not path.replace("\\", "/").startswith("hbbft_tpu/"):
        return []
    lines = src.splitlines()
    escapes = {
        i for i, line in enumerate(lines, 1) if RAW_RECV_RE.search(line)
    }
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "recv"
        ):
            continue
        if any(ln in escapes for ln in range(node.lineno - 2, node.lineno + 1)):
            continue
        ok = False
        if len(node.args) == 1 and not node.keywords:
            a = node.args[0]
            if isinstance(a, ast.Name) and a.id == "RECV_CHUNK":
                ok = True
            elif (
                isinstance(a, ast.Constant)
                and type(a.value) is int
                and 0 < a.value <= MAX_RECV_LITERAL
            ):
                ok = True
        if not ok:
            findings.append(
                Finding(
                    "HBT006",
                    path,
                    node.lineno,
                    "unbounded/over-large socket read: pass RECV_CHUNK (or"
                    f" a literal <= {MAX_RECV_LITERAL}) so one syscall never"
                    " buffers more untrusted bytes than the frame decoder"
                    " admits; '# lint: raw-recv' escapes non-socket recv()s"
                    " (docs/TRANSPORT.md read-path rules)",
                )
            )
    return findings


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

_PER_FILE_RULES = (
    rule_add_unsafe_safety,
    rule_step_reuse,
    rule_jit_interpret_pallas,
    rule_scan_accumulator,
    rule_bounded_recv,
)


def lint_files(sources: Dict[str, str]) -> List[Finding]:
    """Lint a path->source mapping (paths repo-relative, '/'-separated)."""
    findings: List[Finding] = []
    trees: Dict[str, ast.AST] = {}
    for path, src in sources.items():
        try:
            trees[path] = ast.parse(src, filename=path)
        except SyntaxError as exc:
            findings.append(
                Finding("HBT000", path, exc.lineno or 1, f"syntax error: {exc.msg}")
            )
    for path, tree in trees.items():
        for rule in _PER_FILE_RULES:
            findings.extend(rule(path, sources[path], tree))
    findings.extend(rule_subgroup_checks(trees, sources))
    return findings
