"""Cross-language contract analyzer (HBX001-003).

Three implementations of one protocol (Python oracle, C++ thread
engine, proc-per-node engine) must stay byte-identical; the contracts
binding them live on both sides of the language boundary and drift
silently when only one side is edited.  This module machine-checks the
three contract surfaces:

* **HBX001 — wire-codec parity.**  The Python registry (every
  ``register_struct(tag, ...)`` in ``hbbft_tpu/wire.py``) and the
  engine's mirror (``wenc_struct``/``wenc_share_emsg`` emit sites, the
  ``WireWalk`` decode acceptance, ``take_share_struct``) must agree tag
  for tag, and the caller-supplied ``hbe_serde_scan`` limits in
  ``native/engine.cpp`` must equal serde.py's ``MAX_DEPTH``/``_MAX_LEN``.
  A tag the engine carries that Python cannot decode (or vice versa) is
  a finding.  Tags that legitimately cross only the committed-
  contribution boundary (the engine sees them as opaque bytes) are
  annotated ``# lint: wire-oneside (<reason>)`` at the registration;
  an annotation on a tag the engine DOES carry is itself a finding
  (stale escape).  Decode-only engine tags are fine by design (the
  classifier accepts more than the engine emits), but every emitted tag
  must also be accepted.

* **HBX002 — knob registry.**  Every ``HBBFT_TPU_*`` env knob
  referenced anywhere in the tree must be registered in
  :mod:`tools.lint.knob_registry` (default, owning layer, A/B
  semantics), every registered knob must still be referenced, and the
  committed ``docs/KNOBS.md`` must byte-match the generated output
  (``python -m tools.lint --knobs-md``).  ``tools/lint/`` and
  ``tests/test_lint.py`` are excluded from the reference scan — they
  hold the registry and the mutation fixtures themselves.

* **HBX003 — mirror obligations.**  CLAUDE.md's prose "must be
  mirrored in BOTH continuations" becomes paired anchors: a
  ``# mirror: <key>`` comment in Python and a ``// mirror: <key>``
  comment in C++ mark the two halves of one obligation.  A key present
  on one side only fails, so deleting or renaming either anchor (or the
  code around it) trips the linter and points at the surviving twin.

These are repo-level rules: they read a fixed file set, so they run
only when ``python -m tools.lint`` lints the whole repo (explicit-path
invocations skip them).  All file access goes through an ``overrides``
dict (repo-relative path -> source) so the mutation self-tests in
tests/test_lint.py can seed one-line drifts without touching disk.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Tuple

from tools.lint import Finding, _REPO, knob_registry

WIRE_PY = "hbbft_tpu/wire.py"
SERDE_PY = "hbbft_tpu/utils/serde.py"
ENGINE_CPP = "native/engine.cpp"
KNOBS_MD = "docs/KNOBS.md"
KNOB_REGISTRY_PY = "tools/lint/knob_registry.py"

Overrides = Optional[Dict[str, str]]


def _read_rel(rel: str, overrides: Overrides) -> Optional[str]:
    if overrides and rel in overrides:
        return overrides[rel]
    path = os.path.join(_REPO, rel)
    if not os.path.isfile(path):
        return None
    with open(path, "r", encoding="utf-8") as f:
        return f.read()


# -- C++ text utilities ------------------------------------------------------
#
# cxxlints._strip blanks string CONTENTS (its rules only need structure);
# here the string literals ARE the data — wire tags and knob names — so
# this stripper blanks comments and preserves strings, keeping offsets
# and line structure intact.


def _cxx_strip_comments(src: str) -> str:
    out: List[str] = []
    i, n = 0, len(src)
    quote = ""
    while i < n:
        c = src[i]
        if quote:
            if c == "\\" and i + 1 < n:
                out.append(src[i : i + 2])
                i += 2
            else:
                out.append(c)
                if c == quote or c == "\n":
                    quote = ""
                i += 1
        elif c in "\"'":
            quote = c
            out.append(c)
            i += 1
        elif c == "/" and i + 1 < n and src[i + 1] == "/":
            while i < n and src[i] != "\n":
                out.append(" ")
                i += 1
        elif c == "/" and i + 1 < n and src[i + 1] == "*":
            out.append("  ")
            i += 2
            while i < n and not (c == "*" and src[i] == "/"):
                c = src[i]
                out.append("\n" if c == "\n" else " ")
                i += 1
            if i < n:
                out.append(" ")
                i += 1
        else:
            out.append(c)
            i += 1
    return "".join(out)


def _line_of(text: str, pos: int) -> int:
    return text.count("\n", 0, pos) + 1


def _balanced_args(text: str, open_pos: int) -> str:
    """The argument text between ``(`` at open_pos and its matching
    ``)``, tracking nesting and skipping over string literals."""
    depth = 0
    quote = ""
    i, n = open_pos, len(text)
    while i < n:
        c = text[i]
        if quote:
            if c == "\\" and i + 1 < n:
                i += 1
            elif c == quote or c == "\n":
                quote = ""
        elif c in "\"'":
            quote = c
        elif c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return text[open_pos + 1 : i]
        i += 1
    return text[open_pos + 1 :]


def _split_top(args: str) -> List[str]:
    """Split an argument string on top-level commas (paren/string aware)."""
    parts: List[str] = []
    depth = 0
    quote = ""
    cur: List[str] = []
    i, n = 0, len(args)
    while i < n:
        c = args[i]
        if quote:
            if c == "\\" and i + 1 < n:
                cur.append(args[i : i + 2])
                i += 2
                continue
            if c == quote or c == "\n":
                quote = ""
        elif c in "\"'":
            quote = c
        elif c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
            i += 1
            continue
        cur.append(c)
        i += 1
    parts.append("".join(cur))
    return parts


def _cxx_int(expr: str) -> Optional[int]:
    """Evaluate a C++ integer constant expression of the shapes the
    serde-limit call sites use: a literal (with u/l suffixes, decimal
    or hex) or ``a << b``.  None for anything else."""
    expr = expr.strip()
    if "<<" in expr:
        a, _, b = expr.partition("<<")
        va, vb = _cxx_int(a), _cxx_int(b)
        return None if va is None or vb is None else va << vb
    m = re.fullmatch(r"\(?\s*(0[xX][0-9a-fA-F]+|\d+)\s*[uUlL]*\s*\)?", expr)
    return int(m.group(1), 0) if m else None


# -- HBX001: wire-codec parity ----------------------------------------------

ONESIDE_RE = re.compile(r"#\s*lint:\s*wire-oneside\s*\(\S", re.IGNORECASE)
_TAG_LIT_RE = re.compile(r'"([A-Za-z0-9_]+)"')
_ENC_CALL_RE = re.compile(r"\b(?:wenc_struct|wenc_share_emsg)\s*\(")
_ENTER_RE = re.compile(r"\benter_struct\s*\(\s*(\w+)\s*,\s*(\w+)\s*\)")
_EQ_RE = re.compile(r"\beq\s*\(\s*(\w+)\s*,\s*(\w+)\s*,\s*\"([A-Za-z0-9_]+)\"")
_TAKE_SHARE_RE = re.compile(r"\btake_share_struct\s*\(\s*\"([A-Za-z0-9_]+)\"")
_SCAN_CALL_RE = re.compile(r"\bhbe_serde_scan\s*\(")


def engine_wire_tags(code: str) -> Tuple[Dict[str, int], Dict[str, int]]:
    """(encoded, decoded) tag -> first line, from comment-stripped
    engine source.

    Encode side: every string literal inside a ``wenc_struct`` /
    ``wenc_share_emsg`` call's argument list (paren-tracked, so the
    multi-line ternary emit sites count every branch).  Decode side:
    ``eq(name, len, "tag")`` where ``(name, len)`` is a variable pair
    bound by some ``enter_struct(name, len)`` — kind-string
    comparisons over ``take_str`` vars never bind that way — plus the
    ``take_share_struct("tag", ...)`` literals.
    """
    enc: Dict[str, int] = {}
    dec: Dict[str, int] = {}
    for m in _ENC_CALL_RE.finditer(code):
        args = _balanced_args(code, m.end() - 1)
        for tag in _TAG_LIT_RE.findall(args):
            enc.setdefault(tag, _line_of(code, m.start()))
    pairs = set(_ENTER_RE.findall(code))
    for m in _EQ_RE.finditer(code):
        if (m.group(1), m.group(2)) in pairs:
            dec.setdefault(m.group(3), _line_of(code, m.start()))
    for m in _TAKE_SHARE_RE.finditer(code):
        dec.setdefault(m.group(1), _line_of(code, m.start()))
    return enc, dec


def engine_scan_limits(code: str) -> List[Tuple[int, int, int]]:
    """Every ``hbe_serde_scan(...)`` call whose depth/len arguments are
    integer constant expressions, as (max_depth, max_len, line).  The
    extern declaration and the definition carry parameter names there,
    not literals, so only real caller sites qualify."""
    out: List[Tuple[int, int, int]] = []
    for m in _SCAN_CALL_RE.finditer(code):
        parts = _split_top(_balanced_args(code, m.end() - 1))
        if len(parts) != 6:
            continue
        depth, length = _cxx_int(parts[4]), _cxx_int(parts[5])
        if depth is None or length is None:
            continue
        out.append((depth, length, _line_of(code, m.start())))
    return out


def python_wire_registry(src: str) -> Dict[str, int]:
    """tag -> line of every ``register_struct(tag, ...)`` call (the
    call's first line, so the two-line annotation window above it works
    for multi-line registrations too)."""
    tags: Dict[str, int] = {}
    for node in ast.walk(ast.parse(src)):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = (
            fn.id
            if isinstance(fn, ast.Name)
            else fn.attr if isinstance(fn, ast.Attribute) else None
        )
        if (
            name == "register_struct"
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            tags.setdefault(node.args[0].value, node.lineno)
    return tags


def _py_const_eval(node: ast.AST) -> Optional[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return node.value
    if isinstance(node, ast.BinOp):
        a, b = _py_const_eval(node.left), _py_const_eval(node.right)
        if a is None or b is None:
            return None
        if isinstance(node.op, ast.LShift):
            return a << b
        if isinstance(node.op, ast.Add):
            return a + b
        if isinstance(node.op, ast.Sub):
            return a - b
        if isinstance(node.op, ast.Mult):
            return a * b
        if isinstance(node.op, ast.Pow):
            return a**b
    return None


def python_serde_limits(
    src: str,
) -> Tuple[Optional[Tuple[int, int]], Optional[Tuple[int, int]]]:
    """((MAX_DEPTH, line), (_MAX_LEN, line)) from serde.py, either None
    if the assignment is missing or not a constant expression."""
    depth = length = None
    for node in ast.walk(ast.parse(src)):
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
        ):
            val = _py_const_eval(node.value)
            if val is None:
                continue
            if node.targets[0].id == "MAX_DEPTH" and depth is None:
                depth = (val, node.lineno)
            elif node.targets[0].id == "_MAX_LEN" and length is None:
                length = (val, node.lineno)
    return depth, length


def _annotated(raw_lines: List[str], line: int, rx: re.Pattern) -> bool:
    for ln in range(max(1, line - 2), min(line, len(raw_lines)) + 1):
        if rx.search(raw_lines[ln - 1]):
            return True
    return False


def rule_wire_parity(overrides: Overrides = None) -> List[Finding]:
    findings: List[Finding] = []
    wire_src = _read_rel(WIRE_PY, overrides)
    engine_src = _read_rel(ENGINE_CPP, overrides)
    if wire_src is None or engine_src is None:
        return findings
    py_tags = python_wire_registry(wire_src)
    code = _cxx_strip_comments(engine_src)
    enc, dec = engine_wire_tags(code)
    raw_wire = wire_src.splitlines()
    # Extraction failure must be loud, never silently green: a rename of
    # register_struct/wenc_struct would otherwise turn the rule off.
    if not py_tags:
        findings.append(
            Finding(
                "HBX001",
                WIRE_PY,
                1,
                "extraction failed: no register_struct(tag, ...) calls "
                "found — if the registration API was renamed, teach "
                "tools/lint/contracts.py the new shape",
            )
        )
    if not enc or not dec:
        findings.append(
            Finding(
                "HBX001",
                ENGINE_CPP,
                1,
                "extraction failed: no engine wire "
                f"{'emit' if not enc else 'accept'} sites found — if "
                "wenc_struct/enter_struct were renamed, teach "
                "tools/lint/contracts.py the new shape",
            )
        )
    if findings:
        return findings
    engine_tags = set(enc) | set(dec)
    for tag, line in sorted(py_tags.items()):
        has_escape = _annotated(raw_wire, line, ONESIDE_RE)
        if tag in engine_tags and has_escape:
            findings.append(
                Finding(
                    "HBX001",
                    WIRE_PY,
                    line,
                    f'stale escape: wire tag "{tag}" carries a '
                    "wire-oneside annotation but native/engine.cpp "
                    "mirrors it — drop the annotation",
                )
            )
        elif tag not in engine_tags and not has_escape:
            findings.append(
                Finding(
                    "HBX001",
                    WIRE_PY,
                    line,
                    f'wire tag "{tag}" is registered in the Python codec '
                    "but native/engine.cpp neither emits nor accepts it "
                    "— mirror it in the engine wire codec, or annotate "
                    "the registration `# lint: wire-oneside (<reason>)` "
                    "if it legitimately crosses only the committed-"
                    "contribution boundary",
                )
            )
    for tag in sorted(engine_tags - set(py_tags)):
        findings.append(
            Finding(
                "HBX001",
                ENGINE_CPP,
                enc.get(tag) or dec[tag],
                f'engine wire tag "{tag}" has no register_struct twin in '
                "hbbft_tpu/wire.py — the Python oracle could not decode "
                "engine frames carrying it",
            )
        )
    for tag in sorted(set(enc) - set(dec)):
        findings.append(
            Finding(
                "HBX001",
                ENGINE_CPP,
                enc[tag],
                f'engine emits wire tag "{tag}" but its decode path '
                "never accepts it — a native peer could not parse its "
                "own frames",
            )
        )
    # serde scan limits: Python constants vs the engine's literal-arg
    # hbe_serde_scan call(s).
    serde_src = _read_rel(SERDE_PY, overrides)
    py_depth = py_len = None
    if serde_src is not None:
        py_depth, py_len = python_serde_limits(serde_src)
    limits = engine_scan_limits(code)
    if serde_src is None or py_depth is None or py_len is None:
        findings.append(
            Finding(
                "HBX001",
                SERDE_PY,
                1,
                "extraction failed: MAX_DEPTH/_MAX_LEN constants not "
                "found in serde.py — the serde-limit parity check "
                "cannot run",
            )
        )
    elif not limits:
        findings.append(
            Finding(
                "HBX001",
                ENGINE_CPP,
                1,
                "extraction failed: no hbe_serde_scan call with literal "
                "depth/len arguments found — the serde-limit parity "
                "check cannot run",
            )
        )
    else:
        for depth, length, line in limits:
            if depth != py_depth[0]:
                findings.append(
                    Finding(
                        "HBX001",
                        ENGINE_CPP,
                        line,
                        f"serde scan max_depth {depth} != serde.py "
                        f"MAX_DEPTH {py_depth[0]} "
                        f"({SERDE_PY}:{py_depth[1]}) — the two decoders "
                        "would accept different nesting",
                    )
                )
            if length != py_len[0]:
                findings.append(
                    Finding(
                        "HBX001",
                        ENGINE_CPP,
                        line,
                        f"serde scan max_len {length} != serde.py "
                        f"_MAX_LEN {py_len[0]} ({SERDE_PY}:{py_len[1]}) "
                        "— the two decoders would accept different "
                        "payload sizes",
                    )
                )
    return findings


# -- HBX002: knob registry ---------------------------------------------------

KNOB_FULL_RE = re.compile(r"HBBFT_TPU_[A-Z0-9_]+\Z")
_C_KNOB_RE = re.compile(r'"(HBBFT_TPU_[A-Z0-9_]+)"')

# The scan surface: every tree that reads env knobs.  tools/lint/ (the
# registry + rule sources name knobs) and tests/test_lint.py (mutation
# fixtures) are excluded — they are the checker, not the checked.
_PY_SCAN_ROOTS = ("hbbft_tpu", "benchmarks", "tests", "tools", "examples")
_PY_SCAN_EXTRA = ("chip_smoke.py", "__graft_entry__.py")
_SKIP_DIRS = {"__pycache__", "build", ".jax_cache", ".git"}


def _scan_excluded(rel: str) -> bool:
    return rel.startswith("tools/lint/") or rel == "tests/test_lint.py"


def _py_scan_files(overrides: Overrides) -> List[str]:
    rels = set()
    for root in _PY_SCAN_ROOTS:
        absroot = os.path.join(_REPO, root)
        if not os.path.isdir(absroot):
            continue
        for dirpath, dirnames, filenames in os.walk(absroot):
            dirnames[:] = [d for d in dirnames if d not in _SKIP_DIRS]
            for fn in filenames:
                if fn.endswith(".py"):
                    rels.add(os.path.relpath(os.path.join(dirpath, fn), _REPO))
    for rel in _PY_SCAN_EXTRA:
        if os.path.isfile(os.path.join(_REPO, rel)):
            rels.add(rel)
    if overrides:
        for rel in overrides:
            if rel.endswith(".py") and (
                rel in _PY_SCAN_EXTRA or rel.split("/", 1)[0] in _PY_SCAN_ROOTS
            ):
                rels.add(rel)
    return sorted(r for r in rels if not _scan_excluded(r))


def _c_scan_files(overrides: Overrides) -> List[str]:
    rels = set()
    absroot = os.path.join(_REPO, "native")
    if os.path.isdir(absroot):
        for dirpath, dirnames, filenames in os.walk(absroot):
            dirnames[:] = [d for d in dirnames if d not in _SKIP_DIRS]
            for fn in filenames:
                if fn.endswith((".cpp", ".cc", ".c", ".h", ".hpp")):
                    rels.add(os.path.relpath(os.path.join(dirpath, fn), _REPO))
    if overrides:
        for rel in overrides:
            if rel.startswith("native/") and rel.endswith(
                (".cpp", ".cc", ".c", ".h", ".hpp")
            ):
                rels.add(rel)
    return sorted(rels)


def knob_references(overrides: Overrides = None) -> Dict[str, Tuple[str, int]]:
    """knob name -> (path, line) of its first reference site.

    Python side: AST string constants that ARE a knob name (getenv
    keys, environ subscripts, env-dict literals); prose mentions inside
    docstrings never fullmatch, so they don't count as references.  C
    side: string literals in comment-stripped source (getenv keys)."""
    refs: Dict[str, Tuple[str, int]] = {}
    for rel in _py_scan_files(overrides):
        src = _read_rel(rel, overrides)
        if src is None:
            continue
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Constant)
                and isinstance(node.value, str)
                and KNOB_FULL_RE.fullmatch(node.value)
            ):
                refs.setdefault(node.value, (rel, node.lineno))
    for rel in _c_scan_files(overrides):
        src = _read_rel(rel, overrides)
        if src is None:
            continue
        code = _cxx_strip_comments(src)
        for m in _C_KNOB_RE.finditer(code):
            refs.setdefault(m.group(1), (rel, _line_of(code, m.start())))
    return refs


def _registry_line(name: str, overrides: Overrides) -> int:
    src = _read_rel(KNOB_REGISTRY_PY, overrides)
    if src:
        for i, ln in enumerate(src.splitlines(), 1):
            if f'"{name}"' in ln:
                return i
    return 1


def rule_knob_registry(overrides: Overrides = None) -> List[Finding]:
    findings: List[Finding] = []
    refs = knob_references(overrides)
    registered = knob_registry.KNOBS
    for name, (path, line) in sorted(refs.items()):
        if name not in registered:
            findings.append(
                Finding(
                    "HBX002",
                    path,
                    line,
                    f"env knob {name} is not registered in "
                    "tools/lint/knob_registry.py — add its default, "
                    "owning layer, and A/B semantics, then regenerate "
                    "docs/KNOBS.md (python -m tools.lint --knobs-md)",
                )
            )
    for name in sorted(registered):
        if name not in refs:
            findings.append(
                Finding(
                    "HBX002",
                    KNOB_REGISTRY_PY,
                    _registry_line(name, overrides),
                    f"registered knob {name} has no os.environ/getenv "
                    "reference anywhere in the tree — retire the "
                    "registry entry (and regenerate docs/KNOBS.md) or "
                    "restore the reference",
                )
            )
    committed = _read_rel(KNOBS_MD, overrides)
    generated = knob_registry.generate_knobs_md()
    if committed is None or committed.rstrip("\n") != generated.rstrip("\n"):
        findings.append(
            Finding(
                "HBX002",
                KNOBS_MD,
                1,
                "docs/KNOBS.md is "
                + ("missing" if committed is None else "stale")
                + " vs the knob registry — regenerate with "
                "`python -m tools.lint --knobs-md > docs/KNOBS.md`",
            )
        )
    return findings


# -- HBX003: mirror obligations ----------------------------------------------

PY_MIRROR_RE = re.compile(r"#\s*mirror:\s*([A-Za-z0-9_.\-]+)")
CXX_MIRROR_RE = re.compile(r"//\s*mirror:\s*([A-Za-z0-9_.\-]+)")


def _py_mirror_files(overrides: Overrides) -> List[str]:
    rels = set()
    absroot = os.path.join(_REPO, "hbbft_tpu")
    for dirpath, dirnames, filenames in os.walk(absroot):
        dirnames[:] = [d for d in dirnames if d not in _SKIP_DIRS]
        for fn in filenames:
            if fn.endswith(".py"):
                rels.add(os.path.relpath(os.path.join(dirpath, fn), _REPO))
    if overrides:
        for rel in overrides:
            if rel.startswith("hbbft_tpu/") and rel.endswith(".py"):
                rels.add(rel)
    return sorted(rels)


def _collect_anchors(
    files: List[str], rx: re.Pattern, overrides: Overrides
) -> Dict[str, Tuple[str, int]]:
    anchors: Dict[str, Tuple[str, int]] = {}
    for rel in files:
        src = _read_rel(rel, overrides)
        if src is None:
            continue
        for i, ln in enumerate(src.splitlines(), 1):
            m = rx.search(ln)
            if m:
                anchors.setdefault(m.group(1), (rel, i))
    return anchors


def rule_mirror_obligations(overrides: Overrides = None) -> List[Finding]:
    findings: List[Finding] = []
    py = _collect_anchors(_py_mirror_files(overrides), PY_MIRROR_RE, overrides)
    cxx = _collect_anchors(_c_scan_files(overrides), CXX_MIRROR_RE, overrides)
    for key in sorted(set(py) - set(cxx)):
        path, line = py[key]
        findings.append(
            Finding(
                "HBX003",
                path,
                line,
                f'mirror anchor "{key}" has no C++ twin — add '
                f"`// mirror: {key}` at the mirrored site under "
                "native/, or remove this anchor if the obligation is "
                "gone (both halves, never one)",
            )
        )
    for key in sorted(set(cxx) - set(py)):
        path, line = cxx[key]
        findings.append(
            Finding(
                "HBX003",
                path,
                line,
                f'mirror anchor "{key}" has no Python twin — add '
                f"`# mirror: {key}` at the mirrored site under "
                "hbbft_tpu/, or remove this anchor if the obligation "
                "is gone (both halves, never one)",
            )
        )
    return findings


def lint_contracts(overrides: Overrides = None) -> List[Finding]:
    """All cross-language contract findings (HBX001-003)."""
    findings = rule_wire_parity(overrides)
    findings.extend(rule_knob_registry(overrides))
    findings.extend(rule_mirror_obligations(overrides))
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))
