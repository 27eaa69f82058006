"""Which ``jax.named_scope`` each device op of a compiled program runs under.

The optimised HLO of a compiled program names every instruction and keeps,
in its ``metadata={op_name="..."}``, the name stack the op was traced
under: ``jit(hbbft_pair_3)/final_exp/pow_x_abs/while/body/closed_call/mul``.
:func:`scopes_of_hlo` reads that text into the module's name and
``{instruction: scope path}`` (here ``final_exp/pow_x_abs``) for the
instructions a device trace's ``XLA Ops`` line can show: those of the ENTRY
computation and of every computation a ``while``, a ``conditional`` or a
``call`` runs, not those inside a fusion, which runs as one op.  An
instruction whose name stack holds no scope is left out.  :func:`encode`
packs the map into one line that holds no comma (a profiler annotation's
args split there) and :func:`decode` reads it back; :func:`op_name` is the
instruction that an op event of a device trace stands for.  Scopes are
plain identifiers.

No jax here: a trace reduction imports this module to read what the program
wrote into its trace.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Tuple

# Segments of a name stack that a control-flow primitive or a call adds;
# a transformation's segment holds parentheses (``jit(f)``, ``vmap(g)``).
_STRUCTURE = frozenset({
    "while", "body", "cond", "closed_call", "pjit", "checkpoint", "remat",
    "custom_jvp_call", "custom_vjp_call",
})
_BRANCH = re.compile(r"branch_\d+(_fun)?$")
_IDENT = re.compile(r"[A-Za-z_]\w*$")

_INSTRUCTION = re.compile(r"\s+(?:ROOT\s+)?%?([^\s=]+)\s=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(?:condition|body|true_computation|false_computation)=%?([^\s,}]+)"
)
_CALL = re.compile(r"\scall\(.*\bto_apply=%?([^\s,}]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_NUMBERED = re.compile(r"(.*\.)([1-9]\d*|0)$")


def scope_of(op_name: str) -> str:
    """The ``named_scope`` path of an op's name stack: the last segment (the
    primitive) dropped, and with it every segment that is not a plain
    identifier or that control flow adds."""
    return "/".join(
        s for s in op_name.split("/")[:-1]
        if _IDENT.match(s) and s not in _STRUCTURE and not _BRANCH.match(s)
    )


def scopes_of_hlo(hlo: str) -> Tuple[str, Dict[str, str]]:
    """(module name, ``{instruction: scope path}``) of one optimised HLO
    module's text, as ``Compiled.as_text()`` prints it.  One pass finds the
    computations and what each one's loops, branches and calls run; only
    the lines of those reached from ENTRY are read for their metadata."""
    lines = hlo.splitlines()
    module = entry = ""
    current = None
    bounds: Dict[str, Tuple[int, int]] = {}  # computation -> its lines
    calls: Dict[str, List[str]] = {}
    for i, line in enumerate(lines):
        if line.endswith("{") and not line[:1].isspace():
            head = line.split()
            if head[0] == "ENTRY":
                head = head[1:]
                entry = head[0].lstrip("%")
            current, first = head[0].lstrip("%"), i + 1
            calls[current] = []
        elif line.startswith("}"):
            if current is not None:
                bounds[current] = (first, i)
            current = None
        elif current is not None and (
            " while(" in line or " conditional(" in line or " call(" in line
        ):
            calls[current] += _CALLED.findall(line) + _CALL.findall(line)
            for group in _BRANCHES.findall(line):
                calls[current] += [c.strip().lstrip("%") for c in group.split(",")]
        elif line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
    out: Dict[str, str] = {}
    seen = set()
    todo = [entry] if entry else []
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in bounds:
            continue
        seen.add(comp)
        first, last = bounds[comp]
        for line in lines[first:last]:
            name = _INSTRUCTION.match(line)
            stack = name and _OP_NAME.search(line)
            if stack:
                scope = scope_of(stack.group(1))
                if scope:
                    out[name.group(1)] = scope
        todo += calls[comp]
    return module, out


def _ranges(numbers: Iterable[int]) -> List[str]:
    out: List[str] = []
    run: List[int] = []
    for n in sorted(numbers) + [None]:
        if run and (n is None or n != run[-1] + 1):
            out.append(str(run[0]) if len(run) == 1 else f"{run[0]}~{run[-1]}")
            run = []
        if n is not None:
            run.append(n)
    return out


def encode(module: str, scopes: Dict[str, str]) -> str:
    """``<module>;<scope>:<names>;...``: per scope its instructions, a
    numbered family as ``<base>.`` followed by its numbers, runs as
    ``<first>~<last>`` (``final_exp/pow_x_abs:fusion. 40~212 310 while. 5~9``),
    any other name whole."""
    by_scope: Dict[str, Dict[str, List[int]]] = {}
    for name, scope in scopes.items():
        m = _NUMBERED.match(name)
        base, n = (m.group(1), int(m.group(2))) if m else (name, None)
        numbers = by_scope.setdefault(scope, {}).setdefault(base, [])
        if n is not None:
            numbers.append(n)
    groups = [module]
    for scope in sorted(by_scope):
        tokens: List[str] = []
        for base, numbers in sorted(by_scope[scope].items()):
            tokens += [base] + _ranges(numbers) if numbers else [base]
        groups.append(f"{scope}:{' '.join(tokens)}")
    return ";".join(groups)


def decode(text: str) -> Tuple[str, Dict[str, str]]:
    """:func:`encode`'s (module, ``{instruction: scope path}``) back."""
    module, *groups = text.split(";")
    out: Dict[str, str] = {}
    for group in groups:
        scope, _, names = group.partition(":")
        base = ""
        for token in names.split():
            if token[0].isdigit():
                first, _, last = token.partition("~")
                for n in range(int(first), int(last or first) + 1):
                    out[f"{base}{n}"] = scope
            elif token.endswith("."):
                base = token
            else:
                out[token] = scope
    return module, out


def op_name(event_name: str) -> str:
    """The instruction an op event of a device trace stands for: its name
    is the instruction's HLO line, or starts with the instruction's name."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")
