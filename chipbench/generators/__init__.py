"""Traffic generators: one module per generator, found by the name a traffic
file gives (``make_keys``, ``make_flush``; chipbench/README.md)."""

from typing import Any, List, NamedTuple, Tuple


class Flush(NamedTuple):
    """One ``verify_batch`` call: the requests, the verdicts the
    construction expects, and for the plain reference each request's kind
    (a module of ``chipbench/kinds``) and wire form (the byte strings that
    kind's ``verify`` takes).  What requests share, a document or a
    ciphertext, is the same bytes in their wire forms; the work formulas
    find it there."""

    requests: List[Any]
    expected: List[bool]
    wire: List[Tuple[bytes, ...]]
    kinds: List[str]
