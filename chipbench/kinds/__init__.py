"""Request kinds: one module per kind, found by the kind's name.

A flush says the kind of each of its requests (``Flush.kinds``); the harness
judges the request and counts its work through ``chipbench.kinds.<kind>``:

* ``verify(reference, *wire) -> bool``: the plain verdict on one request
  from its wire form, on a :class:`chipbench.reference.verify.Reference`;
* ``SCAN_FQ_MULS``: the base-field products of the request's scalar
  multiplications and subgroup checks in the batch equation
  (chipbench/harness/work.py has the costs and the equation);
* ``pairs(*wire)``: the second arguments of the pairings the request takes
  part in, as keys; a flush needs one Miller loop per distinct key;
* ``sent(*wire) -> (own, once)``: the bytes of the request that are its own,
  and the byte strings it shares with others of its flush, which the least
  work sends once;
* ``wire_of(request)``: the wire form of the program's request object, by
  its ``to_bytes`` alone (the control and the tests put the reference in the
  program's place with it).

A module imports nothing of the program.  A further kind is a further file.
"""

import importlib
from types import ModuleType


def load(kind: str) -> ModuleType:
    name = "chipbench.kinds." + kind
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise  # the kind's module is there; something it imports is not
        raise KeyError(
            f"no verifier and no work formula for request kind {kind!r}: "
            f"chipbench/kinds/{kind}.py is not there"
        ) from None
