"""The shape rule (``hbbft_tpu/crypto/flush_shapes.py``), without jax: a flush
decides ``(n1, n2, legs)`` once, from its own rows (``scan_shape``), and every
group a halving bisection makes of it is prepared in that triple
(``group_shape``), so a flush of any size and its fault isolation run in ONE
scan program and one pair program.  ``tests/test_spans.py`` holds
``TpuBackend`` to the same answers on stubbed kernels."""

from types import SimpleNamespace

import pytest

from hbbft_tpu.crypto import flush_shapes

#: (G1 rows, G2 rows, legs on one document or ciphertext) a request brings
ROWS = {"sig_share": (1, 1, 1), "dec_share": (2, 0, 2), "ciphertext": (1, 1, 1)}

# name -> (the flush's kinds, its program, the programs its groups had up to PR 34)
FLUSHES = {
    "sig_16": (["sig_share"] * 16, (16, 16, 2), {(16, 16, 2)}),
    "dec_15": (["dec_share"] * 15, (32, 16, 2), {(32, 16, 2)}),
    "check_and_15": (["ciphertext"] + ["dec_share"] * 15, (32, 16, 2), {(32, 16, 2)}),
    "dec_17": (["dec_share"] * 17, (64, 16, 2), {(32, 16, 2)}),
    "dec_103": (
        ["dec_share"] * 103, (256, 16, 2), {(128, 16, 2), (64, 16, 2), (32, 16, 2)},
    ),
    "check_and_103": (
        ["ciphertext"] + ["dec_share"] * 103, (256, 16, 2),
        {(128, 16, 2), (64, 16, 2), (32, 16, 2)},
    ),
    "sig_2048": (
        ["sig_share"] * 2048, (2048, 2048, 2),
        {(2 ** k, 2 ** k, 2) for k in range(4, 11)},
    ),
}


def own_shape(kinds):
    """``scan_shape`` on the rows these requests bring, as ``_scan_prep``
    counts them from ``_build_legs``'s entries."""
    return flush_shapes.scan_shape(
        [SimpleNamespace(kind=k) for k in kinds],
        sum(ROWS[k][0] for k in kinds),
        sum(ROWS[k][1] for k in kinds),
        max(ROWS[k][2] for k in kinds),
    )


def halves(kinds):
    """Every group ``TpuBackend._bisect`` can cut: ``g[: len(g) // 2]`` and
    the rest, down to groups of one."""
    if len(kinds) < 2:
        return []
    low, high = kinds[: len(kinds) // 2], kinds[len(kinds) // 2 :]
    return [low, high] + halves(low) + halves(high)


@pytest.mark.parametrize("case", sorted(FLUSHES))
def test_every_group_of_a_flush_lands_in_the_flushs_own_program(case):
    kinds, program, before = FLUSHES[case]
    chunk = own_shape(kinds)
    assert chunk == program
    groups = halves(kinds)
    assert len(groups) == 2 * len(kinds) - 2
    assert min(len(g) for g in groups) == 1  # a lone share, the lone check
    if "ciphertext" in kinds:
        assert ["ciphertext"] in groups
    assert {flush_shapes.group_shape(chunk, own_shape(g)) for g in groups} == {program}
    # what the groups' own rows would ask for: the programs of PR 34's rule
    assert {own_shape(g) for g in groups} - {program} == before - {program}
    assert flush_shapes.pairs_bucket(1 + program[2]) == 3


def test_a_shape_that_does_not_hold_its_group_is_refused():
    assert flush_shapes.group_shape((256, 16, 2), (32, 16, 2)) == (256, 16, 2)
    assert flush_shapes.group_shape((32, 16, 2), (32, 16, 2)) == (32, 16, 2)
    # legs are handed down too: a group on one of a flush's four documents
    assert flush_shapes.group_shape((64, 64, 4), (16, 16, 2)) == (64, 64, 4)
    for own in [(64, 16, 2), (32, 32, 2), (32, 16, 4)]:
        with pytest.raises(ValueError, match="was cut from a chunk"):
            flush_shapes.group_shape((32, 16, 2), own)
