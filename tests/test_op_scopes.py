"""The op-to-scope map of a compiled program (``utils/op_scopes.py``): read
from the optimised HLO of the executable that runs, packed into a dispatch
span's ``scopes`` arg and read back.

The small program compiles on the CPU in a second; the two flush programs
take minutes each and are ``slow``."""

import jax
import jax.numpy as jnp
import pytest

from hbbft_tpu.utils import op_scopes


def _small(x, p):
    with jax.named_scope("outer_a"):
        y = jax.lax.scan(lambda c, _: (c * 3 + 1, None), x, None, length=7)[0]
    with jax.named_scope("outer_b"):
        z = jax.lax.cond(p > 0, lambda v: jnp.sin(v) * 2, lambda v: v, y)
    return z + 1.5  # under no scope


def _instructions(hlo, opcode):
    return [
        line.split("=", 1)[0].split()[-1].lstrip("%")
        for line in hlo.splitlines() if f" {opcode}(" in line
    ]


def test_a_small_programs_map_names_its_loop_and_branch():
    x = jnp.ones((8, 128), jnp.float32)
    jitted = jax.jit(_small)
    jitted(x, jnp.int32(1))
    hlo = jitted.lower(x, jnp.int32(1)).compile().as_text()
    module, scopes = op_scopes.scopes_of_hlo(hlo)
    assert module == "jit__small"
    whiles, conds = _instructions(hlo, "while"), _instructions(hlo, "conditional")
    assert whiles and conds
    assert {scopes[w] for w in whiles} == {"outer_a"}
    assert {scopes[c] for c in conds} == {"outer_b"}
    # the op inside the branch resolves too; the last add is under no scope
    assert "outer_b" in {s for n, s in scopes.items() if n not in conds}
    assert set(scopes.values()) == {"outer_a", "outer_b"}
    text = op_scopes.encode(module, scopes)
    assert "," not in text and "#" not in text and "=" not in text
    assert op_scopes.decode(text) == (module, scopes)


HLO = """HloModule jit_prog, is_scheduled=true
FileNames
1 "prog.py"

%fused_computation (param_0: s32[4]) -> s32[4] {
  ROOT %mul.1 = s32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(prog)/inner/mul"}
}

%body (p.1: (s32[], s32[4])) -> (s32[], s32[4]) {
  %p.1 = (s32[], s32[4]{0}) parameter(0)
  %gte.1 = s32[4]{0} get-tuple-element(%p.1), index=1
  %mul_fusion = s32[4]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(prog)/a/while/body/mul"}
  ROOT %tuple.1 = (s32[], s32[4]{0}) tuple(%gte.0, %mul_fusion)
}

%cond (p.2: (s32[], s32[4])) -> pred[] {
  ROOT %lt.1 = pred[] compare(%gte.2, %c.1), direction=LT, metadata={op_name="jit(prog)/a/while/cond/lt"}
}

%while.7_computation (p.3: (s32[], s32[4])) -> (s32[], s32[4]) {
  ROOT %while.7 = (s32[], s32[4]{0}) while(%p.3), condition=%cond, body=%body, metadata={op_name="jit(prog)/a/while"}
}

%branch_0 (p.4: s32[4]) -> s32[4] {
  ROOT %neg.2 = s32[4]{0} negate(%p.4), metadata={op_name="jit(prog)/b/cond/branch_0_fun/neg"}
}

ENTRY %main.9 (x.1: s32[4], i.1: s32[]) -> s32[4] {
  %x.1 = s32[4]{0} parameter(0), metadata={op_name="x"}
  %call.3 = (s32[], s32[4]{0}) call(%t.1), to_apply=%while.7_computation, metadata={op_name="jit(prog)/a/while"}
  %cond.2 = s32[4]{0} conditional(%i.1, %x.1), branch_computations={%branch_0}, metadata={op_name="jit(prog)/b/cond"}
  ROOT %add.5 = s32[4]{0} add(%cond.2, %x.1), metadata={op_name="jit(prog)/add"}
}
"""


def test_the_map_reaches_what_loops_branches_and_calls_run_and_no_fusion():
    """ENTRY, then the computations a ``call`` (the CPU compiler wraps a
    small loop in one), a ``while`` and a ``conditional`` run; a fusion's
    inside runs as the fusion's one op and is left out."""
    module, scopes = op_scopes.scopes_of_hlo(HLO)
    assert module == "jit_prog"
    assert scopes == {
        "call.3": "a", "while.7": "a", "mul_fusion": "a", "lt.1": "a",
        "cond.2": "b", "neg.2": "b",
    }


@pytest.mark.parametrize(
    "stack,scope",
    [
        ("jit(hbbft_pair_3)/final_exp/pow_x_abs/while/body/closed_call/cond/"
         "branch_1_fun/closed_call/jit(_where)/select_n", "final_exp/pow_x_abs"),
        ("jit(hbbft_scan_16_16_2)/g2_stage/cond", "g2_stage"),
        ("jit(hbbft_scan_16_16_2)/g2_stage/cond/branch_1_fun/scan_g2/while",
         "g2_stage/scan_g2"),
        ("jit(f)/add", ""),
        ("g1_pts", ""),  # an argument's name
    ],
)
def test_a_name_stack_keeps_its_named_scopes_alone(stack, scope):
    assert op_scopes.scope_of(stack) == scope


def test_the_encoding_packs_numbered_families_into_runs():
    scopes = {
        "fusion.3": "a", "fusion.4": "a", "fusion.5": "a", "fusion.9": "a",
        "while": "a", "while.2": "a", "cond.1.clone": "b/c", "fusion.07": "b/c",
        "copy-start.1": "b/c", "add.0": "b/c",
    }
    text = op_scopes.encode("jit_m", scopes)
    assert text == (
        "jit_m;a:fusion. 3~5 9 while while. 2;"
        "b/c:add. 0 cond.1.clone copy-start. 1 fusion.07"
    )
    assert op_scopes.decode(text) == ("jit_m", scopes)
    assert op_scopes.decode("jit_m") == ("jit_m", {})


def test_an_op_event_names_its_instruction():
    name = "%while.2 = (s32[], f32[8,128]{1,0}) while((s32[]) %tuple.22), condition=%c"
    assert op_scopes.op_name(name) == "while.2"
    assert op_scopes.op_name("fusion.7") == "fusion.7"


def _flush_programs():
    """The coin cells' two programs, each with arguments it runs on."""
    import random

    from hbbft_tpu.crypto.backend import VerifyRequest
    from hbbft_tpu.crypto.bls.suite import BLSSuite
    from hbbft_tpu.crypto.keys import SecretKeySet
    from hbbft_tpu.crypto.tpu import backend as B
    from hbbft_tpu.crypto.tpu import curve as dcurve

    suite = BLSSuite()
    sks = SecretKeySet.random(2, random.Random(7), suite)
    pks = sks.public_keys()
    reqs = [
        VerifyRequest.sig_share(
            pks.public_key_share(i), b"doc", sks.secret_key_share(i).sign(b"doc")
        )
        for i in range(16)
    ]
    (n1, n2, nl), args, _ = B.TpuBackend(suite)._scan_prep(reqs)
    scan = B._scan_kernel(n1, n2, nl)
    _, lhs, gen_leg = scan(*args)
    pairs = B._join_kernel(3)([lhs], [gen_leg], [dcurve.identity(dcurve.G2_OPS, (nl,))])
    return [(scan, args), (B._pair_kernel(3), pairs)]


@pytest.fixture
def cache_off():
    """Compile afresh: the persistent cache's key leaves scopes out, so an
    entry an older commit wrote would bring that commit's scopes."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.mark.slow
def test_the_flush_programs_maps_hold_their_stages(cache_off):
    """``_scan_kernel(16,16,2)`` and ``_pair_kernel(3)`` compiled on the CPU
    (minutes each): every ``while`` and ``conditional`` resolves to a scope,
    and the map is what the dispatch span would carry."""
    from hbbft_tpu.crypto.tpu import backend as B

    found = set()
    for kernel, args in _flush_programs():
        kernel(*args)
        hlo = kernel.lower(*args).compile().as_text()
        module, scopes = op_scopes.scopes_of_hlo(hlo)
        for name in _instructions(hlo, "while") + _instructions(hlo, "conditional"):
            assert scopes.get(name), (module, name)
        assert op_scopes.decode(B._program_scopes(kernel, args)) == (module, scopes)
        found |= set(scopes.values())
    assert {"miller_loop", "final_exp/pow_x_abs", "scan_g1", "g2_stage"} <= found
