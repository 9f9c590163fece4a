"""Direct unit tests for the ``utils.hlo_graph`` parser — the parsing core
under the lint engine. The overlap test exercises it end-to-end; these pin
the grammar corners on their own: ``control-predecessors``,
``branch_computations``, multi-computation ``calls``, the two surface
syntaxes (``%``-prefixed dump format vs the bare-name
``compiler_ir("hlo")`` format), ``} // name`` computation closers, and the
result-type capture the memory rule depends on."""

import pytest

from mpi_knn_tpu.utils.hlo_graph import backward_slice, parse_hlo

_BRANCHY = """\
HloModule branchy, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%big.1 (bp.1: f32[8]) -> f32[8] {
  %bp.1 = f32[8]{0} parameter(0)
  ROOT %bd.1 = f32[8]{0} multiply(%bp.1, %bp.1)
}

%small.1 (sp.1: f32[8]) -> f32[8] {
  %sp.1 = f32[8]{0} parameter(0)
  ROOT %sd.1 = f32[8]{0} add(%sp.1, %sp.1)
}

%helper.1 (hp.1: f32[8]) -> f32[8] {
  %hp.1 = f32[8]{0} parameter(0)
  ROOT %hr.1 = f32[8]{0} negate(%hp.1)
}

ENTRY %main.1 (a.1: f32[8], i.1: s32[]) -> f32[8] {
  %a.1 = f32[8]{0} parameter(0)
  %i.1 = s32[] parameter(1)
  %c.1 = f32[8]{0} conditional(%i.1, %a.1, %a.1), branch_computations={%big.1, %small.1}
  %cc.1 = f32[8]{0} custom-call(%c.1), custom_call_target="fake", called_computations={%helper.1, %big.1}
  ROOT %r.1 = f32[8]{0} add(%c.1, %cc.1)
}
"""


def test_branch_computations_and_called_computations_sets():
    """Both set-valued attribute forms create call edges: a conditional's
    ``branch_computations`` and a custom-call's ``called_computations``
    (each possibly multi-computation)."""
    m = parse_hlo(_BRANCHY)
    assert set(m.computations) == {"big.1", "small.1", "helper.1", "main.1"}
    cond = m.instr("main.1", "c.1")
    assert cond.called == ["big.1", "small.1"]
    cc = m.instr("main.1", "cc.1")
    assert cc.called == ["helper.1", "big.1"]
    # the slice of the root reaches through BOTH branches and the helper
    sl = backward_slice(m, "main.1", "r.1")
    comps = {c for c, _ in sl}
    assert {"big.1", "small.1", "helper.1"} <= comps


def test_control_predecessors_parse_and_count_as_edges():
    mod = """\
HloModule ctrl, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

ENTRY %e.1 (p.1: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0)
  %x.1 = f32[4]{0} multiply(%p.1, %p.1)
  %y.1 = f32[4]{0} add(%p.1, %p.1), control-predecessors={%x.1}
  ROOT %r.1 = f32[4]{0} negate(%y.1)
}
"""
    m = parse_hlo(mod)
    y = m.instr("e.1", "y.1")
    assert y.controls == ["x.1"]
    assert ("e.1", "x.1") in backward_slice(m, "e.1", "y.1")


_BARE = """\
HloModule bare, entry_computation_layout={(f32[4,8]{1,0})->f32[4,4]{1,0}}

region_0.1 {
  Arg_0.2 = f32[4,8]{1,0} parameter(0)
  transpose.3 = f32[8,4]{0,1} transpose(Arg_0.2), dimensions={1,0}
  ROOT dot.4 = f32[4,4]{1,0} dot(Arg_0.2, transpose.3), lhs_contracting_dims={1}, rhs_contracting_dims={0}
} // region_0.1

ENTRY main.5 {
  a.6 = f32[4,8]{1,0} parameter(0)
  call.7 = f32[4,4]{1,0} call(a.6), to_apply=region_0.1
  constant.8 = f32[] constant(1)
  broadcast.9 = f32[4,4]{1,0} broadcast(constant.8), dimensions={}
  ROOT add.10 = f32[4,4]{1,0} add(call.7, broadcast.9)
}
"""


def test_bare_name_format_and_comment_closers():
    """The ``compiler_ir("hlo")`` surface syntax: no ``%`` prefixes, headers
    without parameter lists, computations closed by ``} // name``. The old
    parser silently swallowed everything after the first commented closer —
    which is how a whole dump once reported zero collective-permutes."""
    m = parse_hlo(_BARE)
    assert set(m.computations) == {"region_0.1", "main.5"}
    assert m.computations["main.5"].is_entry
    call = m.instr("main.5", "call.7")
    assert call.operands == ["a.6"]
    assert call.called == ["region_0.1"]
    # literal operands (constant(1), parameter(0)) must not become edges
    assert m.instr("main.5", "constant.8").operands == []
    sl = backward_slice(m, "main.5", "add.10")
    assert ("region_0.1", "dot.4") in sl


def test_result_types_captured_for_shape_accounting():
    m = parse_hlo(_BARE)
    assert m.instr("main.5", "a.6").type_str == "f32[4,8]{1,0}"
    assert m.instr("main.5", "constant.8").type_str == "f32[]"
    mt = parse_hlo(
        """\
HloModule t, entry_computation_layout={(f32[2]{0})->(f32[2]{0}, s32[2]{0})}

ENTRY %e.1 (p.1: f32[2]) -> (f32[2], s32[2]) {
  %p.1 = f32[2]{0} parameter(0)
  %i.1 = s32[2]{0} convert(%p.1)
  ROOT %t.1 = (f32[2]{0}, s32[2]{0}) tuple(%p.1, %i.1)
}
"""
    )
    assert mt.instr("e.1", "t.1").type_str == "(f32[2]{0}, s32[2]{0})"


_COND_ARGS = """\
HloModule condargs, entry_computation_layout={(f32[4,4]{1,0}, pred[])->f32[4,4]{1,0}}

%b0.1 (p0.1: f32[4,4]) -> f32[4,4] {
  %p0.1 = f32[4,4]{1,0} parameter(0)
  ROOT %cp.1 = f32[4,4]{1,0} collective-permute(%p0.1), source_target_pairs={{0,1},{1,0}}
}

%b1.1 (p1.1: f32[4,4]) -> f32[4,4] {
  %p1.1 = f32[4,4]{1,0} parameter(0)
  ROOT %neg.1 = f32[4,4]{1,0} negate(%p1.1)
}

ENTRY %main.1 (a.1: f32[4,4], pr.1: pred[]) -> f32[4,4] {
  %a.1 = f32[4,4]{1,0} parameter(0)
  %pr.1 = pred[] parameter(1)
  %d.1 = f32[4,4]{1,0} dot(%a.1, %a.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %e.1 = f32[4,4]{1,0} negate(%a.1)
  ROOT %c.1 = f32[4,4]{1,0} conditional(%pr.1, %d.1, %e.1), branch_computations={%b0.1, %b1.1}
}
"""


def test_conditional_branch_parameter_maps_to_branch_operand():
    """Regression (ADVICE r5): a conditional's operand 0 is the predicate;
    branch b's parameter(0) is call-site operand b+1. The old mapping sent
    parameter(0) to operand 0, so a collective-permute inside a branch
    whose argument derives from a dot was falsely certified
    compute-independent — an under-approximation, the one direction the
    module's soundness contract forbids."""
    m = parse_hlo(_COND_ARGS)
    sl0 = backward_slice(m, "b0.1", "cp.1")
    # branch 0's argument is %d.1 (the dot) — the permute DOES depend on it
    assert ("main.1", "d.1") in sl0
    # ...and the mapping is precise: branch 1's argument is not dragged in
    assert ("main.1", "e.1") not in sl0
    # the predicate is a scheduling edge for everything inside a branch
    # (the branch cannot issue before the branch index is known)
    assert ("main.1", "pr.1") in sl0
    # branch 1 symmetrically sees only its own argument
    sl1 = backward_slice(m, "b1.1", "neg.1")
    assert ("main.1", "e.1") in sl1
    assert ("main.1", "d.1") not in sl1


def test_multi_computation_calls_share_one_callee():
    """Two call sites into the same computation: a parameter must continue
    at BOTH call sites (the conservative over-approximation documented in
    the module docstring)."""
    mod = """\
HloModule twocalls, entry_computation_layout={(f32[4]{0}, f32[4]{0})->f32[4]{0}}

%inner.1 (p.1: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0)
  ROOT %d.1 = f32[4]{0} multiply(%p.1, %p.1)
}

ENTRY %main.1 (a.1: f32[4], b.1: f32[4]) -> f32[4] {
  %a.1 = f32[4]{0} parameter(0)
  %b.1 = f32[4]{0} parameter(1)
  %c1.1 = f32[4]{0} call(%a.1), to_apply=%inner.1
  %c2.1 = f32[4]{0} call(%b.1), to_apply=%inner.1
  ROOT %r.1 = f32[4]{0} add(%c1.1, %c2.1)
}
"""
    m = parse_hlo(mod)
    # slicing from inside the callee reaches both callers' operands
    sl = backward_slice(m, "inner.1", "d.1")
    names = {n for _, n in sl}
    assert {"a.1", "b.1"} <= names


# --- the serial chunk program's per-tile selection, as XLA compiles it -----


def _chunk_program(k, q=64, c=8192, dim=16, tiles=3, **knn):
    """Optimized HLO of ``knn_chunk_update`` at one (q, c) tile shape."""
    import jax
    import jax.numpy as jnp

    from mpi_knn_tpu.backends.serial import knn_chunk_update
    from mpi_knn_tpu.config import KNNConfig

    s = jax.ShapeDtypeStruct
    cfg = KNNConfig(k=k, query_tile=q, corpus_tile=c, **knn)
    return knn_chunk_update.lower(
        s((1, q, dim), jnp.float32), s((1, q), jnp.int32),
        s((tiles, c, dim), jnp.float32), s((tiles, c), jnp.int32),
        s((1, q, k), jnp.float32), s((1, q, k), jnp.int32), cfg=cfg,
    ).compile().as_text()


def _scoped_ops(text, scope=""):
    """(scope path, opcode, result type, operand types) of every instruction
    whose op name holds ``scope``."""
    import re

    out = []
    for comp in parse_hlo(text).computations.values():
        for ins in comp.instructions.values():
            m = re.search(r'op_name="([^"]*)"', ins.attrs)
            if m and scope in m.group(1):
                operands = " ".join(
                    comp.instructions[o].type_str
                    for o in ins.operands if o in comp.instructions
                )
                out.append((m.group(1), ins.opcode, ins.type_str, operands))
    return out


def _wide_sorts(ops, c=8192):
    """The scopes of the sorts / top-ks over rows at least ``c`` wide."""
    import re

    return [
        s for s, op, _, operands in ops
        if (op == "sort" or "top_k" in s) and any(
            int(w) >= c for w in re.findall(r"\[\d+,(\d+)\]", operands))
    ]


def test_engaged_chunk_program_finishes_once_a_query_tile():
    """k = 10 over 8192 columns: the lists ride the scan (ISSUE 33). The
    scan body holds *bins* and no *finish*; the program holds no (T, q, k)
    survivor stack and no s32[q, c] id plane; the one sort over a c-wide
    row is the re-scan's, over a handful of rows under
    ``knn.select/fallback``; and the three scopes a trace reads are in the
    op names."""
    text = _chunk_program(k=10)
    ops = _scoped_ops(text)
    for want in ("knn.select/bins", "knn.select/finish", "knn.select/fallback"):
        assert any(want in s for s, *_ in ops), want

    def loops_around(scope):
        return {s[:s.index(scope)].count("while/body")
                for s, *_ in ops if scope in s}

    # finish sits outside the loop that holds bins: after the scan
    assert max(loops_around("knn.select/finish")) < min(
        loops_around("knn.select/bins"))
    assert "[3,64,10]" not in text  # the survivors of three tiles
    sorts = _wide_sorts(ops)
    assert sorts and all("knn.select/fallback" in s for s in sorts), sorts
    assert all("[64," not in operands for s, _, _, operands in ops
               if "knn.select/fallback" in s and "top_k" in s)
    planes = [(s, op) for s, op, ty, _ in ops
              if "s32[64,8192]" in ty and "knn.select" in s
              and "knn.select/fallback" not in s]
    assert not planes, planes


@pytest.mark.parametrize("knn", [
    dict(k=256), dict(k=10, precision_policy="mixed"),
    dict(k=10, merge_schedule="stream"), dict(k=10, topk_method="block"),
], ids=lambda knn: ",".join(f"{a}={b}" for a, b in knn.items()))
def test_programs_the_rule_does_not_engage_keep_the_per_tile_form(knn):
    """k beyond the rule, ``mixed``, ``stream`` and another method: no
    lists, no finish, no re-scan — the selection each always ran (their
    lowered programs are the parent's byte for byte:
    ``scripts/lowered_hashes.py``, CHANGES.md PR 33)."""
    from mpi_knn_tpu.backends.serial import carried_depth
    from mpi_knn_tpu.config import KNNConfig

    assert carried_depth(KNNConfig(**knn), 64, 8192) is None
    text = _chunk_program(**knn)
    ops = _scoped_ops(text)
    assert not any(
        w in s for s, *_ in ops for w in ("/bins", "/finish", "/fallback"))
    if knn.get("merge_schedule") != "stream":
        assert f"[3,64,{knn['k']}]" in text  # twolevel's survivor stack
    if knn["k"] == 256:
        assert any("knn.select/top_k" in s and "[64,8192]" in operands
                   for s, _, _, operands in ops)
