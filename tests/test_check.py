"""repro.check: static analyzer facts, rules R1-R5, waivers, CLI, and the
kernel.* registry bridge (touch streams cross-checked against hlo_cost)."""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fixtures.bad_kernels import FIXTURES, waived_tile
from repro.check import catalog, cli
from repro.check.facts import trace_kernel
from repro.check.rules import RULES, run_rules
from repro.core import copa
from repro.core.hlo_cost import analyze_hlo_cost
from repro.core.sweep import SweepEngine
from repro.kernels import ref
from repro.workloads import registry

S = jax.ShapeDtypeStruct


# --- facts extraction ---------------------------------------------------------

def test_facts_flash_attention_structure():
    facts, = catalog.trace_case("flash_attention.b2s512")
    assert facts.kernel == "_attn_kernel"
    assert facts.src_file.endswith("flash_attention.py")
    assert facts.grid == (8, 2, 2)
    assert [b.memory_space for b in facts.blocks] == ["vmem"] * 4
    # q block is refetched only when (bh, qi) changes; k/v every step
    q, k, v = facts.inputs
    assert int(q.fetch_mask().sum()) == 8 * 2
    assert int(k.fetch_mask().sum()) == facts.n_steps
    # the output store lives inside pl.when (the guarded finalize idiom)
    out, = facts.outputs
    assert (out.unguarded_stores, out.guarded_stores) == (0, 1)
    # both dots accumulate f32 with preferred_element_type set
    assert all(d.out_dtype == "float32" and
               d.preferred_element_type == "float32" for d in facts.dots)


def test_facts_flash_decode_smem_and_bwd_dual_grids():
    facts, = catalog.trace_case("flash_decode.b2s2048")
    assert facts.inputs[0].memory_space == "smem"     # the kv_len scalar
    assert facts.inputs[0].block_bytes == 4           # (1,) int32
    dq, dkv = catalog.trace_case("flash_attention_bwd.b2s512")
    assert dq.grid == (8, 2, 2) and dkv.grid == (8, 2, 2)
    # dq sweeps kv innermost, dkv sweeps q innermost: outputs revisit only
    # contiguously and every store is guarded (the R3 audit)
    for facts in (dq, dkv):
        for out in facts.outputs:
            assert out.unguarded_stores == 0
            assert out.guarded_stores >= 1


# --- rules on the deliberately-broken fixtures --------------------------------

@pytest.mark.parametrize("rule", list(FIXTURES))
def test_fixture_triggers_exactly_its_rule(rule):
    fn, avals = FIXTURES[rule]
    facts = trace_kernel(fn, *avals, case=f"fixture.{rule}")
    findings = run_rules(facts, waivers=False)
    assert [f.rule for f in findings] == [rule], \
        [f.format() for f in findings]
    assert findings[0].file.endswith("bad_kernels.py")
    assert findings[0].line > 0


def test_unknown_rule_rejected():
    fn, avals = FIXTURES["R1"]
    facts = trace_kernel(fn, *avals)
    with pytest.raises(ValueError, match="unknown rules"):
        run_rules(facts, rules=["R9"])


# --- the shipped kernels audit clean (the CI gate, as a test) -----------------

def test_shipped_kernels_have_no_unwaived_findings():
    findings = run_rules(catalog.trace_all())
    unwaived = [f for f in findings if not f.waived]
    assert unwaived == [], [f.format() for f in unwaived]


def test_ssd_row_slab_finding_is_waived_not_fixed():
    """ssd_scan's dt once streamed as (1, chunk) row slabs, which R1 flagged
    and the v5e compiler refused. dt now enters as an aligned column block,
    so R1 finds nothing with waivers off, at the small and the real width."""
    for case in ("ssd_scan.b2s1024", "ssd_scan.mamba2_s2048"):
        facts = list(catalog.trace_case(case))
        assert run_rules(facts, waivers=False) == []


def test_waiver_comment_marks_its_rule_waived():
    _, avals = FIXTURES["R1"]
    raw = run_rules(trace_kernel(waived_tile, *avals), waivers=False)
    assert [(f.rule, f.waived) for f in raw] == [("R1", False)]
    waived = run_rules(trace_kernel(waived_tile, *avals))
    assert [(f.rule, f.waived) for f in waived] == [("R1", True)]
    assert waived[0].file.endswith("bad_kernels.py")


# --- CLI ----------------------------------------------------------------------

def test_cli_exits_zero_on_shipped_kernels(capsys):
    assert cli.main([]) == 0
    out = capsys.readouterr().out
    assert "0 finding(s)" in out and "0 waived" in out


def test_cli_json_rules_filter_and_waiver_toggle(capsys):
    assert cli.main(["--no-waivers", "--cases", "ssd_scan"]) == 0
    capsys.readouterr()
    assert cli.main(["--no-waivers", "--rules", "R1,R5"]) == 0
    capsys.readouterr()
    assert cli.main(["--no-waivers", "--json"]) == 0
    assert json.loads(capsys.readouterr().out) == []
    assert cli.main(["--cases", "no_such_kernel"]) == 2
    capsys.readouterr()
    assert cli.main(["--list"]) == 0
    out = capsys.readouterr().out
    for rule in RULES:
        assert rule in out
    for name in catalog.case_names():
        assert name in out


# --- kernel.* registry streams vs hlo_cost ------------------------------------

def _hlo(f, *avals):
    return analyze_hlo_cost(jax.jit(f).lower(*avals).compile().as_text())


_REF_CASES = {
    "kernel.flash_attention.b2s512": lambda: _hlo(
        functools.partial(ref.flash_attention_ref, causal=True),
        S((2, 512, 8, 128), jnp.bfloat16), S((2, 512, 4, 128), jnp.bfloat16),
        S((2, 512, 4, 128), jnp.bfloat16)),
    "kernel.flash_decode.b2s2048": lambda: _hlo(
        functools.partial(ref.flash_decode_ref, kv_len=2048),
        S((2, 8, 128), jnp.bfloat16), S((2, 2048, 4, 128), jnp.bfloat16),
        S((2, 2048, 4, 128), jnp.bfloat16)),
    "kernel.fused_ffn.t512d1024": lambda: _hlo(
        ref.fused_ffn_ref,
        S((512, 1024), jnp.bfloat16), S((1024, 2048), jnp.bfloat16),
        S((1024, 2048), jnp.bfloat16), S((2048, 1024), jnp.bfloat16)),
    "kernel.ssd_scan.b2s1024": lambda: _hlo(
        ref.ssd_chunk_ref,
        S((2, 1024, 4, 64), jnp.bfloat16), S((2, 1024, 4), jnp.bfloat16),
        S((4,), jnp.float32), S((2, 1024, 128), jnp.bfloat16),
        S((2, 1024, 128), jnp.bfloat16)),
}


@pytest.mark.parametrize("name", list(_REF_CASES))
def test_kernel_stream_matches_hlo_cost(name):
    """Byte/flop cross-check of the compiled touch streams against the
    reference computation's HLO cost: the stream's unique footprint is the
    kernel's exact HBM floor (the arrays it must move once), the HLO of
    the UNFUSED reference accesses strictly more (the traffic the kernel
    filters on package — the paper's Fig-4 reuse band), and dot flops
    agree exactly for the attention/FFN kernels."""
    tr = registry.scenario(name)
    cost = _REF_CASES[name]()
    case = catalog.get(name.removeprefix("kernel."))
    io_bytes = 0
    for facts in catalog.trace_case(case.name):
        io_bytes += sum(b.array_bytes for b in facts.blocks)
    assert tr.footprint_bytes() == io_bytes
    assert tr.footprint_bytes() <= tr.total_touch_bytes
    assert cost.bytes_accessed >= 2 * tr.footprint_bytes()
    if "ssd_scan" in name:
        # the chunked dual form trades flops for locality vs the
        # token-recurrence oracle (5x at these shapes)
        assert 1.0 <= tr.total_flops / cost.dot_flops <= 8.0
    else:
        assert tr.total_flops == pytest.approx(cost.dot_flops, rel=0.01)


def test_kernel_scenarios_sweep_through_suite_analysis():
    names = registry.match("kernel.*")
    assert len(names) >= 4
    specs = [copa.GPU_N_BASE.build(), copa.HBM_L3.build()]
    sa = registry.suite_analysis("kernel")
    times = sa.time_batch(specs)
    assert times.shape == (2, len(names))
    assert np.all(times > 0) and np.all(np.isfinite(times))
    grid = SweepEngine(["kernel.*"], configs=[copa.GPU_N_BASE,
                                             copa.HBM_L3]).run()
    assert len(grid.rows) == 2 * len(names)
    decode = grid.result("kernel.flash_decode.b2s2048", "GPU-N")
    assert decode.time_s > 0
