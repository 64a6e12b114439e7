"""Trip-count-expanded HLO cost analysis (the §Roofline accounting)."""
import os
import subprocess
import sys
import textwrap


from repro.core.hlo_cost import analyze_hlo_cost


def test_synthetic_while_trip_expansion():
    hlo = """
%body.1 (p: (s32[], f32[64,64])) -> (s32[], f32[64,64]) {
  %p = (s32[], f32[64,64]{1,0}) parameter(0)
  %a = f32[64,64]{1,0} get-tuple-element(%p), index=1
  %d = f32[64,64]{1,0} dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %i = s32[] constant(1)
  ROOT %t = (s32[], f32[64,64]{1,0}) tuple(%i, %d)
}

%cond.1 (p2: (s32[], f32[64,64])) -> pred[] {
  %p2 = (s32[], f32[64,64]{1,0}) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main.1 (x: f32[64,64]) -> f32[64,64] {
  %x = f32[64,64]{1,0} parameter(0)
  %c = s32[] constant(0)
  %tup = (s32[], f32[64,64]{1,0}) tuple(%c, %x)
  %w = (s32[], f32[64,64]{1,0}) while(%tup), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %out = f32[64,64]{1,0} get-tuple-element(%w), index=1
}
"""
    c = analyze_hlo_cost(hlo)
    assert c.dot_flops == 5 * 2 * 64 ** 3


def test_scan_flops_counted_fully():
    """End-to-end: compile a 7-trip scan of a 128^3 matmul in a subprocess
    and verify the analyzer recovers all 7 trips (raw cost_analysis: 1)."""
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp, sys
        sys.path.insert(0, "src")
        from repro.core.hlo_cost import analyze_hlo_cost
        def f(x, w):
            def body(c, _):
                return jnp.tanh(c @ w), None
            return jax.lax.scan(body, x, None, length=7)[0].sum()
        x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        comp = jax.jit(f).lower(x, w).compile()
        c = analyze_hlo_cost(comp.as_text())
        raw = comp.cost_analysis()["flops"]
        assert abs(c.dot_flops - 7 * 2 * 128**3) < 1e5, c.dot_flops
        assert raw < c.dot_flops / 3  # the undercount this module fixes
        print("ok")
    """)
    out = subprocess.run([sys.executable, "-c", script],
                         env=dict(os.environ, PYTHONPATH="src"),
                         capture_output=True, text=True, timeout=300,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0 and "ok" in out.stdout, out.stderr[-1500:]
