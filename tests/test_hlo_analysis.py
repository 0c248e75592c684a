"""Documents the XLA:CPU quirk the dry-run probes exist for, and checks the
collective-byte parser against a real SPMD lowering."""
import jax
import jax.numpy as jnp
from jax import lax

from repro.core.hlo_analysis import analyze_compiled, parse_collectives


def test_xla_cpu_counts_loop_body_once():
    """cost_analysis FLOPs for a scanned loop == ONE body, not trip_count bodies.
    This is why launch/dryrun.py uses unrolled probe compiles for cost extraction
    (see DESIGN.md)."""
    x = jnp.ones((128, 128))
    w = jnp.ones((128, 128))

    def scanned(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        c, _ = lax.scan(body, x, None, length=10)
        return c.sum()

    def unrolled(x, w):
        c = x
        for _ in range(10):
            c = jnp.tanh(c @ w)
        return c.sum()

    f_scan = jax.jit(scanned).lower(x, w).compile().cost_analysis()["flops"]
    f_unroll = jax.jit(unrolled).lower(x, w).compile().cost_analysis()["flops"]
    assert f_unroll > 8 * f_scan, (f_scan, f_unroll)


def test_analyze_compiled_single_device():
    f = jax.jit(lambda a, b: (a @ b).sum())
    a = jnp.ones((64, 64))
    compiled = f.lower(a, a).compile()
    cost = analyze_compiled(compiled, n_devices=1)
    assert cost.flops >= 2 * 64**3 * 0.9
    assert cost.collective_bytes == 0
    assert cost.peak_memory_per_device > 0


def test_parser_ignores_non_collectives():
    st = parse_collectives("%d = f32[8,8]{1,0} dot(%a, %b)\n%r = f32[] reduce(%x)")
    assert st.total_bytes == 0 and st.total_count == 0


def test_parser_handles_tuple_shapes():
    txt = "%ar = (f32[16]{0}, f32[32]{0}) all-reduce(%a, %b), to_apply=%sum"
    st = parse_collectives(txt)
    assert st.bytes_by_kind["all-reduce"] == 16 * 4 + 32 * 4
