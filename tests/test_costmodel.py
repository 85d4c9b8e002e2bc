"""Unit tests for the roofline cost model -- the §Roofline methodology
depends on these being exactly right."""
import jax
import jax.numpy as jnp

from repro.launch.costmodel import Cost, jaxpr_cost, _shape_bytes


def test_dot_flops_exact():
    f = lambda a, b: a @ b
    t = jax.jit(f).trace(jax.ShapeDtypeStruct((32, 64), jnp.float32),
                         jax.ShapeDtypeStruct((64, 16), jnp.float32))
    assert jaxpr_cost(t.jaxpr).flops == 2 * 32 * 64 * 16


def test_batched_dot_flops():
    f = lambda a, b: jnp.einsum("bij,bjk->bik", a, b)
    t = jax.jit(f).trace(jax.ShapeDtypeStruct((4, 8, 16), jnp.float32),
                         jax.ShapeDtypeStruct((4, 16, 8), jnp.float32))
    assert jaxpr_cost(t.jaxpr).flops == 4 * 2 * 8 * 16 * 8


def test_scan_trip_count_multiplies():
    def f(x, ws):
        y, _ = jax.lax.scan(lambda c, w: (c @ w, None), x, ws)
        return y
    t = jax.jit(f).trace(jax.ShapeDtypeStruct((8, 16), jnp.float32),
                         jax.ShapeDtypeStruct((7, 16, 16), jnp.float32))
    got = jaxpr_cost(t.jaxpr).flops
    assert got == 7 * 2 * 8 * 16 * 16


def test_grad_counts_backward():
    def loss(w, x):
        y, _ = jax.lax.scan(lambda c, wi: (jnp.tanh(c @ wi), None), x, w)
        return jnp.sum(y * y)
    w = jax.ShapeDtypeStruct((4, 32, 32), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 32), jnp.float32)
    fwd = jaxpr_cost(jax.jit(loss).trace(w, x).jaxpr).flops
    grad = jaxpr_cost(jax.jit(jax.grad(loss)).trace(w, x).jaxpr).flops
    assert 2.8 < grad / fwd < 3.3          # fwd + 2x in backward


def test_remat_counts_recompute():
    def loss(w, x):
        body = jax.checkpoint(lambda c, wi: jnp.tanh(c @ wi))
        y, _ = jax.lax.scan(lambda c, wi: (body(c, wi), None), x, w)
        return jnp.sum(y * y)
    w = jax.ShapeDtypeStruct((4, 32, 32), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 32), jnp.float32)
    grad = jaxpr_cost(jax.jit(jax.grad(loss)).trace(w, x).jaxpr).flops
    one = 2 * 8 * 32 * 32
    assert 3.8 * 4 * one < grad < 4.4 * 4 * one   # ~4x per layer w/ remat


def test_while_flagged_unknown():
    def f(x):
        return jax.lax.while_loop(lambda c: jnp.sum(c) < 100.0,
                                  lambda c: c * 2.0, x)
    t = jax.jit(f).trace(jax.ShapeDtypeStruct((8,), jnp.float32))
    assert jaxpr_cost(t.jaxpr).unknown_loops >= 1


def test_shape_bytes_parser():
    assert _shape_bytes("f32[8,256]{1,0} all-gather(...)") == 8 * 256 * 4
    assert _shape_bytes("bf16[2,4]{1,0}") == 2 * 4 * 2
    assert _shape_bytes("(f32[4], s32[2])") == 4 * 4 + 2 * 4
    assert _shape_bytes("pred[]") == 1


def test_collective_parser_end_to_end():
    """Hand-checkable program: AG inside a 5-trip scan on a (2,4) mesh."""
    import json
    import os
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, json
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.mesh import make_mesh
        from repro.launch.costmodel import collective_bytes
        mesh = make_mesh((2, 4), ("data", "model"))
        def step(x, ws):
            y, _ = jax.lax.scan(lambda c, w: (c @ w, None), x, ws)
            return jnp.sum(y)
        x = jax.ShapeDtypeStruct((16, 256), jnp.float32,
            sharding=NamedSharding(mesh, P("data", None)))
        ws = jax.ShapeDtypeStruct((5, 256, 256), jnp.float32,
            sharding=NamedSharding(mesh, P(None, None, "model")))
        cb = collective_bytes(jax.jit(step).lower(x, ws).compile().as_text())
        print(json.dumps(cb["by_kind"]))
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))), "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    by_kind = json.loads(out.stdout.strip().splitlines()[-1])
    # AG of f32[8,256] per device, ring (4-1)/4, x5 trips. The exact gathered
    # shape is XLA-version dependent (older releases pad the operand, only
    # ever ADDING bytes -- observed 1.25x on 0.4.x, exact on current), so
    # bound from below by the analytic value and above by the padding slack:
    # dropping a scan trip (0.8x) or the ring factor (1.33x) still fails.
    analytic = 8 * 256 * 4 * 0.75 * 5
    assert analytic * 0.999 <= by_kind["all-gather"] <= analytic * 1.3, \
        by_kind["all-gather"]


def test_cost_add_mul():
    c = Cost(flops=2, bytes=4, collective_bytes=6) * 3
    assert (c.flops, c.bytes, c.collective_bytes) == (6, 12, 18)
    s = c + Cost(flops=1, bytes=1, collective_bytes=1, unknown_loops=2)
    assert (s.flops, s.unknown_loops) == (7, 2)
