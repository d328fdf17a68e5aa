"""Loop-aware HLO analysis (utils/hlo.py): the roofline's measurement
tool must count while-loop bodies by trip count and dots by contraction."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_flops_count_loop_trips():
    """A scan of 7 matmuls must count ~7x one matmul's FLOPs."""
    code = """
    import jax, jax.numpy as jnp
    from repro.utils.hlo import analyze_hlo

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), ()
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out.sum()

    x = jnp.ones((64, 256), jnp.float32)
    w = jnp.ones((256, 256), jnp.float32)
    t = jax.jit(f).lower(x, w).compile().as_text()
    a = analyze_hlo(t)
    per_mm = 2 * 64 * 256 * 256
    ratio = a["flops"] / (7 * per_mm)
    assert 0.9 < ratio < 1.3, ratio
    print("OK", ratio)
    """
    assert "OK" in _run(code)


def test_collective_bytes_sharded_matmul():
    """Row-sharded matmul -> one all-reduce of the result per step,
    counted at bf16 width (CPU promotes to f32)."""
    code = """
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.utils.hlo import analyze_hlo

    mesh = jax.make_mesh((8,), ("m",))
    x = jax.ShapeDtypeStruct((16, 512), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P(None, "m")))
    w = jax.ShapeDtypeStruct((512, 128), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("m", None)))
    def f(x, w):
        # the contraction dim is sharded: the replicated output sharding
        # must be named, and it is what makes the all-reduce
        y = jnp.matmul(x, w, out_sharding=NamedSharding(mesh, P()))
        return jnp.square(y.astype(jnp.float32)).sum()
    with mesh:
        t = jax.jit(f).lower(x, w).compile().as_text()
    a = analyze_hlo(t)
    # result [16,128]: bf16 width = 4096 B (f32 would be 8192)
    ar = a["collective"]["all-reduce"]
    assert 2048 <= ar <= 3 * 4096, ar
    print("OK", ar)
    """
    assert "OK" in _run(code)


def test_dot_flops_formula():
    code = """
    import jax, jax.numpy as jnp
    from repro.utils.hlo import analyze_hlo
    f = lambda a, b: a @ b
    a = jnp.ones((37, 111), jnp.float32)
    b = jnp.ones((111, 53), jnp.float32)
    t = jax.jit(f).lower(a, b).compile().as_text()
    flops = analyze_hlo(t)["flops"]
    assert flops == 2 * 37 * 111 * 53, flops
    print("OK")
    """
    assert "OK" in _run(code)
