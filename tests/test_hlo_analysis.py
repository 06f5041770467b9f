"""Trip-count-aware HLO analysis: scan/nested-scan FLOP accounting."""
import jax
import jax.numpy as jnp

from repro.launch.hlo_analysis import analyze_hlo


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def test_scan_flops_multiplied():
    def f(x, w):
        def body(c, wi):
            return c @ wi, None
        out, _ = jax.lax.scan(body, x, w)
        return out
    c = _compile(f, jax.ShapeDtypeStruct((128, 128), jnp.float32),
                 jax.ShapeDtypeStruct((10, 128, 128), jnp.float32))
    res = analyze_hlo(c.as_text())
    expect = 10 * 2 * 128 ** 3
    assert abs(res["flops"] - expect) / expect < 0.01
    # XLA's own counter is ~10x off — that's why the parser exists
    assert c.cost_analysis()["flops"] < expect / 5


def test_nested_scan_flops():
    def g(x, w):
        def outer(c, wi):
            def inner(c2, _):
                return c2 @ wi, None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        out, _ = jax.lax.scan(outer, x, w)
        return out
    c = _compile(g, jax.ShapeDtypeStruct((128, 128), jnp.float32),
                 jax.ShapeDtypeStruct((10, 128, 128), jnp.float32))
    res = analyze_hlo(c.as_text())
    expect = 30 * 2 * 128 ** 3
    assert abs(res["flops"] - expect) / expect < 0.01


def test_bytes_positive_and_sane():
    def f(x):
        return jnp.tanh(x @ x)
    c = _compile(f, jax.ShapeDtypeStruct((256, 256), jnp.float32))
    res = analyze_hlo(c.as_text())
    assert res["bytes"] >= 3 * 256 * 256 * 4   # two reads + one write minimum
    assert res["collective_bytes"] == 0.0
