"""Validate the loop-aware HLO analyzer against unrolled references."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_analysis import analyze


def _compile(f, *avals):
    return jax.jit(f).lower(*avals).compile()


class TestHLOAnalysis:
    def test_plain_dot(self):
        c = _compile(lambda a, b: a @ b,
                     jax.ShapeDtypeStruct((128, 256), jnp.float32),
                     jax.ShapeDtypeStruct((256, 512), jnp.float32))
        a = analyze(c.as_text())
        assert a["flops"] == pytest.approx(2 * 128 * 256 * 512, rel=0.01)

    @pytest.mark.parametrize("n_layers", [2, 8, 32])
    def test_scan_multiplies_by_trip_count(self, n_layers):
        def f(x, w):
            def body(c, wi):
                return jnp.dot(c, wi), None
            y, _ = jax.lax.scan(body, x, w)
            return y.sum()
        c = _compile(f, jax.ShapeDtypeStruct((128, 256), jnp.float32),
                     jax.ShapeDtypeStruct((n_layers, 256, 256), jnp.float32))
        a = analyze(c.as_text())
        expect = n_layers * 2 * 128 * 256 * 256
        assert a["flops"] == pytest.approx(expect, rel=0.01)
        # XLA's own analysis counts the body once — the bug we correct
        ca = c.cost_analysis()
        assert ca["flops"] < expect / (n_layers / 1.5)

    def test_scan_equals_unrolled(self):
        """Weighted scan accounting == fully unrolled program accounting."""
        def scanf(x, w):
            y, _ = jax.lax.scan(lambda c, wi: (jnp.dot(c, wi), None), x, w)
            return y.sum()

        def unrolledf(x, w):
            for i in range(6):
                x = jnp.dot(x, w[i])
            return x.sum()

        avals = (jax.ShapeDtypeStruct((64, 128), jnp.float32),
                 jax.ShapeDtypeStruct((6, 128, 128), jnp.float32))
        a_scan = analyze(_compile(scanf, *avals).as_text())
        a_unr = analyze(_compile(unrolledf, *avals).as_text())
        assert a_scan["flops"] == pytest.approx(a_unr["flops"], rel=0.01)

    def test_nested_scan(self):
        def f(x, w):
            def outer(c, wi):
                def inner(ci, _):
                    return jnp.tanh(jnp.dot(ci, wi)), None
                ci, _ = jax.lax.scan(inner, c, None, length=3)
                return ci, None
            y, _ = jax.lax.scan(outer, x, w)
            return y.sum()
        c = _compile(f, jax.ShapeDtypeStruct((32, 64), jnp.float32),
                     jax.ShapeDtypeStruct((4, 64, 64), jnp.float32))
        a = analyze(c.as_text())
        expect = 4 * 3 * 2 * 32 * 64 * 64
        assert a["flops"] == pytest.approx(expect, rel=0.01)

    def test_grad_counts_forward_and_backward(self):
        def loss(w, x):
            return jnp.sum(jnp.tanh(x @ w))
        c = _compile(jax.grad(loss),
                     jax.ShapeDtypeStruct((256, 256), jnp.float32),
                     jax.ShapeDtypeStruct((128, 256), jnp.float32))
        a = analyze(c.as_text())
        fwd = 2 * 128 * 256 * 256
        # grad: fwd dot + dW = x^T @ g -> ~2x fwd (dx not needed for arg 0)
        assert a["flops"] >= 1.9 * fwd
