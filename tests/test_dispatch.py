"""HSTU attention backend dispatch: forward/backward parity across
backends (vs the jnp-dense oracle), ragged ROO batches, rab on/off,
non-128-multiple sequence lengths (pad-and-crop), and backend resolution."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hstu import (HSTUConfig, hstu_apply, hstu_attention_chunked,
                             hstu_init)
from repro.core.masks import causal_spec, roo_batch_mask, roo_spec
from repro.kernels import dispatch

PARITY_BACKENDS = ("pallas-interpret", "jnp-chunked")


def _ragged_case(seed, b, h, s, dqk, dv, n_hist, use_rab, tc_min=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = jax.random.normal(ks[0], (b, h, s, dqk))
    k = jax.random.normal(ks[1], (b, h, s, dqk))
    v = jax.random.normal(ks[2], (b, h, s, dv))
    rab = (jax.random.normal(ks[3], (h, 2 * 128 + 1)) * 0.1) if use_rab \
        else None
    hl = jax.random.randint(ks[4], (b,), 0, n_hist + 1)
    tc = jax.random.randint(ks[5], (b,), tc_min, s - n_hist + 1)
    if b >= 3:
        # rows 0-2: empty, a full history with every target, and lengths
        # off the 64-block edge
        hl = hl.at[:3].set(jnp.asarray([0, n_hist, 129]))
        tc = tc.at[:3].set(jnp.asarray([0, s - n_hist, 1]))
    w = jax.random.normal(ks[6], (b, h, s, dv))
    return q, k, v, rab, hl, tc, w


class TestForwardParity:
    @pytest.mark.parametrize("backend", PARITY_BACKENDS)
    @pytest.mark.parametrize("use_rab", [True, False])
    @pytest.mark.parametrize("b,h,s,dqk,dv,n_hist", [
        (2, 2, 128, 32, 32, 96),
        (2, 2, 100, 32, 16, 80),     # non-128-multiple -> pad-and-crop
        (1, 2, 65, 32, 32, 64),      # s < block, m_targets = 1
        (2, 1, 48, 16, 16, 48),      # pure causal (no target slots)
        (4, 2, 200, 32, 32, 184),    # edge rows (see _ragged_case)
    ])
    def test_matches_dense_oracle(self, backend, use_rab, b, h, s, dqk, dv,
                                  n_hist):
        q, k, v, rab, hl, tc, _ = _ragged_case(
            s + 17 * use_rab, b, h, s, dqk, dv, n_hist, use_rab)
        if n_hist == s:
            tc = jnp.zeros_like(tc)
        spec = roo_spec(hl, tc, n_hist)
        out = dispatch.hstu_attention(q, k, v, rab, spec, backend=backend,
                                      block_q=64, block_k=64)
        want = dispatch.hstu_attention(q, k, v, rab, spec,
                                       backend="jnp-dense")
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


class TestGradientParity:
    """Acceptance criterion: jax.grad through the custom_vjp Pallas kernel
    (interpret mode) matches the jnp oracle within 1e-4 rtol on ragged ROO
    batches — and the chunked jnp path does too."""

    @pytest.mark.parametrize("backend", PARITY_BACKENDS)
    @pytest.mark.parametrize("use_rab", [True, False])
    @pytest.mark.parametrize("b,h,s,dqk,dv,n_hist", [
        (2, 2, 128, 32, 32, 96),
        (2, 2, 100, 32, 16, 80),     # pad-and-crop in the backward too
        (4, 2, 200, 32, 32, 184),    # edge rows (see _ragged_case)
    ])
    def test_grads_match_oracle(self, backend, use_rab, b, h, s, dqk, dv,
                                n_hist):
        q, k, v, rab, hl, tc, w = _ragged_case(
            1000 + s, b, h, s, dqk, dv, n_hist, use_rab)
        spec = roo_spec(hl, tc, n_hist)
        argnums = (0, 1, 2, 3) if use_rab else (0, 1, 2)

        def loss(be):
            def f(q, k, v, rab=None):
                out = dispatch.hstu_attention(q, k, v, rab, spec, backend=be,
                                              block_q=64, block_k=64)
                return jnp.sum(out * w)
            return f

        args = (q, k, v, rab) if use_rab else (q, k, v)
        got = jax.grad(loss(backend), argnums=argnums)(*args)
        want = jax.grad(loss("jnp-dense"), argnums=argnums)(*args)
        for name, g, wg in zip("qkvr", got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(wg),
                                       atol=1e-4, rtol=1e-4,
                                       err_msg=f"d{name} ({backend})")

    def test_grad_under_jit_value_and_grad(self):
        """The train-step shape: jit(value_and_grad) through hstu_apply with
        a MaskSpec hits the fused kernel end-to-end."""
        cfg = HSTUConfig(d_model=32, n_heads=2, d_qk=16, d_v=16, n_layers=2,
                         max_rel_pos=72, attn_backend="pallas-interpret")
        params = hstu_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (3, 72, 32))
        spec = roo_spec(jnp.asarray([5, 64, 0]), jnp.asarray([8, 3, 1]), 64)

        def loss(p, be):
            return jnp.sum(hstu_apply(p, cfg, x, spec, backend=be) ** 2)

        l_pl, g_pl = jax.jit(jax.value_and_grad(loss),
                             static_argnums=1)(params, "pallas-interpret")
        l_rf, g_rf = jax.jit(jax.value_and_grad(loss),
                             static_argnums=1)(params, "jnp-dense")
        np.testing.assert_allclose(float(l_pl), float(l_rf), rtol=1e-5)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4), g_pl, g_rf)


class TestChunkedPath:
    def test_chunk_size_independence(self):
        """Output must not depend on the q-chunk tiling."""
        q, k, v, rab, hl, tc, _ = _ragged_case(7, 2, 2, 96, 32, 32, 64, True)
        spec = roo_spec(hl, tc, 64)
        a = hstu_attention_chunked(q, k, v, rab, spec, chunk=32)
        b = hstu_attention_chunked(q, k, v, rab, spec, chunk=128)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def test_no_dense_scores_in_hlo(self):
        """The chunked path must not materialize any (S, S) tensor."""
        s = 256
        q, k, v, rab, hl, tc, _ = _ragged_case(9, 1, 1, s, 16, 16, 192, True)
        spec = roo_spec(hl, tc, 192)
        txt = jax.jit(lambda *a: hstu_attention_chunked(
            *a, spec, chunk=64)).lower(q, k, v, rab).compile().as_text()
        assert f"{s},{s}" not in txt


class TestMaskSpec:
    def test_dense_matches_roo_batch_mask(self):
        hl = jnp.asarray([0, 3, 7])
        tc = jnp.asarray([2, 0, 4])
        spec = roo_spec(hl, tc, 8)
        np.testing.assert_array_equal(np.asarray(spec.dense(12)),
                                      np.asarray(roo_batch_mask(hl, tc, 8, 4)))

    def test_causal_spec_has_no_targets(self):
        spec = causal_spec(jnp.asarray([3]), 4)
        dense = np.asarray(spec.dense(4))
        want = np.tril(np.ones((4, 4), bool)) & \
            (np.arange(4)[None, :] < 3) & (np.arange(4)[:, None] < 3)
        np.testing.assert_array_equal(dense[0], want)

    def test_is_pytree(self):
        spec = roo_spec(jnp.asarray([1]), jnp.asarray([2]), 8)
        leaves = jax.tree.leaves(spec)
        assert len(leaves) == 2
        out = jax.jit(lambda sp: sp.hist_lengths + sp.target_counts)(spec)
        assert int(out[0]) == 3


class TestResolution:
    def test_explicit_arg_wins(self):
        assert dispatch.resolve_backend("jnp-dense") == "jnp-dense"

    def test_env_var(self, monkeypatch):
        monkeypatch.setenv(dispatch.ENV_VAR, "jnp-chunked")
        assert dispatch.resolve_backend() == "jnp-chunked"
        assert dispatch.resolve_backend("jnp-dense") == "jnp-dense"

    def test_explicit_knobs_beat_env(self, monkeypatch):
        """An exported env override must not silently win over the CLI
        flag (set_default_backend) or a pinned serve config (use_backend)."""
        monkeypatch.setenv(dispatch.ENV_VAR, "jnp-dense")
        dispatch.set_default_backend("jnp-chunked")
        try:
            assert dispatch.resolve_backend() == "jnp-chunked"
            with dispatch.use_backend("pallas-interpret"):
                assert dispatch.resolve_backend() == "pallas-interpret"
        finally:
            dispatch.set_default_backend(None)

    def test_default_backend_context(self):
        with dispatch.use_backend("pallas-interpret"):
            assert dispatch.resolve_backend() == "pallas-interpret"
        assert dispatch.get_default_backend() is None
        assert dispatch.resolve_backend() != "pallas-interpret" or \
            jax.default_backend() == "tpu"

    def test_use_backend_is_thread_local(self):
        import threading
        seen = {}

        def other_thread():
            seen["backend"] = dispatch.resolve_backend()

        with dispatch.use_backend("jnp-dense"):
            t = threading.Thread(target=other_thread)
            t.start()
            t.join()
        assert seen["backend"] != "jnp-dense"

    def test_auto_off_tpu(self):
        if jax.default_backend() != "tpu":
            assert dispatch.resolve_backend() == "jnp-chunked"

    def test_unknown_backend_raises(self):
        with pytest.raises(ValueError):
            dispatch.resolve_backend("triton")
