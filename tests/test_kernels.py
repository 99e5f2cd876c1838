"""Per-kernel allclose vs the ref.py oracles: shape/dtype sweeps +
hypothesis property tests (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.dot_interaction import dot_interaction
from repro.kernels.embedding_bag import embedding_bag
from repro.core.masks import roo_batch_mask
from repro.kernels.hstu_attention import _tile_live, _tile_table, hstu_attention


class TestHSTUAttention:
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                           (jnp.bfloat16, 2e-2)])
    @pytest.mark.parametrize("b,h,s,dqk,dv,n_hist", [
        (1, 1, 128, 32, 32, 96),
        (2, 2, 256, 64, 64, 192),
        (2, 4, 256, 64, 128, 224),
    ])
    def test_matches_oracle(self, b, h, s, dqk, dv, n_hist, dtype, tol):
        rng = jax.random.PRNGKey(0)
        ks = jax.random.split(rng, 6)
        q = jax.random.normal(ks[0], (b, h, s, dqk), dtype)
        k = jax.random.normal(ks[1], (b, h, s, dqk), dtype)
        v = jax.random.normal(ks[2], (b, h, s, dv), dtype)
        rab = (jax.random.normal(ks[3], (h, 2 * 128 + 1)) * 0.1).astype(dtype)
        hl = jax.random.randint(ks[4], (b,), 0, n_hist + 1)
        tc = jax.random.randint(ks[5], (b,), 1, s - n_hist + 1)
        out = hstu_attention(q, k, v, rab, n_hist, hl, tc, 128,
                             block_q=64, block_k=64)
        want = ref.hstu_attention_ref(q, k, v, rab, n_hist, hl, tc, 128)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=tol, rtol=tol)

    def test_no_rab(self):
        rng = jax.random.PRNGKey(1)
        q = jax.random.normal(rng, (1, 2, 128, 32))
        out = hstu_attention(q, q, q, None, 96, jnp.asarray([80]),
                             jnp.asarray([20]), 128, block_q=64, block_k=64)
        want = ref.hstu_attention_ref(q, q, q, None, 96, jnp.asarray([80]),
                                      jnp.asarray([20]), 128)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-5)

    def test_block_shape_independence(self):
        """Output must not depend on the VMEM tiling."""
        rng = jax.random.PRNGKey(2)
        q = jax.random.normal(rng, (1, 1, 256, 32))
        args = (q, q, q, None, 192, jnp.asarray([150]), jnp.asarray([40]), 128)
        a = hstu_attention(*args, block_q=64, block_k=64)
        b = hstu_attention(*args, block_q=128, block_k=256)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    @pytest.mark.parametrize("tc_of", ["none", "one", "all"])
    @pytest.mark.parametrize("n_hist,s", [(496, 512),    # on the lattice
                                          (500, 516)])   # padded to it
    @pytest.mark.parametrize("bq,bk", [(64, 64), (128, 128), (128, 256)])
    def test_tile_live_matches_dense_mask(self, bq, bk, n_hist, s, tc_of):
        """``_tile_live`` is true on exactly the tiles where the dense ROO
        mask (over the padded lattice, as the kernels see it) holds a true
        cell, in-kernel (jnp) and on the host (numpy); the structural table
        holds every tile live for any lengths."""
        lcm = bq * bk // np.gcd(bq, bk)
        s_pad = -(-s // lcm) * lcm
        hl = np.asarray([0, 1, 127, 128, 129, n_hist // 2 + 37, n_hist])
        tc = np.full_like(hl, {"none": 0, "one": 1, "all": s - n_hist}[tc_of])
        dense = np.asarray(roo_batch_mask(jnp.asarray(hl), jnp.asarray(tc),
                                          n_hist, s_pad - n_hist))
        want = dense.reshape(len(hl), s_pad // bq, bq, s_pad // bk, bk) \
            .any(axis=(2, 4))                                   # (B, nq, nk)
        qi, ki = np.meshgrid(np.arange(s_pad // bq), np.arange(s_pad // bk),
                             indexing="ij")
        kw = dict(n_hist=n_hist, bq=bq, bk=bk)
        host = _tile_live(qi[None], ki[None], hl[:, None, None],
                          tc[:, None, None], xp=np, **kw)
        np.testing.assert_array_equal(host, want)
        dev = _tile_live(jnp.asarray(qi)[None], jnp.asarray(ki)[None],
                         jnp.asarray(hl)[:, None, None],
                         jnp.asarray(tc)[:, None, None], **kw)
        np.testing.assert_array_equal(np.asarray(dev), want)
        for by_col in (False, True):
            tq, tk = _tile_table(n_hist, s_pad, bq, bk, by_col)
            table = np.zeros(want.shape[1:], bool)
            table[tq, tk] = True
            assert not (want & ~table).any()
            assert len(tq) == table.sum()


class TestEmbeddingBag:
    @pytest.mark.parametrize("pooling", ["sum", "mean", "max"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("v,d,b,l", [(100, 8, 4, 3), (1000, 64, 16, 10),
                                         (5000, 128, 32, 20)])
    def test_matches_oracle(self, v, d, b, l, dtype, pooling):
        rng = jax.random.PRNGKey(0)
        tbl = jax.random.normal(rng, (v, d), dtype)
        ids = jax.random.randint(jax.random.fold_in(rng, 1), (b, l), 0, v)
        lens = jax.random.randint(jax.random.fold_in(rng, 2), (b,), 0, l + 1)
        out = embedding_bag(tbl, ids, lens, pooling,
                            backend="pallas-interpret")
        want = ref.embedding_bag_ref(tbl, ids, lens, pooling)
        # bf16: kernel accumulates in-place in bf16; oracle reduces in a
        # different order — tolerance is 2 ulps of the running sum
        tol = 5e-2 if dtype == jnp.bfloat16 else 1e-6
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=tol, rtol=tol)

    @pytest.mark.parametrize("pooling", ["sum", "mean", "max"])
    def test_table_grad_matches_oracle(self, pooling):
        """The custom_vjp backward (COO rows -> dense cotangent) must agree
        with autodiff through the jnp oracle."""
        rng = jax.random.PRNGKey(3)
        v, d, b, l = 200, 16, 8, 6
        tbl = jax.random.normal(rng, (v, d))
        ids = jax.random.randint(jax.random.fold_in(rng, 1), (b, l), 0, v)
        lens = jax.random.randint(jax.random.fold_in(rng, 2), (b,), 0, l + 1)
        w = jax.random.normal(jax.random.fold_in(rng, 3), (b, d))

        def loss(fn):
            return lambda t: jnp.sum(w * fn(t))
        g_kernel = jax.grad(loss(lambda t: embedding_bag(
            t, ids, lens, pooling, backend="pallas-interpret")))(tbl)
        g_oracle = jax.grad(loss(lambda t: ref.embedding_bag_ref(
            t, ids, lens, pooling)))(tbl)
        np.testing.assert_allclose(np.asarray(g_kernel),
                                   np.asarray(g_oracle), atol=1e-5)

    def test_backend_resolution(self, monkeypatch):
        """Selection follows the dispatch ladder: auto==jnp off-TPU, env
        override honored, explicit arg beats env."""
        from repro.kernels import dispatch
        assert dispatch.resolve_emb_backend() == "jnp"   # CPU auto
        monkeypatch.setenv(dispatch.EMB_ENV_VAR, "pallas-interpret")
        assert dispatch.resolve_emb_backend() == "pallas-interpret"
        assert dispatch.resolve_emb_backend("jnp") == "jnp"
        with dispatch.use_emb_backend("jnp"):            # scoped beats env
            assert dispatch.resolve_emb_backend() == "jnp"
        dispatch.set_default_emb_backend("jnp")          # default beats env
        try:
            assert dispatch.resolve_emb_backend() == "jnp"
        finally:
            dispatch.set_default_emb_backend(None)
        with pytest.raises(ValueError):
            dispatch.resolve_emb_backend("cuda")

    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 12), st.integers(1, 9), st.data())
    def test_property_random_bags(self, b, l, data):
        v, d = 64, 16
        rng = np.random.RandomState(data.draw(st.integers(0, 2 ** 16)))
        tbl = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
        ids = jnp.asarray(rng.randint(0, v, size=(b, l)).astype(np.int32))
        lens = jnp.asarray(rng.randint(0, l + 1, size=(b,)).astype(np.int32))
        out = np.asarray(embedding_bag(tbl, ids, lens,
                                       backend="pallas-interpret"))
        # independent numpy oracle
        want = np.zeros((b, d), np.float32)
        for i in range(b):
            for j in range(int(lens[i])):
                want[i] += np.asarray(tbl)[int(ids[i, j])]
        np.testing.assert_allclose(out, want, atol=1e-5)


class TestDotInteraction:
    @pytest.mark.parametrize("b,f,d", [(128, 26, 128), (256, 8, 64),
                                       (128, 13, 32)])
    def test_matches_oracle(self, b, f, d):
        rng = jax.random.PRNGKey(0)
        de = jax.random.normal(rng, (b, d))
        sp = jax.random.normal(jax.random.fold_in(rng, 1), (b, f, d))
        out = dot_interaction(de, sp)
        want = ref.dot_interaction_ref(de, sp)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-4)

    def test_output_width(self):
        b, f, d = 128, 26, 128
        out = dot_interaction(jnp.ones((b, d)), jnp.ones((b, f, d)))
        assert out.shape == (b, d + (f + 1) * f // 2)


class TestOpsWrappers:
    def test_never_path_equals_pallas(self):
        from repro.kernels import ops
        rng = jax.random.PRNGKey(3)
        tbl = jax.random.normal(rng, (64, 16))
        ids = jax.random.randint(rng, (8, 4), 0, 64)
        lens = jnp.full((8,), 4, jnp.int32)
        a = ops.embedding_bag(tbl, ids, lens, backend="jnp")
        b = ops.embedding_bag(tbl, ids, lens, backend="pallas-interpret")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    def test_wrappers_resolve_through_dispatch(self, monkeypatch):
        """No wrapper picks interpret mode on its own: off a TPU the auto
        rung is the jnp path, and ``pallas-interpret`` must be asked for."""
        from repro.kernels import dispatch, ops
        monkeypatch.delenv(dispatch.EMB_ENV_VAR, raising=False)
        de = jnp.ones((8, 16))
        sp = jnp.ones((8, 3, 16))
        want = ref.dot_interaction_ref(de, sp)
        assert dispatch.resolve_emb_backend() == "jnp"
        np.testing.assert_array_equal(
            np.asarray(ops.dot_interaction(de, sp)), np.asarray(want))
        np.testing.assert_allclose(
            np.asarray(ops.dot_interaction(de, sp,
                                           backend="pallas-interpret")),
            np.asarray(want), atol=1e-4)
