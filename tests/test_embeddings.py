"""The unified embedding subsystem: dedup lookups, the GatheredTable proxy,
SparseRows gradients, the sparse row-wise Adagrad apply, and sparse-vs-dense
training trajectory parity (LSR + DLRM).

Dedup and proxy lookups are pure index bookkeeping, so the contracts here
are EXACT equality (assert_array_equal); the optimizer sparse apply is
bit-for-bit against the dense apply; full training trajectories compare at
rtol 1e-5 over 50 steps (grad summation order differs between the paths).
"""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.data.jagged import JaggedTensor
from repro.embeddings import collection as ec
from repro.embeddings.sparse import (GatheredTable, SparseRows, gather_table,
                                     make_sparse_value_and_grad)
from repro.train.optim import (adam, default_is_embedding, make_mixed,
                               rowwise_adagrad)

N_TRAJECTORY_STEPS = 50


def _rand_table(v=5000, d=16, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (v, d))


class TestDedupLookups:
    """dedup lookup == direct lookup, exactly, on ragged/empty/duplicate-
    heavy bags — the tentpole's correctness contract."""

    @settings(max_examples=25, deadline=None)
    @given(st.integers(1, 10), st.integers(1, 12), st.integers(2, 30),
           st.data())
    def test_dense_bags(self, b, l, alphabet, data):
        """duplicate-heavy: ids drawn from a tiny alphabet."""
        rng = np.random.RandomState(data.draw(st.integers(0, 2 ** 16)))
        tbl = _rand_table()
        ids = jnp.asarray(rng.randint(0, alphabet, size=(b, l)).astype(np.int32))
        lens = jnp.asarray(rng.randint(0, l + 1, size=(b,)).astype(np.int32))
        for pooling in ("sum", "mean", "max"):
            a = ec.bag_lookup_dense(tbl, ids, lens, pooling, dedup=True)
            c = ec.bag_lookup_dense(tbl, ids, lens, pooling, dedup=False)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))

    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.lists(st.integers(0, 20), max_size=8), min_size=1,
                    max_size=8))
    def test_jagged_bags(self, rows):
        """ragged rows incl. empty bags and fully-empty batches."""
        tbl = _rand_table()
        jt = JaggedTensor.from_lists(rows, capacity=80)
        for pooling in ("sum", "mean", "max"):
            a = ec.bag_lookup(tbl, jt, pooling, dedup=True)
            c = ec.bag_lookup(tbl, jt, pooling, dedup=False)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))

    def test_seq_and_row(self):
        tbl = _rand_table()
        ids = jax.random.randint(jax.random.PRNGKey(1), (6, 9), 0, 40)
        np.testing.assert_array_equal(
            np.asarray(ec.seq_lookup(tbl, ids, dedup=True)),
            np.asarray(ec.seq_lookup(tbl, ids, dedup=False)))
        np.testing.assert_array_equal(
            np.asarray(ec.row_lookup(tbl, ids[:, 0], dedup=True)),
            np.asarray(ec.row_lookup(tbl, ids[:, 0], dedup=False)))

    def test_auto_policy_thresholds(self, monkeypatch):
        # tiny table: auto skips dedup; env flips it on for every lookup —
        # outputs stay identical either way (that's the whole point)
        tbl = _rand_table(v=32)
        ids = jax.random.randint(jax.random.PRNGKey(2), (4, 5), 0, 32)
        base = np.asarray(ec.seq_lookup(tbl, ids))
        monkeypatch.setenv("REPRO_EMB_DEDUP", "always")
        np.testing.assert_array_equal(np.asarray(ec.seq_lookup(tbl, ids)),
                                      base)
        monkeypatch.setenv("REPRO_EMB_DEDUP", "never")
        np.testing.assert_array_equal(np.asarray(ec.seq_lookup(tbl, ids)),
                                      base)


class TestGatheredTable:
    def test_proxy_lookups_match_dense(self):
        tbl = _rand_table(v=300, d=8)
        ids = jax.random.randint(jax.random.PRNGKey(0), (7, 11), 0, 300)
        lens = jax.random.randint(jax.random.PRNGKey(1), (7,), 0, 12)
        gt = gather_table(tbl, ids)
        assert isinstance(gt, GatheredTable) and gt.shape == (300, 8)
        np.testing.assert_allclose(
            np.asarray(ec.seq_lookup(gt, ids)),
            np.asarray(ec.seq_lookup(tbl, ids, dedup=False)), atol=0)
        for pooling in ("sum", "mean", "max"):
            np.testing.assert_allclose(
                np.asarray(ec.bag_lookup_dense(gt, ids, lens, pooling)),
                np.asarray(ec.bag_lookup_dense(tbl, ids, lens, pooling,
                                               dedup=False)), atol=0)

    def test_missing_id_reads_zero(self):
        """Ids outside the gathered set read as zero rows, not garbage."""
        tbl = _rand_table(v=100, d=4)
        gt = gather_table(tbl, jnp.asarray([3, 5]))
        out = np.asarray(gt.take(jnp.asarray([3, 7, 5])))
        np.testing.assert_allclose(out[0], np.asarray(tbl)[3], atol=0)
        np.testing.assert_array_equal(out[1], 0)
        np.testing.assert_allclose(out[2], np.asarray(tbl)[5], atol=0)


class TestSparseRows:
    def test_merge_and_densify(self):
        g = SparseRows(jnp.asarray([2, 0, 2, 5], jnp.int32),
                       jnp.asarray([[1., 1.], [2., 2.], [3., 3.], [4., 4.]]),
                       vocab=5)                     # id 5 == padding
        m = g.merged()
        dense = np.asarray(g.to_dense())
        assert dense.shape == (5, 2)
        np.testing.assert_allclose(dense[2], [4., 4.])
        np.testing.assert_allclose(dense[0], [2., 2.])
        np.testing.assert_allclose(np.asarray(m.to_dense()), dense)

    def test_flows_through_value_and_grad(self):
        tbl = _rand_table(v=64, d=8, seed=3)
        params = {"emb": tbl,
                  "w": jax.random.normal(jax.random.PRNGKey(4), (8,))}
        ids = jax.random.randint(jax.random.PRNGKey(5), (12, 4), 0, 64)
        lens = jnp.full((12,), 4, jnp.int32)
        batch = {"ids": ids, "lens": lens}

        def loss(p, b, r):
            e = ec.bag_lookup_dense(p["emb"], b["ids"], b["lens"], "mean")
            return jnp.sum((e @ p["w"]) ** 2)

        vag = make_sparse_value_and_grad(loss, lambda b: {"emb": b["ids"]})
        l_s, g_s = jax.jit(vag)(params, batch, jax.random.PRNGKey(0))
        l_d, g_d = jax.value_and_grad(loss)(params, batch, None)
        assert isinstance(g_s["emb"], SparseRows)
        np.testing.assert_allclose(float(l_s), float(l_d), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(g_s["emb"].to_dense()),
                                   np.asarray(g_d["emb"]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(g_s["w"]),
                                   np.asarray(g_d["w"]), atol=1e-5)


class TestSparseRowwiseAdagrad:
    """sparse-grad apply == dense-grad apply, bit for bit."""

    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 30), st.data())
    def test_bit_for_bit(self, n_touched, data):
        v, d = 50, 6
        rng = np.random.RandomState(data.draw(st.integers(0, 2 ** 16)))
        p = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
        touched = rng.choice(v, size=min(n_touched, v), replace=False)
        g_dense = np.zeros((v, d), np.float32)
        g_dense[touched] = rng.normal(size=(len(touched), d))
        g_sparse = SparseRows(jnp.asarray(touched.astype(np.int32)),
                              jnp.asarray(g_dense[touched]), vocab=v)
        opt = rowwise_adagrad(0.05)
        # run two chained steps so the accumulator path is exercised too
        st_d = st_s = opt.init([p])
        p_d, p_s = [p], [p]
        for _ in range(2):
            p_d, st_d = opt.update([jnp.asarray(g_dense)], st_d, p_d)
            p_s, st_s = opt.update([g_sparse], st_s, p_s)
        np.testing.assert_array_equal(np.asarray(p_d[0]), np.asarray(p_s[0]))
        np.testing.assert_array_equal(np.asarray(st_d["acc"][0]),
                                      np.asarray(st_s["acc"][0]))

    def test_duplicate_ids_merge_before_rowsq(self):
        """Duplicates must sum BEFORE the accumulator math (dense scatter
        semantics), not update twice."""
        v, d = 8, 2
        p = jnp.ones((v, d))
        half = np.full((1, d), 0.5, np.float32)
        g_dup = SparseRows(jnp.asarray([3, 3], jnp.int32),
                           jnp.concatenate([half, half]), vocab=v)
        g_dense = jnp.zeros((v, d)).at[3].set(1.0)
        opt = rowwise_adagrad(0.1)
        p_a, st_a = opt.update([g_dup], opt.init([p]), [p])
        p_b, st_b = opt.update([g_dense], opt.init([p]), [p])
        np.testing.assert_allclose(np.asarray(p_a[0]), np.asarray(p_b[0]),
                                   atol=1e-7)
        np.testing.assert_allclose(np.asarray(st_a["acc"][0]),
                                   np.asarray(st_b["acc"][0]), atol=1e-7)

    def test_mixed_routes_sparse_to_embedding_opt(self):
        params = {"item_emb": jnp.ones((16, 4)), "w": jnp.ones((4, 4))}
        grads = {"item_emb": SparseRows(jnp.asarray([1, 2], jnp.int32),
                                        jnp.ones((2, 4)), vocab=16),
                 "w": jnp.ones((4, 4)) * 0.1}
        opt = make_mixed(adam(1e-3), rowwise_adagrad(0.05),
                         default_is_embedding)
        new_p, _ = opt.update(grads, opt.init(params), params)
        moved = np.asarray(new_p["item_emb"]) != np.asarray(params["item_emb"])
        assert moved[1].all() and moved[2].all() and not moved[0].any()
        assert (np.asarray(new_p["w"]) != np.asarray(params["w"])).all()


class TestSparseGradAccum:
    def test_microbatch_scan_matches_dense(self):
        """SparseRows grads ride the accumulation scan as stacked ys; the
        resulting step must match the dense-grad step."""
        from repro.train.loop import make_train_step
        rng = jax.random.PRNGKey(0)
        params = {"emb": _rand_table(v=64, d=8, seed=3) * 0.1,
                  "w": jax.random.normal(jax.random.PRNGKey(4), (8,))}
        ids = jax.random.randint(jax.random.PRNGKey(5), (2, 12, 4), 0, 64)
        mb = {"ids": ids, "lens": jnp.full((2, 12), 4, jnp.int32)}

        def loss(p, b, r):
            e = ec.bag_lookup_dense(p["emb"], b["ids"], b["lens"], "mean")
            return jnp.sum((e @ p["w"]) ** 2)

        vag = make_sparse_value_and_grad(loss, lambda b: {"emb": b["ids"]})
        opt = make_mixed(adam(1e-3), rowwise_adagrad(0.05),
                         default_is_embedding)

        def run(value_and_grad_fn):
            step = make_train_step(loss, opt, microbatches=2,
                                   value_and_grad_fn=value_and_grad_fn)
            state = {"params": params, "opt": opt.init(params),
                     "step": jnp.zeros((), jnp.int32)}
            losses = []
            for i in range(8):
                state, m = step(state, mb, jax.random.fold_in(rng, i))
                losses.append(float(m["loss"]))
            return losses, state

        losses_d, state_d = run(None)
        losses_s, state_s = run(vag)
        # The two paths add the microbatches' row contributions in different
        # orders (dense accumulator vs COO segment-sum): the first step's
        # table differs by 1 ulp at the table's scale, and 8 Adam/Adagrad
        # steps carry that into the loss (up to 10 ulps measured). Bound:
        # 32 ulps of f32 (32 * 2**-23 ~= 3.8e-6).
        np.testing.assert_allclose(losses_s, losses_d, rtol=4e-6)
        np.testing.assert_allclose(np.asarray(state_s["params"]["emb"]),
                                   np.asarray(state_d["params"]["emb"]),
                                   rtol=1e-5, atol=1e-7)


def _roo_batches(n_requests=60, n_items=512, b_ro=8, b_nro=32, hist=16):
    from repro.core.joiner import RequestLevelJoiner
    from repro.data.batcher import BatcherConfig, ROOBatcher
    from repro.data.events import EventSimulator, EventStreamConfig
    stream = EventStreamConfig(n_requests=n_requests, n_items=n_items,
                               hist_init_max=12, seed=0)
    samples = RequestLevelJoiner().join(list(EventSimulator(stream).stream()))
    cfg = BatcherConfig(b_ro=b_ro, b_nro=b_nro, hist_len=hist,
                        ro_idlist_capacity=256, item_idlist_capacity=512)
    return list(ROOBatcher(cfg).batches(samples))


class TestSparseTrajectoryParity:
    """Acceptance contract: sparse-grad training == dense-grad training,
    loss trajectories within rtol 1e-5 over >= 50 steps, LSR and DLRM."""

    def _run(self, loss, params, batches, vag, n_steps):
        from repro.train.loop import make_train_step
        opt = make_mixed(adam(1e-3), rowwise_adagrad(0.05),
                         default_is_embedding)
        step = make_train_step(loss, opt, value_and_grad_fn=vag)
        state = {"params": params, "opt": opt.init(params),
                 "step": jnp.zeros((), jnp.int32)}
        rng = jax.random.PRNGKey(7)
        losses = []
        for i in range(n_steps):
            state, m = step(state, batches[i % len(batches)],
                            jax.random.fold_in(rng, i))
            losses.append(float(m["loss"]))
        return np.asarray(losses), state

    def test_lsr_50_steps(self):
        from repro.core.hstu import HSTUConfig
        from repro.models.lsr import LSRConfig, lsr_init, lsr_loss, \
            lsr_table_ids
        cfg = LSRConfig(n_items=512, n_user_cats=64, n_item_cats=64,
                        embed_dim=32, hist_len=16, mode="userarch_hstu",
                        lce_n_out=4, lce_d_out=32, n_cross_layers=2,
                        top_mlp=(64,),
                        hstu=HSTUConfig(d_model=32, n_heads=2, d_qk=16,
                                        d_v=16, n_layers=1, max_rel_pos=16))
        params = lsr_init(jax.random.PRNGKey(0), cfg)
        batches = _roo_batches()
        loss = lambda p, b, r: lsr_loss(p, cfg, b)
        vag = make_sparse_value_and_grad(loss,
                                         lambda b: lsr_table_ids(cfg, b))
        losses_d, state_d = self._run(loss, params, batches, None,
                                      N_TRAJECTORY_STEPS)
        losses_s, state_s = self._run(loss, params, batches, vag,
                                      N_TRAJECTORY_STEPS)
        np.testing.assert_allclose(losses_s, losses_d, rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(
            np.asarray(state_s["params"]["item_emb"]),
            np.asarray(state_d["params"]["item_emb"]), rtol=1e-4, atol=1e-6)

    def test_dlrm_50_steps(self):
        from repro.models.dlrm import (DLRMConfig, dlrm_forward_roo,
                                       dlrm_init, dlrm_table_ids)
        cfg = DLRMConfig(n_dense=4, embed_dim=16, bot_mlp=(4, 32, 16),
                         top_mlp=(64, 32, 1), vocabs=(512, 256, 64, 32),
                         n_ro_fields=2, multi_hot=2)
        params = dlrm_init(jax.random.PRNGKey(0), cfg)
        r = np.random.RandomState(0)
        b_ro, b_nro = 8, 32
        batches = []
        for _ in range(4):
            batches.append({
                "ro_dense": jnp.asarray(
                    r.normal(size=(b_ro, 4)).astype(np.float32)),
                "ro_ids": jnp.asarray(
                    r.randint(0, 512, (b_ro, 2, 2)).astype(np.int32)),
                "ro_len": jnp.full((b_ro, 2), 2, jnp.int32),
                "nro_ids": jnp.asarray(
                    r.randint(0, 32, (b_nro, 2, 2)).astype(np.int32)),
                "nro_len": jnp.full((b_nro, 2), 2, jnp.int32),
                "seg": jnp.repeat(jnp.arange(b_ro, dtype=jnp.int32),
                                  b_nro // b_ro),
                "y": jnp.asarray(
                    (r.uniform(size=(b_nro,)) < 0.3).astype(np.float32))})

        def loss(p, b, r_):
            logits = dlrm_forward_roo(p, cfg, b["ro_dense"], b["ro_ids"],
                                      b["ro_len"], b["nro_ids"], b["nro_len"],
                                      b["seg"])
            y = b["y"]
            bce = jnp.maximum(logits, 0) - logits * y + \
                jnp.log1p(jnp.exp(-jnp.abs(logits)))
            return jnp.mean(bce)

        vag = make_sparse_value_and_grad(
            loss, lambda b: dlrm_table_ids(cfg, b["ro_ids"], b["nro_ids"]))
        losses_d, state_d = self._run(loss, params, batches, None,
                                      N_TRAJECTORY_STEPS)
        losses_s, state_s = self._run(loss, params, batches, vag,
                                      N_TRAJECTORY_STEPS)
        np.testing.assert_allclose(losses_s, losses_d, rtol=1e-5, atol=1e-7)
        for name, tbl in state_d["params"]["tables"].items():
            np.testing.assert_allclose(
                np.asarray(state_s["params"]["tables"][name]),
                np.asarray(tbl), rtol=1e-4, atol=1e-6,
                err_msg=f"table {name} diverged")
