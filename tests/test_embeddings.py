"""The unified embedding subsystem: dedup lookups, the GatheredTable proxy,
SparseRows gradients, the sparse row-wise Adagrad apply, and the train
step's default touched-rows gradient against an explicit dense one:
training trajectory parity (LSR + DLRM) and one step at a time (DLRM,
hstu-gr, LSR).

Dedup and proxy lookups are pure index bookkeeping, so the contracts here
are EXACT equality (assert_array_equal); the optimizer sparse apply is
bit-for-bit against the dense apply; full training trajectories compare at
rtol 1e-5 over 50 steps (grad summation order differs between the paths).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.data.jagged import JaggedTensor
from repro.embeddings import collection as ec
from repro.embeddings.sparse import (GatheredTable, SparseRows,
                                     gather_table,
                                     touched_rows_value_and_grad)
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
        # 48 id slots a step: 1,024 rows keep them within TOUCHED_MAX_FILL
        tbl = _rand_table(v=1024, d=8, seed=3)
        params = {"emb": tbl,
                  "w": jax.random.normal(jax.random.PRNGKey(4), (8,))}
        ids = jax.random.randint(jax.random.PRNGKey(5), (12, 4), 0, 1024)
        lens = jnp.full((12,), 4, jnp.int32)
        batch = {"ids": ids, "lens": lens}

        def loss(p, b, r):
            e = ec.bag_lookup_dense(p["emb"], b["ids"], b["lens"], "mean")
            return jnp.sum((e @ p["w"]) ** 2)

        vag = touched_rows_value_and_grad(loss, _mixed().rows_mask)
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
        """The default step's SparseRows grads ride the accumulation scan
        as stacked ys; the resulting step must match the dense-grad step."""
        from repro.train.loop import make_train_step
        rng = jax.random.PRNGKey(0)
        # 48 id slots a microbatch: 1,024 rows keep them within
        # TOUCHED_MAX_FILL, so the default takes the touched rows
        params = {"emb": _rand_table(v=1024, d=8, seed=3) * 0.1,
                  "w": jax.random.normal(jax.random.PRNGKey(4), (8,))}
        ids = jax.random.randint(jax.random.PRNGKey(5), (2, 12, 4), 0, 1024)
        mb = {"ids": ids, "lens": jnp.full((2, 12), 4, jnp.int32)}

        def loss(p, b, r):
            e = ec.bag_lookup_dense(p["emb"], b["ids"], b["lens"], "mean")
            return jnp.sum((e @ p["w"]) ** 2)

        opt = make_mixed(adam(1e-3), rowwise_adagrad(0.05),
                         default_is_embedding)

        def run(value_and_grad_fn):
            step = make_train_step(loss, opt, microbatches=2,
                                   value_and_grad_fn=value_and_grad_fn)
            state = {"params": params, "opt": opt.init(params),
                     "step": jnp.zeros((), jnp.int32)}
            losses, touched = [], []
            for i in range(8):
                state, m = step(state, mb, jax.random.fold_in(rng, i))
                losses.append(float(m["loss"]))
                touched.append(int(m["touched_rows"]))
            return losses, touched, state

        losses_d, _, state_d = run(jax.value_and_grad(loss))
        losses_s, touched, state_s = run(None)
        assert min(touched) > 0
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
    """Acceptance contract: the default touched-rows training == dense-grad
    training, loss trajectories within rtol 1e-5 over >= 50 steps, LSR and
    DLRM."""

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

    @pytest.mark.parametrize("model", ["lsr", "dlrm"])
    def test_50_steps(self, model):
        # the tables compared after the run, and the tables the default
        # step must take on the touched rows
        loss, params, batches, _ = {
            "lsr": lambda: _tiny_lsr(n_batches=None),
            "dlrm": lambda: _tiny_dlrm(vocabs=(512, 256, 64, 32),
                                       n_batches=4)}[model]()
        compared, sparse = {
            "lsr": ("item_emb", {("item_emb",)}),
            "dlrm": ("tables", {("tables", "t0"), ("tables", "t1")})}[model]
        assert sparse <= _touched(loss, params, batches[0])
        losses_d, state_d = self._run(loss, params, batches,
                                      jax.value_and_grad(loss),
                                      N_TRAJECTORY_STEPS)
        losses_s, state_s = self._run(loss, params, batches, None,
                                      N_TRAJECTORY_STEPS)
        np.testing.assert_allclose(losses_s, losses_d, rtol=1e-5, atol=1e-7)
        for (path, a), (_, b) in zip(
                jax.tree_util.tree_flatten_with_path(
                    state_s["params"][compared])[0],
                jax.tree_util.tree_flatten_with_path(
                    state_d["params"][compared])[0]):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6,
                err_msg=f"{compared}{jax.tree_util.keystr(path)} diverged")


# ---------------------------------------------------------------------------
# The train step's default: touched-rows gradients found by a recording pass
# ---------------------------------------------------------------------------

def _mixed():
    return make_mixed(adam(1e-3), rowwise_adagrad(0.05), default_is_embedding)


def _steps(loss, params, batches, vag=None, n=3, plan=None):
    """``n`` steps of the real train step: (metrics per step, final state)."""
    from repro.train.loop import make_train_step
    opt = _mixed()
    step = make_train_step(loss, opt, value_and_grad_fn=vag, plan=plan)
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    metrics = []
    for i in range(n):
        state, m = step(state, batches[i % len(batches)],
                        jax.random.PRNGKey(i))
        metrics.append({k: int(v) if k == "touched_rows" else float(v)
                        for k, v in m.items()})
    return metrics, state


def _tiny_dlrm(vocabs=(4096, 2048, 1024, 512, 32), n_batches=3):
    """A tiny DLRM, its ROO batches, and the distinct valid ids each batch
    reads, per table path. A step reads 16 id slots of each user-side table
    (``t0``, ``t1``) and 64 of each item-side one: ``t2`` takes the touched
    rows at 1/16 of its rows, ``t3`` reads too many for its 512 rows and
    ``t4`` is below ``SPARSE_MIN_VOCAB``."""
    from repro.core.joiner import RequestLevelJoiner
    from repro.data.batcher import BatcherConfig, ROOBatcher
    from repro.data.events import EventSimulator, EventStreamConfig
    from repro.models.dlrm import (DLRMConfig, dlrm_batch_fields,
                                   dlrm_batcher_config, dlrm_init, dlrm_loss,
                                   dlrm_sample)
    cfg = DLRMConfig(n_dense=4, embed_dim=16, bot_mlp=(4, 32, 16),
                     top_mlp=(64, 32, 1), vocabs=vocabs, n_ro_fields=2,
                     multi_hot=2)
    stream = EventStreamConfig(n_requests=60, n_items=512, hist_init_max=12,
                               seed=0)
    samples = [dlrm_sample(s, cfg) for s in RequestLevelJoiner().join(
        list(EventSimulator(stream).stream()))]
    batches = list(ROOBatcher(dlrm_batcher_config(
        cfg, BatcherConfig(b_ro=8, b_nro=32))).batches(samples))[:n_batches]

    def valid_ids(batch):
        ro_ids, ro_len, nro_ids, nro_len = (
            np.asarray(a) for a in dlrm_batch_fields(cfg, batch))
        out = {}
        for ids, lens, first in ((ro_ids, ro_len, 0),
                                 (nro_ids, nro_len, cfg.n_ro_fields)):
            for j in range(ids.shape[1]):
                valid = np.arange(ids.shape[2])[None, :] < lens[:, j, None]
                out[("tables", f"t{first + j}")] = set(
                    ids[:, j][valid].tolist())
        return out

    return (lambda p, b, r: dlrm_loss(p, cfg, b),
            dlrm_init(jax.random.PRNGKey(0), cfg), batches, valid_ids)


def _tiny_gr():
    """A tiny hstu-gr ranking model, its ROO batches, and the distinct ids
    each batch's lookups read from ``item_emb`` (history slots and targets;
    ``act_emb`` has 4 rows)."""
    from repro.core.hstu import HSTUConfig
    from repro.models.gr import GRConfig, gr_init, gr_ranking_loss
    cfg = GRConfig(n_items=4096, hist_len=16, m_targets=8,
                   hstu=HSTUConfig(d_model=32, n_heads=2, d_qk=16, d_v=16,
                                   n_layers=1, max_rel_pos=24))

    def valid_ids(batch):
        ids = np.concatenate([
            np.asarray(batch.history_ids)[:, :cfg.hist_len].ravel(),
            np.asarray(batch.item_ids).ravel()])
        return {("item_emb",): set(np.clip(ids, 0, cfg.n_items - 1).tolist())}

    return (lambda p, b, r: gr_ranking_loss(p, cfg, b),
            gr_init(jax.random.PRNGKey(0), cfg),
            _roo_batches(n_items=cfg.n_items)[:3], valid_ids)


def _tiny_lsr(n_batches=3):
    """A tiny LSR, its ROO batches, and the distinct ids each batch's
    lookups read from ``item_emb`` (history slots and targets, 160 id slots
    a step; the other tables have 64 rows or fewer)."""
    from repro.core.hstu import HSTUConfig
    from repro.models.lsr import LSRConfig, lsr_init, lsr_loss
    cfg = LSRConfig(n_items=4096, n_user_cats=64, n_item_cats=64,
                    embed_dim=32, hist_len=16, mode="userarch_hstu",
                    lce_n_out=4, lce_d_out=32, n_cross_layers=2,
                    top_mlp=(64,),
                    hstu=HSTUConfig(d_model=32, n_heads=2, d_qk=16,
                                    d_v=16, n_layers=1, max_rel_pos=16))

    def valid_ids(batch):
        ids = np.concatenate([np.asarray(batch.history_ids).ravel(),
                              np.asarray(batch.item_ids).ravel()])
        return {("item_emb",): set(np.clip(ids, 0, cfg.n_items - 1).tolist())}

    return (lambda p, b, r: lsr_loss(p, cfg, b),
            lsr_init(jax.random.PRNGKey(0), cfg),
            _roo_batches(n_items=cfg.n_items)[:n_batches], valid_ids)


def _at(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _touched(loss, params, batch):
    """The param paths whose default gradient is a SparseRows."""
    _, grads = jax.jit(touched_rows_value_and_grad(
        loss, _mixed().rows_mask))(params, batch, jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_flatten_with_path(
        grads, is_leaf=lambda g: isinstance(g, SparseRows))[0]
    return {tuple(k.key for k in path) for path, g in flat
            if isinstance(g, SparseRows)}


MODELS = {"dlrm": _tiny_dlrm, "hstu-gr": _tiny_gr, "lsr": _tiny_lsr}


class TestTouchedRowsDefault:
    """The default step (a plain loss, no ``value_and_grad_fn``) against the
    same step given an explicit dense ``jax.value_and_grad(loss)``."""

    @pytest.mark.parametrize("model", sorted(MODELS))
    def test_matches_the_dense_step(self, model):
        loss, params, batches, _ = MODELS[model]()
        m_t, s_t = _steps(loss, params, batches)
        m_d, s_d = _steps(loss, params, batches, jax.value_and_grad(loss))
        for a, b in zip(m_t, m_d):
            np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-6)
            np.testing.assert_allclose(a["grad_norm"], b["grad_norm"],
                                       rtol=1e-5)
        for key in ("params", "opt"):
            for (path, a), (_, b) in zip(
                    jax.tree_util.tree_flatten_with_path(s_t[key])[0],
                    jax.tree_util.tree_flatten_with_path(s_d[key])[0]):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7,
                    err_msg=f"{key} diverged at {path}")

    @pytest.mark.parametrize("model", sorted(MODELS))
    def test_untouched_rows_stay_bit_identical(self, model):
        loss, params, batches, valid_ids = MODELS[model]()
        _, state = _steps(loss, params, batches)
        paths = _touched(loss, params, batches[0])
        touched = {}
        for b in batches:
            for path, ids in valid_ids(b).items():
                if path in paths:
                    touched.setdefault(path, set()).update(ids)
        # row-wise Adagrad's accumulators, in the order make_mixed keeps them
        names = [tuple(getattr(k, "key", None) for k in p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(params)[0]
                 if default_is_embedding(tuple(str(k) for k in p))]
        acc = dict(zip(names, state["opt"]["emb"]["acc"]))
        checked = 0
        for path, ids in touched.items():
            start = np.asarray(_at(params, path))
            rest = np.setdiff1d(np.arange(start.shape[0]), sorted(ids))
            assert rest.size, path
            np.testing.assert_array_equal(
                np.asarray(_at(state["params"], path))[rest], start[rest])
            np.testing.assert_array_equal(np.asarray(acc[path])[rest], 0.0)
            moved = np.asarray(_at(state["params"], path)) != start
            assert moved[sorted(ids)].any(), path
            checked += 1
        assert checked

    @pytest.mark.parametrize("model", sorted(MODELS))
    def test_touched_rows_counts_distinct_valid_ids(self, model):
        loss, params, batches, valid_ids = MODELS[model]()
        metrics, _ = _steps(loss, params, batches)
        paths = _touched(loss, params, batches[0])
        assert paths
        for m, b in zip(metrics, batches):
            want = sum(len(ids) for path, ids in valid_ids(b).items()
                       if path in paths)
            assert m["touched_rows"] == want
        dense, _ = _steps(loss, params, batches, jax.value_and_grad(loss),
                          n=1)
        assert dense[0]["touched_rows"] == 0

    def test_paths_are_counted_in_the_registry(self):
        from repro.obs import metrics as obs_metrics
        loss, params, batches, _ = _tiny_dlrm()
        vag = touched_rows_value_and_grad(loss, _mixed().rows_mask)
        with obs_metrics.OBS_KNOB.scoped("metrics"):
            _, grads = jax.jit(vag)(params, batches[0], jax.random.PRNGKey(0))
        gauge = obs_metrics.gauge("train.tables")
        # t3 reads 64 id slots a step of 512 rows, over TOUCHED_MAX_FILL;
        # t4 has 32 rows
        assert gauge.value(path="touched_rows") == 3
        assert gauge.value(path="dense") == 2
        tables = grads["tables"]
        assert all(isinstance(tables[t], SparseRows)
                   for t in ("t0", "t1", "t2"))
        assert not any(isinstance(tables[t], SparseRows)
                       for t in ("t3", "t4"))

    @pytest.mark.parametrize("read", ["matmul", "einsum", "jnp_take"])
    def test_table_read_otherwise_stays_dense(self, read):
        """A table the loss also reads outside the collection's lookups
        keeps its dense gradient and still trains; the other table still
        takes the touched rows. ``jnp_take`` is a use the recording stand-in
        cannot follow: the tables are then recorded one at a time."""
        out_read = {
            "matmul": lambda h, t, ids: h @ t.T,
            "einsum": lambda h, t, ids: jnp.einsum("bd,vd->bv", h, t),
            "jnp_take": lambda h, t, ids: h @ jnp.take(t, ids, axis=0).T,
        }[read]
        params = {"emb": _rand_table(v=512, d=8, seed=1) * 0.1,
                  "out_emb": _rand_table(v=80, d=8, seed=2) * 0.1}
        ids = jax.random.randint(jax.random.PRNGKey(5), (6, 3), 0, 512)
        batch = {"ids": ids, "lens": jnp.full((6,), 3, jnp.int32),
                 "cand": jnp.arange(10)}

        def loss(p, b, r):
            h = ec.bag_lookup_dense(p["emb"], b["ids"], b["lens"])
            return jnp.mean(jax.nn.log_softmax(
                out_read(h, p["out_emb"], b["cand"]))[:, 0])

        _, grads = jax.jit(touched_rows_value_and_grad(
            loss, _mixed().rows_mask))(params, batch, jax.random.PRNGKey(0))
        assert isinstance(grads["emb"], SparseRows)
        assert not isinstance(grads["out_emb"], SparseRows)
        m_t, s_t = _steps(loss, params, [batch])
        m_d, s_d = _steps(loss, params, [batch], jax.value_and_grad(loss))
        assert m_t[-1]["loss"] < m_t[0]["loss"]
        assert [m["touched_rows"] for m in m_t] == [len(set(
            np.asarray(ids).ravel().tolist()))] * 3
        for a, b in zip(jax.tree.leaves(s_t), jax.tree.leaves(s_d)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)

    def test_enabled_plan_stays_dense(self):
        """Under an enabled sharding plan every table keeps the dense
        gradient (the touched-rows path gathers rows locally)."""
        from repro.distributed.sharding import plan_for_mesh
        from repro.launch.mesh import auto_mesh
        from repro.obs import metrics as obs_metrics
        loss, params, batches, _ = _tiny_dlrm()
        plan = plan_for_mesh(auto_mesh((1, 1), ("data", "model")))
        assert plan.enabled
        with obs_metrics.OBS_KNOB.scoped("metrics"):
            m_p, s_p = _steps(loss, params, batches, plan=plan)
        gauge = obs_metrics.gauge("train.tables")
        assert gauge.value(path="touched_rows") == 0
        assert gauge.value(path="dense") == 5
        assert [m["touched_rows"] for m in m_p] == [0, 0, 0]
        _, s_d = _steps(loss, params, batches, jax.value_and_grad(loss))
        for a, b in zip(jax.tree.leaves(s_p), jax.tree.leaves(s_d)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_no_table_shaped_broadcast(self):
        """The default step of a tiny DLRM holds no dense gradient of its
        4,099-row table: no broadcast (the zero fill of a dense table
        gradient) to the table's shape, in the lowered program or the
        compiled one. The explicit dense step has them."""
        import re
        from repro.train.loop import make_train_step
        loss, params, batches, _ = _tiny_dlrm(vocabs=(4099, 256, 64, 32, 16))
        opt = _mixed()
        state = {"params": params, "opt": opt.init(params),
                 "step": jnp.zeros((), jnp.int32)}

        def broadcasts(vag):
            low = make_train_step(loss, opt, value_and_grad_fn=vag).lower(
                state, batches[0], jax.random.PRNGKey(0))
            return (len(re.findall(r"broadcast_in_dim.*-> tensor<4099x16xf32>",
                                   low.as_text())),
                    len(re.findall(r"f32\[4099,16\]\{[^}]*\} broadcast\(",
                                   low.compile().as_text())))
        assert broadcasts(None) == (0, 0)
        assert min(broadcasts(jax.value_and_grad(loss))) > 0
