"""Incremental user-state serving (paper §2.2 applied to inference).

Parity contract: a request scored through the cached-prefix path — per-user
K/V state extended with only the request's new events — must equal the full
recompute. On the jnp backends the match is bit-exact by construction
(row-wise ops are row-count invariant; masked attention entries contribute
exact zeros; the 1/n normalizer is pinned to the full-sequence length); the
Pallas kernel matches within float tolerance.

Layers under test, bottom up:
  * kernel   — dispatch.hstu_attention_prefix backends vs the dense oracle;
  * model    — gr_score_from_state / gr_extend_user_state vs
               gr_ranking_logits (extend-from-empty and two-step);
  * store    — UserStateStore epoch/digest/LRU semantics + obs mirror;
  * engine   — ScoringEngine state-store routing: cold, repeat, eviction,
               param hot-swap, window slide — each vs a stateless engine;
  * adapter  — ServeAdapter capability contract for every servable arch.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hstu import HSTUConfig
from repro.core.joiner import ROOSample
from repro.core.masks import prefix_spec
from repro.data.batcher import BatcherConfig, ROOBatcher
from repro.kernels import dispatch
from repro.models.gr import (GRConfig, gr_extend_user_state, gr_init,
                             gr_ranking_logits, gr_score_from_state,
                             gr_state_init)
from repro.serve.adapter import ServeAdapter
from repro.serve.engine import EnginePolicy, ScoringEngine
from repro.serve.user_cache import UserStateStore, history_digest

# tiny GR: big enough for 2 layers / 2 heads of real HSTU, small enough
# that every test jit-compiles in well under a second
TINY = GRConfig(
    n_items=60,
    hstu=HSTUConfig(d_model=16, n_heads=2, d_qk=8, d_v=8, n_layers=2,
                    max_rel_pos=8),
    hist_len=8, m_targets=4)


def mk_req(uid: int, hist, items) -> ROOSample:
    hist = [int(x) for x in hist]
    return ROOSample(
        request_id=uid, user_id=uid,
        ro_dense=np.full((4,), float(uid), np.float32),
        ro_idlist=[uid % 7 + 1],
        history_ids=hist, history_actions=[h % 4 for h in hist],
        item_ids=[int(i) for i in items],
        item_dense=[np.full((4,), float(i), np.float32) for i in items],
        item_idlist=[[int(i) % 5 + 1] for i in items],
        labels=[{"click": 0.0, "view_sec": 0.0} for _ in items])


def first_batch(samples, b_ro=4, b_nro=16, hist_len=8):
    return next(iter(ROOBatcher(
        BatcherConfig(b_ro=b_ro, b_nro=b_nro, hist_len=hist_len)
    ).batches(samples)))


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------

def _kernel_inputs(seed=0, b=3, h=2, n_hist=16, n_new=8, m=4,
                   dqk=8, dv=8, max_rel=16):
    """Random inputs with ragged per-request prefixes honoring the engine
    contract prefix <= effective history length."""
    r = np.random.RandomState(seed)
    q = jnp.asarray(r.normal(size=(b, h, n_new + m, dqk)).astype(np.float32))
    k = jnp.asarray(r.normal(size=(b, h, n_hist + m, dqk)).astype(np.float32))
    v = jnp.asarray(r.normal(size=(b, h, n_hist + m, dv)).astype(np.float32))
    rab = jnp.asarray(
        r.normal(size=(h, 2 * max_rel + 1)).astype(np.float32))
    hl = r.randint(0, n_hist + 1, size=b)
    pfx = np.array([r.randint(0, x + 1) for x in hl])
    new = np.minimum(hl - pfx, n_new)
    tgt = r.randint(0, m + 1, size=b)
    spec = prefix_spec(jnp.asarray(pfx, jnp.int32), jnp.asarray(new, jnp.int32),
                       jnp.asarray(tgt, jnp.int32), n_hist, n_new)
    return q, k, v, rab, spec, max_rel


class TestPrefixKernelParity:
    def test_jnp_chunked_matches_ref(self):
        # cross-backend: float tolerance (contraction order differs); the
        # bit-exact claim is incremental-vs-full on the SAME backend, which
        # the model/engine classes below assert with assert_array_equal
        q, k, v, rab, spec, mr = _kernel_inputs()
        ref = dispatch.hstu_attention_prefix(
            q, k, v, rab, spec, backend="jnp-dense", scale_len=20,
            max_rel_pos=mr)
        chunked = dispatch.hstu_attention_prefix(
            q, k, v, rab, spec, backend="jnp-chunked", scale_len=20,
            max_rel_pos=mr, block_q=4)
        np.testing.assert_allclose(np.asarray(chunked), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_pallas_interpret_matches_ref(self):
        q, k, v, rab, spec, mr = _kernel_inputs(seed=1)
        ref = dispatch.hstu_attention_prefix(
            q, k, v, rab, spec, backend="jnp-dense", scale_len=20,
            max_rel_pos=mr)
        pal = dispatch.hstu_attention_prefix(
            q, k, v, rab, spec, backend="pallas-interpret", scale_len=20,
            max_rel_pos=mr, block_q=8, block_k=8)
        np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_no_rab_path(self):
        q, k, v, _, spec, mr = _kernel_inputs(seed=2)
        ref = dispatch.hstu_attention_prefix(
            q, k, v, None, spec, backend="jnp-dense", scale_len=20,
            max_rel_pos=mr)
        chunked = dispatch.hstu_attention_prefix(
            q, k, v, None, spec, backend="jnp-chunked", scale_len=20,
            max_rel_pos=mr, block_q=4)
        np.testing.assert_allclose(np.asarray(chunked), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_invalid_rows_are_zero(self):
        # rows past a request's new_count/target_count are padding; every
        # backend must emit exact zeros there (they land in the K/V cache)
        q, k, v, rab, spec, mr = _kernel_inputs(seed=3)
        out = np.asarray(dispatch.hstu_attention_prefix(
            q, k, v, rab, spec, backend="jnp-chunked", scale_len=20,
            max_rel_pos=mr))
        n_new = spec.n_new
        for bi in range(out.shape[0]):
            nc = int(spec.new_counts[bi])
            tc = int(spec.target_counts[bi])
            np.testing.assert_array_equal(out[bi, :, nc:n_new], 0.0)
            np.testing.assert_array_equal(out[bi, :, n_new + tc:], 0.0)


# ---------------------------------------------------------------------------
# model-level parity (GR)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gr_setup():
    params = gr_init(jax.random.PRNGKey(0), TINY)
    reqs = [mk_req(1, [], [5, 6]),                    # empty history
            mk_req(2, [3, 1, 4, 1, 5], [7]),
            mk_req(3, [2, 7, 1, 8, 2, 8, 1, 8], [9, 10, 11]),   # full window
            mk_req(4, [1, 2], [12, 13, 14, 15])]
    return params, first_batch(reqs)


def _stacked_empty_state(batch):
    one = jax.tree.map(np.asarray, gr_state_init(TINY))
    return jax.tree.map(
        lambda a: jnp.asarray(np.stack([a] * batch.b_ro)), one)


class TestGRStateParity:
    def test_extend_from_empty_is_full_forward(self, gr_setup):
        params, batch = gr_setup
        want = gr_ranking_logits(params, TINY, batch)
        got, st = gr_score_from_state(params, TINY, batch,
                                      _stacked_empty_state(batch),
                                      n_new=TINY.hist_len)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        lengths = np.minimum(np.asarray(batch.history_lengths), TINY.hist_len)
        np.testing.assert_array_equal(np.asarray(st.length), lengths)

    def test_two_step_incremental_is_bit_exact(self, gr_setup):
        params, batch = gr_setup
        want = gr_ranking_logits(params, TINY, batch)
        lengths = np.minimum(np.asarray(batch.history_lengths), TINY.hist_len)
        pfx = jnp.asarray(lengths // 2, jnp.int32)
        # step 1: prewarm the state with only the first half of each history
        batch1 = dataclasses.replace(batch, history_lengths=pfx)
        st1 = gr_extend_user_state(params, TINY, batch1,
                                   _stacked_empty_state(batch),
                                   n_new=TINY.hist_len)
        np.testing.assert_array_equal(np.asarray(st1.length), lengths // 2)
        # step 2: score the full request from the half-warm state
        got, st2 = gr_score_from_state(params, TINY, batch, st1,
                                       n_new=TINY.hist_len)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(st2.length), lengths)

    def test_two_step_cache_matches_one_shot_cache(self, gr_setup):
        params, batch = gr_setup
        _, st_full = gr_score_from_state(params, TINY, batch,
                                         _stacked_empty_state(batch),
                                         n_new=TINY.hist_len)
        lengths = np.minimum(np.asarray(batch.history_lengths), TINY.hist_len)
        pfx = jnp.asarray(lengths // 2, jnp.int32)
        st1 = gr_extend_user_state(
            params, TINY, dataclasses.replace(batch, history_lengths=pfx),
            _stacked_empty_state(batch), n_new=TINY.hist_len)
        _, st2 = gr_score_from_state(params, TINY, batch, st1,
                                     n_new=TINY.hist_len)
        # the K/V cache is bit-identical on every resident position
        for li in range(TINY.hstu.n_layers):
            for bi in range(batch.b_ro):
                n = int(lengths[bi])
                np.testing.assert_array_equal(
                    np.asarray(st2.k)[bi, li, :n],
                    np.asarray(st_full.k)[bi, li, :n])
                np.testing.assert_array_equal(
                    np.asarray(st2.v)[bi, li, :n],
                    np.asarray(st_full.v)[bi, li, :n])


# ---------------------------------------------------------------------------
# state store semantics
# ---------------------------------------------------------------------------

class TestUserStateStore:
    def test_miss_then_hit(self):
        store = UserStateStore(capacity=4)
        s = mk_req(1, [3, 1, 4], [9])
        p = store.probe(s, epoch=0, hist_cap=8)
        assert p.prefix_len == 0 and p.state is None and p.eff_len == 3
        store.put(1, 0, p.eff_len, p.digest, {"x": np.ones(2)})
        p2 = store.probe(s, epoch=0, hist_cap=8)
        assert p2.prefix_len == 3 and p2.state is not None
        assert store.stats.hits == 1 and store.stats.misses == 1

    def test_prefix_reuse_on_grown_history(self):
        store = UserStateStore(capacity=4)
        s1 = mk_req(1, [3, 1, 4], [9])
        p1 = store.probe(s1, 0, 8)
        store.put(1, 0, p1.eff_len, p1.digest, "state")
        s2 = mk_req(1, [3, 1, 4, 1, 5], [9])       # two appended events
        p2 = store.probe(s2, 0, 8)
        assert p2.prefix_len == 3 and p2.eff_len == 5

    def test_rewritten_history_is_a_mismatch(self):
        store = UserStateStore(capacity=4)
        s1 = mk_req(1, [3, 1, 4], [9])
        p1 = store.probe(s1, 0, 8)
        store.put(1, 0, p1.eff_len, p1.digest, "state")
        s2 = mk_req(1, [9, 9, 9, 1], [9])          # history rewritten
        p2 = store.probe(s2, 0, 8)
        assert p2.prefix_len == 0 and p2.state is None
        assert store.stats.prefix_mismatches == 1
        assert 1 not in store                      # dropped, not kept stale

    def test_window_slide_is_a_mismatch(self):
        store = UserStateStore(capacity=4)
        hist = list(range(1, 9))                   # exactly hist_cap events
        p1 = store.probe(mk_req(1, hist, [9]), 0, 8)
        store.put(1, 0, p1.eff_len, p1.digest, "state")
        p2 = store.probe(mk_req(1, hist + [9], [9]), 0, 8)  # window slides
        assert p2.prefix_len == 0
        assert store.stats.prefix_mismatches == 1

    def test_epoch_mismatch_drops_entry(self):
        store = UserStateStore(capacity=4)
        s = mk_req(1, [3, 1], [9])
        p = store.probe(s, 0, 8)
        store.put(1, 0, p.eff_len, p.digest, "state")
        p2 = store.probe(s, 1, 8)                  # weights swapped
        assert p2.prefix_len == 0 and len(store) == 0
        assert store.stats.invalidations == 1

    def test_invalidate_epoch_sweeps(self):
        store = UserStateStore(capacity=8)
        for uid in range(3):
            s = mk_req(uid, [uid + 1], [9])
            p = store.probe(s, 0, 8)
            store.put(uid, 0, p.eff_len, p.digest, "state")
        assert store.invalidate_epoch(current_epoch=1) == 3
        assert len(store) == 0

    def test_lru_eviction(self):
        store = UserStateStore(capacity=2)
        for uid in (1, 2):
            s = mk_req(uid, [uid], [9])
            p = store.probe(s, 0, 8)
            store.put(uid, 0, p.eff_len, p.digest, "state")
        store.probe(mk_req(1, [1], [9]), 0, 8)     # 1 now most-recent
        p3 = store.probe(mk_req(3, [3], [9]), 0, 8)
        store.put(3, 0, p3.eff_len, p3.digest, "state")
        assert 2 not in store and 1 in store
        assert store.stats.evictions == 1

    def test_obs_mirror(self):
        from repro.obs import metrics as obs_metrics
        store = UserStateStore(capacity=2)
        store.probe(mk_req(1, [1], [9]), 0, 8)
        snap = obs_metrics.snapshot()["components"].get("serve.user_state")
        assert snap is not None
        assert snap["misses"] == 1 and snap["capacity"] == 2

    def test_history_digest_is_order_sensitive(self):
        assert history_digest([1, 2], [0, 1]) != history_digest([2, 1], [0, 1])
        assert history_digest([1, 2], [0, 1]) != history_digest([1, 2], [1, 0])
        assert history_digest([], []) == history_digest([], [])


# ---------------------------------------------------------------------------
# engine routing
# ---------------------------------------------------------------------------

def _gr_adapter(cfg=TINY):
    return ServeAdapter(
        score=lambda p, b: gr_ranking_logits(p, cfg, b),
        init_user_state=lambda: gr_state_init(cfg),
        extend_user_state=lambda p, b, s, *, n_new:
            gr_extend_user_state(p, cfg, b, s, n_new=n_new),
        score_from_state=lambda p, b, s, *, n_new:
            gr_score_from_state(p, cfg, b, s, n_new=n_new),
        state_hist_len=cfg.hist_len)


@pytest.fixture(scope="module")
def gr_params():
    return gr_init(jax.random.PRNGKey(0), TINY)


def _engine_pair(params, capacity=32):
    policy = EnginePolicy(max_requests=4, max_impressions=32,
                          hist_len=TINY.hist_len)
    full = ScoringEngine(params, adapter=_gr_adapter(), policy=policy)
    inc = ScoringEngine(params, adapter=_gr_adapter(), policy=policy,
                        state_store=UserStateStore(capacity))
    return full, inc


def _assert_parity(full, inc, reqs):
    want = full.score_requests(reqs)
    got = inc.score_requests(reqs)
    assert len(got) == len(want)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    return got


class TestIncrementalEngine:
    def test_cold_traffic_matches_full(self, gr_params):
        full, inc = _engine_pair(gr_params)
        reqs = [mk_req(1, [], [5, 6]),              # empty history
                mk_req(2, [3, 1, 4], [7]),
                mk_req(3, list(range(1, 9)), [9, 10])]
        _assert_parity(full, inc, reqs)
        assert inc.stats.n_incremental_batches > 0
        assert inc.state_store.stats.misses == 3

    def test_repeat_traffic_extends_state(self, gr_params):
        full, inc = _engine_pair(gr_params)
        hists = {1: [3, 1], 2: [2, 7, 1]}
        _assert_parity(full, inc,
                       [mk_req(u, h, [u + 5]) for u, h in hists.items()])
        for wave in range(3):                      # each wave appends events
            for u in hists:
                hists[u] = hists[u] + [wave + 1]
            _assert_parity(full, inc,
                           [mk_req(u, h, [u + 5, u + 6])
                            for u, h in hists.items()])
        assert inc.state_store.stats.hits >= 6     # 2 users x 3 repeat waves
        assert inc.state_store.stats.prefix_mismatches == 0

    def test_single_event_extends(self, gr_params):
        full, inc = _engine_pair(gr_params)
        inc.score_requests([mk_req(1, [3, 1, 4], [5])])
        got = _assert_parity(full, inc, [mk_req(1, [3, 1, 4, 1], [5, 6])])
        assert got[0].shape == (2, TINY.n_tasks)
        assert inc.state_store.stats.hits == 1

    def test_eviction_recompute_recache(self, gr_params):
        full, inc = _engine_pair(gr_params, capacity=1)
        r1, r2 = mk_req(1, [3, 1, 4], [5]), mk_req(2, [2, 7], [6])
        for _ in range(3):                         # alternate: evict each time
            _assert_parity(full, inc, [r1])
            _assert_parity(full, inc, [r2])
        assert inc.state_store.stats.evictions >= 4
        # re-cached after eviction: a hit needs the entry back in the store
        _assert_parity(full, inc, [r2])
        assert inc.state_store.stats.hits >= 1

    def test_param_hot_swap_invalidates_and_matches(self, gr_params):
        full, inc = _engine_pair(gr_params)
        reqs = [mk_req(1, [3, 1, 4], [5]), mk_req(2, [2], [6, 7])]
        _assert_parity(full, inc, reqs)
        assert len(inc.state_store) == 2
        new_params = gr_init(jax.random.PRNGKey(7), TINY)
        full.params = new_params
        inc.params = new_params
        assert len(inc.state_store) == 0           # stale states dropped
        assert inc.param_epoch == 1
        _assert_parity(full, inc, reqs)            # recomputed under new params

    def test_window_slide_falls_back_to_recompute(self, gr_params):
        full, inc = _engine_pair(gr_params)
        hist = list(range(1, 9))                   # exactly hist_len events
        _assert_parity(full, inc, [mk_req(1, hist, [5])])
        # two more events: the batcher window slides, the cached prefix is
        # no longer a prefix of the served history -> full recompute
        _assert_parity(full, inc, [mk_req(1, hist + [9, 10], [5, 6])])
        assert inc.state_store.stats.prefix_mismatches == 1
        # and the recomputed state is re-usable again
        _assert_parity(full, inc, [mk_req(1, hist + [9, 10], [7])])
        assert inc.state_store.stats.hits >= 1

    def test_state_store_needs_stateful_adapter(self, gr_params):
        stateless = ServeAdapter(
            score=lambda p, b: gr_ranking_logits(p, TINY, b))
        with pytest.raises(ValueError):
            ScoringEngine(gr_params, adapter=stateless,
                          state_store=UserStateStore(4))

    def test_state_store_excludes_user_cache(self, gr_params):
        from repro.serve.user_cache import UserTowerCache
        with pytest.raises(ValueError):
            ScoringEngine(gr_params, adapter=_gr_adapter(),
                          policy=EnginePolicy(hist_len=TINY.hist_len),
                          cache=UserTowerCache(4),
                          state_store=UserStateStore(4))

    def test_hist_len_mismatch_rejected(self, gr_params):
        with pytest.raises(ValueError):
            ScoringEngine(gr_params, adapter=_gr_adapter(),
                          policy=EnginePolicy(hist_len=16),
                          state_store=UserStateStore(4))

    def test_snapshot_covers_state_store(self, gr_params):
        _, inc = _engine_pair(gr_params)
        inc.score_requests([mk_req(1, [3], [5])])
        snap = inc.snapshot()
        assert snap["param_epoch"] == 0
        assert snap["state_store"]["size"] == 1
        assert snap["state_store"]["misses"] == 1


# ---------------------------------------------------------------------------
# adapter conformance (every servable arch through the first-class interface)
# ---------------------------------------------------------------------------

SERVABLE = ("roo-lsr", "roo-esr", "roo-retrieval", "hstu-gr",
            "dien", "mind", "bert4rec")


class TestAdapterConformance:
    @pytest.mark.parametrize("arch", SERVABLE)
    def test_bundle_exposes_serve_adapter(self, arch):
        from repro.configs.registry import scenario
        from repro.scenario.build import build_model
        spec = scenario(arch, {"model.n_items": 300})
        bundle = build_model(spec, jax.random.PRNGKey(0))
        ad = bundle.serve
        assert isinstance(ad, ServeAdapter)
        assert callable(ad.score)
        # legacy aliases stay importable call-sites (benchmarks, examples)
        assert ad.score_fn is ad.score
        assert ad.user_fn is ad.user_repr
        if ad.supports_user_cache:
            assert callable(ad.user_repr) and callable(ad.score_from_user)
        if ad.supports_incremental:
            assert callable(ad.init_user_state)
            assert callable(ad.score_from_state)
            assert ad.state_hist_len > 0

    def test_capability_matrix(self):
        from repro.configs.registry import scenario
        from repro.scenario.build import build_model
        caps = {}
        for arch in SERVABLE:
            bundle = build_model(scenario(arch, {"model.n_items": 300}),
                                 jax.random.PRNGKey(0))
            caps[arch] = (bundle.serve.supports_user_cache,
                          bundle.serve.supports_incremental)
        assert caps["hstu-gr"] == (True, True)     # the stateful arch
        for arch in ("roo-lsr", "roo-esr", "roo-retrieval"):
            assert caps[arch] == (True, False)     # split halves, stateless
        for arch in ("dien", "mind", "bert4rec"):
            assert caps[arch] == (False, False)    # fused forward only

    def test_spec_rejects_incremental_plus_user_cache(self):
        from repro.configs.registry import scenario
        from repro.scenario.spec import ScenarioValidationError
        with pytest.raises(ScenarioValidationError):
            scenario("hstu-gr", {"serve.incremental": True,
                                 "serve.cache_user_tower": True})

    def test_engine_from_scenario_rejects_stateless_incremental(self):
        from repro.configs.registry import scenario
        from repro.scenario.spec import ScenarioValidationError
        spec = scenario("dien", {"serve.incremental": True,
                                 "model.n_items": 300})
        with pytest.raises(ScenarioValidationError):
            ScoringEngine.from_scenario(spec)


class TestEngineFromScenarioIncremental:
    def test_end_to_end_repeat_traffic(self):
        from repro.configs.registry import scenario
        from repro.scenario.build import build_samples
        spec = scenario("hstu-gr", {"data.n_requests": 12,
                                    "model.n_items": 300,
                                    "serve.incremental": True,
                                    "serve.state_capacity": 16})
        engine = ScoringEngine.from_scenario(spec)
        requests = build_samples(spec)[:8]
        scores = engine.score_requests(requests)
        assert len(scores) == len(requests)
        assert all(s.shape[0] == r.num_impressions
                   for r, s in zip(requests, scores))
        again = engine.score_requests(requests)    # repeat: all prefixes hit
        assert engine.state_store.stats.hits > 0
        assert engine.stats.n_incremental_batches > 0
        # The repeat pass attends from the target rows alone against the
        # cached K/V: the same sums, but in matmuls of another row count.
        # XLA's CPU dot picks its reduction blocking from the shape, so the
        # history sum is reassociated (a few ulps per output; the cold pass,
        # which has the full shape, is bit-equal to the stateless engine).
        # Bound: 16 ulps of f32 at the logits' O(1) scale.
        for a, b in zip(scores, again):
            np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)
