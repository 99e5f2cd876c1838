"""SPMD multi-device training, run for real on a CPU-simulated mesh.

Run with XLA_FORCE_HOST_PLATFORM_DEVICE_COUNT=8 (conftest translates the
env var into the XLA flag before jax initializes — the tier1-multidevice CI
job does exactly this); under the default single-device run the whole
module skips.

Covers the acceptance contract of the SPMD tentpole:
  * N-device loss/metrics parity with single-device training over >= 50
    steps, for LSR and GR, through the full jit'd train step (sharded
    params + optimizer state, psum embedding lookups, data-axis batches);
  * sharded checkpoint save/restore roundtrip, including resume onto a
    DIFFERENT mesh shape and bit-continuation of training there;
  * the compiled HLO of the sharded LSR RO tower contains the all-reduce
    the row-sharded RO tables' psum implies (and the replicated path
    doesn't);
  * the prefetch loader places batches per-shard (no replicated copies)
    when given a sharding.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hstu import HSTUConfig
from repro.core.joiner import RequestLevelJoiner
from repro.data.batcher import BatcherConfig, ROOBatcher
from repro.data.events import EventSimulator, EventStreamConfig
from repro.distributed import spmd
from repro.distributed.sharding import plan_for_mesh, replicated_plan
from repro.launch.mesh import make_test_mesh
from repro.models.gr import GRConfig, gr_init, gr_ranking_loss
from repro.models.lsr import LSRConfig, lsr_init, lsr_loss, lsr_user_repr
from repro.train.checkpoint import CheckpointManager
from repro.train.loop import make_train_step
from repro.train.optim import (adam, default_is_embedding, make_mixed,
                               rowwise_adagrad)

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices: run with XLA_FORCE_HOST_PLATFORM_DEVICE_COUNT=8")

N_PARITY_STEPS = 50


def _distinct_shard_blocks(arr) -> int:
    """Number of distinct row blocks an array is split into (slices are
    unhashable pre-3.12, hence the tuple dance)."""
    return len({tuple((s.start, s.stop) for s in sh.index)
                for sh in arr.addressable_shards})


@pytest.fixture(scope="module")
def mesh():
    return make_test_mesh(2, 4)          # the 2x4 CI mesh: data=2, model=4


@pytest.fixture(scope="module")
def plan(mesh):
    return plan_for_mesh(mesh)


@pytest.fixture(scope="module")
def dist_batches():
    stream = EventStreamConfig(n_requests=60, n_items=512, hist_init_max=12,
                               seed=0)
    samples = RequestLevelJoiner().join(list(EventSimulator(stream).stream()))
    cfg = BatcherConfig(b_ro=8, b_nro=32, hist_len=16, n_shards=2,
                        ro_idlist_capacity=256, item_idlist_capacity=512)
    return list(ROOBatcher(cfg).batches(samples))


def _lsr_cfg():
    # vocabs divide model=4 and clear spmd.SHARD_MIN_ROWS, so item_emb and
    # user_cat_emb genuinely row-shard while act_emb stays replicated
    return LSRConfig(n_items=512, n_user_cats=64, n_item_cats=64,
                     embed_dim=32, n_ro_dense=16, n_item_dense=8, hist_len=16,
                     mode="userarch_hstu", lce_n_out=4, lce_d_out=32,
                     n_cross_layers=2, top_mlp=(64,),
                     hstu=HSTUConfig(d_model=32, n_heads=2, d_qk=16, d_v=16,
                                     n_layers=1, max_rel_pos=16))


def _gr_cfg():
    return GRConfig(n_items=512, hist_len=16, m_targets=8,
                    hstu=HSTUConfig(d_model=32, n_heads=2, d_qk=16, d_v=16,
                                    n_layers=1, max_rel_pos=24))


def _train(loss_with_plan, params, batches, plan_, n_steps,
           ckpt_dir=None, ckpt_every=None):
    """Run n_steps of the real train step; returns (losses, final state)."""
    opt = make_mixed(adam(1e-3), rowwise_adagrad(0.05), default_is_embedding)
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    shardings = spmd.state_shardings(state, plan_) if plan_ is not None \
        else None
    if shardings is not None:
        state = jax.device_put(state, shardings)
    step_fn = make_train_step(lambda p, b, r: loss_with_plan(p, b, plan_),
                              opt, plan=plan_, state_shardings=shardings)
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    rng = jax.random.PRNGKey(7)
    losses = []
    for i in range(n_steps):
        batch = spmd.place_batch(batches[i % len(batches)], plan_)
        state, metrics = step_fn(state, batch, jax.random.fold_in(rng, i))
        losses.append(float(metrics["loss"]))
        if mgr is not None and (i + 1) % ckpt_every == 0:
            mgr.save(i + 1, state)
    return np.asarray(losses), state


class TestLossParity:
    """N-device training == single-device training, through real psums."""

    def _check(self, loss_with_plan, params, batches, plan_):
        losses_1, state_1 = _train(loss_with_plan, params, batches, None,
                                   N_PARITY_STEPS)
        losses_n, state_n = _train(loss_with_plan, params, batches, plan_,
                                   N_PARITY_STEPS)
        np.testing.assert_allclose(losses_n, losses_1, rtol=2e-4, atol=1e-6)
        # final params agree too (the stronger statement: every update path
        # — psum lookups, sharded adam/adagrad — stayed on-trajectory)
        for (path, a), (_, b) in zip(
                jax.tree_util.tree_flatten_with_path(state_1["params"])[0],
                jax.tree_util.tree_flatten_with_path(state_n["params"])[0]):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-3, atol=2e-4,
                err_msg=f"param diverged at {path}")

    def test_lsr_parity_50_steps(self, plan, dist_batches):
        cfg = _lsr_cfg()
        params = lsr_init(jax.random.PRNGKey(0), cfg)
        self._check(lambda p, b, pl: lsr_loss(p, cfg, b, plan=pl),
                    params, dist_batches, plan)

    def test_gr_parity_50_steps(self, plan, dist_batches):
        cfg = _gr_cfg()
        params = gr_init(jax.random.PRNGKey(1), cfg)
        self._check(lambda p, b, pl: gr_ranking_loss(p, cfg, b, plan=pl),
                    params, dist_batches, plan)

    def test_tables_actually_sharded(self, plan):
        cfg = _lsr_cfg()
        params = lsr_init(jax.random.PRNGKey(0), cfg)
        placed = jax.device_put(params, spmd.state_shardings(params, plan))
        spec = placed["item_emb"].sharding.spec
        assert tuple(spec) == ("model", None)
        # 4 model shards x 2 data-axis replicas, 128 rows each
        assert _distinct_shard_blocks(placed["item_emb"]) == 4
        # tiny action vocab stays replicated
        assert tuple(placed["act_emb"].sharding.spec) in ((), (None, None))


class TestDLRMShardedLookups:
    def test_forward_parity(self, plan):
        """DLRM field bags through the psum path == replicated forward."""
        from repro.models.dlrm import DLRMConfig, dlrm_forward_roo, dlrm_init
        cfg = DLRMConfig(n_dense=4, embed_dim=32, bot_mlp=(4, 32, 32),
                         top_mlp=(64, 32, 1), vocabs=(256, 128, 64, 8),
                         n_ro_fields=2, multi_hot=2)
        params = dlrm_init(jax.random.PRNGKey(0), cfg)
        r = np.random.RandomState(0)
        b_ro, b_nro = 8, 32
        ro_dense = jnp.asarray(r.normal(size=(b_ro, 4)).astype(np.float32))
        ro_ids = jnp.asarray(r.randint(0, 64, (b_ro, 2, 2)).astype(np.int32))
        ro_len = jnp.full((b_ro, 2), 2, jnp.int32)
        nro_ids = jnp.asarray(r.randint(0, 8, (b_nro, 2, 2)).astype(np.int32))
        nro_len = jnp.full((b_nro, 2), 2, jnp.int32)
        seg = jnp.repeat(jnp.arange(b_ro, dtype=jnp.int32), b_nro // b_ro)
        args = (ro_dense, ro_ids, ro_len, nro_ids, nro_len, seg)
        ref = dlrm_forward_roo(params, cfg, *args)
        sh_params = jax.device_put(
            params, spmd.state_shardings(params, plan))
        sh_args = tuple(spmd.place_batch(a, plan) for a in args)
        out = jax.jit(lambda p, a: dlrm_forward_roo(p, cfg, *a, plan=plan))(
            sh_params, sh_args)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=1e-5)
        # the field bags declare out_sharded=True (dot_interaction contracts
        # over D), so sharded tables route through sharded_bag_lookup_rs —
        # the reduce-scatter must survive into the compiled HLO
        text = (jax.jit(lambda p, a: dlrm_forward_roo(p, cfg, *a, plan=plan))
                .lower(sh_params, sh_args).compile().as_text())
        assert "reduce-scatter" in text, \
            "expected the RS lookup's reduce-scatter in DLRM HLO"


class TestMicrobatchSPMD:
    def test_grad_accum_shards_real_batch_dim(self, plan, dist_batches):
        """With microbatches > 1 dim 0 is the scan axis: placement must
        shard dim 1 (the real batch dim), and the accumulated step must
        match single-device grad accumulation."""
        cfg = _lsr_cfg()
        params = lsr_init(jax.random.PRNGKey(0), cfg)
        opt = make_mixed(adam(1e-3), rowwise_adagrad(0.05),
                         default_is_embedding)
        mb = jax.tree.map(lambda a, b: jnp.stack([a, b]),
                          dist_batches[0], dist_batches[1])
        placed = spmd.place_batch(mb, plan, batch_dim=1)
        assert placed.ro_dense.sharding.spec == jax.sharding.PartitionSpec(
            None, ("data",), None)
        rng = jax.random.PRNGKey(3)

        def run(plan_, batch):
            state = {"params": params, "opt": opt.init(params),
                     "step": jnp.zeros((), jnp.int32)}
            sh = spmd.state_shardings(state, plan_) if plan_ else None
            if sh is not None:
                state = jax.device_put(state, sh)
            step = make_train_step(
                lambda p, b, r: lsr_loss(p, cfg, b, plan=plan_), opt,
                microbatches=2, plan=plan_, state_shardings=sh)
            losses = []
            for i in range(5):
                state, m = step(state, batch, jax.random.fold_in(rng, i))
                losses.append(float(m["loss"]))
            return losses

        np.testing.assert_allclose(run(plan, placed), run(None, mb),
                                   rtol=2e-4, atol=1e-6)


class TestShardedCheckpoint:
    def test_roundtrip_and_mesh_change(self, mesh, plan, dist_batches,
                                       tmp_path):
        cfg = _lsr_cfg()
        params = lsr_init(jax.random.PRNGKey(0), cfg)
        loss = lambda p, b, pl: lsr_loss(p, cfg, b, plan=pl)
        # 10 sharded steps, checkpoint at 5 and 10
        _, state_n = _train(loss, params, dist_batches, plan, 10,
                            ckpt_dir=str(tmp_path / "ck"), ckpt_every=5)
        mgr = CheckpointManager(str(tmp_path / "ck"))
        assert mgr.all_steps() == [5, 10]
        # per-shard format really happened (spec manifest committed)
        specs = mgr.saved_specs(10)
        assert any(s == ["model", None] for s in specs.values() if s)
        # roundtrip: host restore equals the live sharded state globally
        restored = mgr.restore(10)
        for (path, a), (_, b) in zip(
                jax.tree_util.tree_flatten_with_path(restored)[0],
                jax.tree_util.tree_flatten_with_path(state_n)[0]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"mismatch at {path}")

    def test_bfloat16_roundtrip(self, mesh, tmp_path):
        """ml_dtypes leaves degrade to raw void inside npz; the per-shard
        byte-view + manifest dtype must restore them exactly (incl. 0-d)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        table = (jnp.arange(512 * 8).reshape(512, 8) / 7.0).astype(
            jnp.bfloat16)
        state = {"tbl": jax.device_put(
                     table, NamedSharding(mesh, P("model", None))),
                 "s": jax.device_put(jnp.asarray(2.5, jnp.bfloat16),
                                     NamedSharding(mesh, P())),
                 "step": jnp.asarray(3)}
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(3, state)
        out = mgr.restore(3)
        assert str(out["tbl"].dtype) == "bfloat16"
        assert str(out["s"].dtype) == "bfloat16" and float(out["s"]) == 2.5
        np.testing.assert_array_equal(
            np.asarray(out["tbl"]).view(np.uint16),
            np.asarray(table).view(np.uint16))
        resharded = mgr.restore_sharded(make_test_mesh(4, 2), 3)
        assert resharded["tbl"].dtype == jnp.bfloat16
        assert tuple(resharded["tbl"].sharding.spec) == ("model", None)

    def test_resume_onto_different_mesh_shape(self, plan, dist_batches,
                                              tmp_path):
        """Save on (data=2, model=4), resume on (data=4, model=2); the
        resumed trajectory must match an uninterrupted single-device run."""
        cfg = _lsr_cfg()
        params = lsr_init(jax.random.PRNGKey(0), cfg)
        loss = lambda p, b, pl: lsr_loss(p, cfg, b, plan=pl)
        losses_full, _ = _train(loss, params, dist_batches, None, 16)

        _train(loss, params, dist_batches, plan, 8,
               ckpt_dir=str(tmp_path / "ck"), ckpt_every=8)
        mesh_b = make_test_mesh(4, 2)
        plan_b = plan_for_mesh(mesh_b)
        mgr = CheckpointManager(str(tmp_path / "ck"))
        state = mgr.restore_sharded(mesh_b)
        # saved specs re-applied on the new mesh: 2-way row shards now
        assert tuple(state["params"]["item_emb"].sharding.spec) == \
            ("model", None)
        assert _distinct_shard_blocks(state["params"]["item_emb"]) == 2
        # continue steps 8..16 on the new mesh
        state = jax.device_put(state, spmd.state_shardings(state, plan_b))
        opt = make_mixed(adam(1e-3), rowwise_adagrad(0.05),
                         default_is_embedding)
        step_fn = make_train_step(
            lambda p, b, r: loss(p, b, plan_b), opt, plan=plan_b,
            state_shardings=spmd.state_shardings(state, plan_b))
        rng = jax.random.PRNGKey(7)
        losses_resumed = []
        for i in range(8, 16):
            batch = spmd.place_batch(dist_batches[i % len(dist_batches)],
                                     plan_b)
            state, metrics = step_fn(state, batch, jax.random.fold_in(rng, i))
            losses_resumed.append(float(metrics["loss"]))
        np.testing.assert_allclose(losses_resumed, losses_full[8:],
                                   rtol=2e-4, atol=1e-6)


class TestDedupComposesWithPsum:
    """Request-level id dedup (embeddings/collection.py) must compose with
    the row-sharded psum lookup path: unique ids go through the sharded
    gather, duplicates expand locally, results match the replicated direct
    gather exactly."""

    def test_seq_lookup_dedup_sharded_parity(self, plan):
        from repro.embeddings import collection as ec
        table = jax.random.normal(jax.random.PRNGKey(0), (512, 32))
        # duplicate-heavy ids: 8 requests x 16 slots over a 40-id alphabet
        ids = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 40)
        want = jnp.take(table, ids, axis=0)
        sh_table = jax.device_put(
            table, jax.sharding.NamedSharding(
                plan.mesh, jax.sharding.PartitionSpec("model", None)))
        sh_ids = spmd.place_batch(ids, plan)
        out = jax.jit(lambda t, i: ec.seq_lookup(
            t, i, vocab=512, plan=plan, dedup=True))(sh_table, sh_ids)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        # and the composed path still lowers to the psum all-reduce
        text = (jax.jit(lambda t, i: ec.seq_lookup(
            t, i, vocab=512, plan=plan, dedup=True))
            .lower(sh_table, sh_ids).compile().as_text())
        assert "all-reduce" in text

    def test_lsr_loss_dedup_forced(self, plan, dist_batches):
        from repro.embeddings.collection import set_dedup_policy
        cfg = _lsr_cfg()
        params = lsr_init(jax.random.PRNGKey(0), cfg)
        batch = dist_batches[0]
        try:
            set_dedup_policy("never")
            want = float(lsr_loss(params, cfg, batch))
            set_dedup_policy("always")
            sh_params = jax.device_put(params,
                                       spmd.state_shardings(params, plan))
            sh_batch = spmd.place_batch(batch, plan)
            got = float(jax.jit(lambda p, b: lsr_loss(p, cfg, b, plan=plan))(
                sh_params, sh_batch))
        finally:
            set_dedup_policy(None)
        np.testing.assert_allclose(got, want, rtol=2e-5)


class TestCompressedOverlappedExchange:
    """ISSUE 10 acceptance: 50-step loss trajectories under the
    compressed/overlapped exchange (distributed/comms.py) vs the
    synchronous full-precision path, through the real sharded train step.

    Bounds here are the documented contract (docs/DISTRIBUTED.md):
    overlap+none is bit-comparable to the scan; for lossy wire formats
    the per-step loss perturbation is tiny (property-tested in
    test_comms.py) but compounds chaotically through 50 optimizer steps
    — a single-ulp perturbation already grows to ~2e-6 relative by step
    50 — so trajectory parity is asserted where it is well-posed:
    pointwise over the early trajectory (before amplification dominates)
    and on the 50-step trajectory mean.  Overlapped bf16 matches sync
    f32 within rtol 1e-2 on the trajectory mean (2e-2 pointwise over the
    first 10 steps); int8+error-feedback within 2e-2 mean / 5e-2 early
    pointwise.
    """

    def _stacked(self, dist_batches):
        # pairs of shards stacked on a leading microbatch axis (M=2)
        return [jax.tree.map(lambda a, b: jnp.stack([a, b]),
                             dist_batches[2 * i], dist_batches[2 * i + 1])
                for i in range(len(dist_batches) // 2)]

    def _train_comms(self, plan_, dist_batches, compress, overlap,
                     n_steps=N_PARITY_STEPS):
        from repro.distributed import comms
        from repro.scenario.knobs import UNSET
        cfg = _lsr_cfg()
        params = lsr_init(jax.random.PRNGKey(0), cfg)
        opt = make_mixed(adam(1e-3), rowwise_adagrad(0.01),
                         default_is_embedding)
        mbs = self._stacked(dist_batches)
        comms.COMPRESS_KNOB.set_default(compress)
        comms.OVERLAP_KNOB.set_default(overlap)
        try:
            state = {"params": params, "opt": opt.init(params),
                     "step": jnp.zeros((), jnp.int32)}
            if compress != "none":
                state["comms_ef"] = comms.ef_init(params, plan_)
                assert state["comms_ef"], "no compressible tables found"
            sh = (spmd.state_shardings(state, plan_)
                  if plan_ is not None else None)
            if sh is not None:
                state = jax.device_put(state, sh)
            step = make_train_step(
                lambda p, b, r: lsr_loss(p, cfg, b, plan=plan_), opt,
                microbatches=2, plan=plan_, state_shardings=sh)
            rng = jax.random.PRNGKey(7)
            losses = []
            for i in range(n_steps):
                batch = spmd.place_batch(mbs[i % len(mbs)], plan_,
                                         batch_dim=1)
                state, m = step(state, batch, jax.random.fold_in(rng, i))
                losses.append(float(m["loss"]))
            return np.asarray(losses), state
        finally:
            comms.COMPRESS_KNOB.set_default(UNSET)
            comms.OVERLAP_KNOB.set_default(UNSET)

    def test_overlap_none_bit_comparable(self, plan, dist_batches):
        """Unrolled (overlapped) accumulation vs the scan: identical
        float-op ORDER, so trajectories agree to the ulp — the only
        daylight is backend fusion choices inside the unrolled graph
        (observed <= 2e-6 relative over 50 steps on CPU), orders of
        magnitude inside the compression bounds."""
        sync, _ = self._train_comms(plan, dist_batches, "none", "off")
        ovl, _ = self._train_comms(plan, dist_batches, "none", "on")
        np.testing.assert_allclose(ovl, sync, rtol=5e-6, atol=5e-7)

    def test_bf16_overlap_matches_sync_f32(self, plan, dist_batches):
        sync, s_sync = self._train_comms(plan, dist_batches, "none", "off")
        bf16, s_bf16 = self._train_comms(plan, dist_batches, "bf16", "on")
        # early trajectory: pointwise, before chaotic amplification
        np.testing.assert_allclose(bf16[:10], sync[:10],
                                   rtol=2e-2, atol=2e-3)
        # full 50-step trajectory: rtol 1e-2 on the mean loss
        assert abs(bf16.mean() - sync.mean()) <= 1e-2 * sync.mean(), (
            bf16.mean(), sync.mean())
        # params stay close in aggregate: global relative drift over the
        # whole tree (per-element / per-leaf relative comparisons are
        # ill-posed for near-zero entries and zero-init biases under
        # chaotic trajectory divergence)
        diff_sq = tot_sq = 0.0
        for a, b in zip(jax.tree.leaves(s_sync["params"]),
                        jax.tree.leaves(s_bf16["params"])):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            diff_sq += float(np.sum((a - b) ** 2))
            tot_sq += float(np.sum(a ** 2))
        drift = (diff_sq / tot_sq) ** 0.5
        assert drift <= 0.1, f"global param drift {drift:.3g}"

    def test_int8_ef_within_documented_bound(self, plan, dist_batches):
        sync, _ = self._train_comms(plan, dist_batches, "none", "off")
        int8, state = self._train_comms(plan, dist_batches, "int8", "on")
        np.testing.assert_allclose(int8[:10], sync[:10],
                                   rtol=5e-2, atol=5e-3)
        assert abs(int8.mean() - sync.mean()) <= 2e-2 * sync.mean(), (
            int8.mean(), sync.mean())
        # the residual is live state: sharded like its table, checkpoint-
        # adjacent, and non-zero once quantization error accumulates
        ef = state["comms_ef"]["item_emb"]
        assert tuple(ef.sharding.spec) == ("model", None)
        assert float(jnp.max(jnp.abs(ef))) > 0.0

    def test_wire_accounting_and_obs_mirror(self, plan, dist_batches):
        from repro.distributed import comms
        from repro.obs import metrics as obs_metrics
        comms.STATS.reset()
        self._train_comms(plan, dist_batches, "int8", "on", n_steps=2)
        snap = comms.STATS.snapshot()
        # >= 2x on-wire reduction at int8 over every recorded exchange
        assert snap["compression_ratio"] >= 2.0, snap
        assert snap["overlap"]["enabled"]
        assert snap["overlap"]["occupancy"] == 0.5      # (m-1)/m, m=2
        assert snap["overlap"]["deferred_grad_exchanges_per_step"] == 1
        assert any(s["kind"] == "grad" for s in snap["sites"].values())
        # the unique-rows (dedup) route carried the compressed lookups
        assert snap["dedup_exchanges"] > 0
        # mirrored into the one obs snapshot
        assert obs_metrics.snapshot()["components"]["distributed.comms"][
            "compression_ratio"] >= 2.0


class TestShardedHLO:
    def test_ro_tower_hlo_has_model_allreduce(self, plan, dist_batches):
        """The RO (user) tower's compiled HLO must contain the all-reduce
        the row-sharded RO tables imply — the collective whose bytes ROO
        shrinks from B_NRO*D to B_RO*D."""
        cfg = _lsr_cfg()
        params = lsr_init(jax.random.PRNGKey(0), cfg)
        batch = dist_batches[0]

        sh_params = jax.device_put(params, spmd.state_shardings(params, plan))
        sh_batch = spmd.place_batch(batch, plan)
        text = (jax.jit(lambda p, b: lsr_user_repr(p, cfg, b, plan=plan))
                .lower(sh_params, sh_batch).compile().as_text())
        assert "all-reduce" in text, "expected psum all-reduce in RO tower"

        # control: the replicated path compiles to no collective at all
        text_1 = (jax.jit(lambda p, b: lsr_user_repr(
            p, cfg, b, plan=replicated_plan()))
            .lower(params, batch).compile().as_text())
        assert "all-reduce" not in text_1


class TestPrefetchSharding:
    def test_loader_places_per_shard(self, plan, tmp_path):
        """PrefetchLoader with a sharding fn yields device batches already
        split over the data axis — no replicated host copy, no reshard."""
        from repro.pipeline import write_samples
        from repro.pipeline.prefetch import PrefetchLoader, ShardDataset

        stream = EventStreamConfig(n_requests=40, n_items=512,
                                   hist_init_max=8, seed=3)
        samples = RequestLevelJoiner().join(
            list(EventSimulator(stream).stream()))
        write_samples(str(tmp_path / "shards"), samples,
                      requests_per_shard=32)
        bcfg = BatcherConfig(b_ro=8, b_nro=32, hist_len=16, n_shards=2,
                             ro_idlist_capacity=256, item_idlist_capacity=512)
        loader = PrefetchLoader(
            ShardDataset(str(tmp_path / "shards"), bcfg),
            prefetch=True, epochs=1,
            sharding=spmd.make_batch_sharding_fn(plan))
        batch, _ = next(iter(loader.batches()))
        ro = batch.ro_dense
        assert ro.sharding.spec[0] in ("data", ("data",))
        # two distinct row blocks, not 8 replicas
        assert _distinct_shard_blocks(ro) == 2
        # and the sharded forward consumes it directly
        cfg = _lsr_cfg()
        params = lsr_init(jax.random.PRNGKey(0), cfg)
        params = jax.device_put(params, spmd.state_shardings(params, plan))
        loss = jax.jit(lambda p, b: lsr_loss(p, cfg, b, plan=plan))(
            params, batch)
        assert np.isfinite(float(loss))
