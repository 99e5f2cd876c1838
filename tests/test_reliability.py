"""Chaos suite: seeded fault injection (repro.reliability.faults) and the
graceful-degradation behaviors it exercises end to end —

  * per-block CRC32 shard integrity + corrupt-shard quarantine,
  * checkpoint verify-on-restore with fallback to the latest valid step,
  * prefetch retry/backoff, stall watchdog, explicit shutdown,
  * ShardWriter crash-mid-write (torn tmp never reaches the manifest),
  * scoring-engine failure isolation + circuit breaker,
  * trainer non-finite skip-step guard,
  * kill-and-restart under transient faults stays bit-identical.

CI runs this file with REPRO_FAULTS set at fixed seeds (the chaos job);
tests that install their own plan are unaffected by the env var.
"""
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.joiner import ROOSample
from repro.data.batcher import BatcherConfig
from repro.data.events import EventSimulator, EventStreamConfig
from repro.data.storage import (SCHEMA_VERSION, ShardCorruptionError,
                                decode_roo_shard, encode_roo_shard,
                                peek_shard_header)
from repro.pipeline import (CursorStore, PipelineDataSource, PrefetchLoader,
                            ShardDataset, WatermarkJoiner, read_all,
                            write_samples)
from repro.pipeline.shards import ShardWriter
from repro.reliability import (ENV_VAR, FaultPlan, FaultSpec, InjectedFault,
                               TransientFault, use_plan)
from repro.serve.engine import EnginePolicy, ScoreError, ScoringEngine
from repro.train.checkpoint import CheckpointCorruptionError, CheckpointManager
from repro.train.loop import (NonFiniteLossError, Trainer, TrainLoopConfig,
                              _poison_batch, make_train_step)
from repro.train.optim import sgd


# ---------------------------------------------------------------------------
# helpers / fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def joined_samples():
    cfg = EventStreamConfig(n_requests=120, hist_init_max=40, seed=0,
                            late_fraction=0.2)
    return WatermarkJoiner().join(EventSimulator(cfg).stream())


@pytest.fixture(scope="module")
def shard_dir(joined_samples, tmp_path_factory):
    d = tmp_path_factory.mktemp("shards")
    write_samples(str(d), joined_samples, requests_per_shard=40)
    return str(d)


def _bcfg():
    return BatcherConfig(b_ro=16, b_nro=128, hist_len=64)


def _flip_byte(path: str, offset_from_end: int = 16) -> None:
    with open(path, "r+b") as f:
        f.seek(0, os.SEEK_END)
        pos = f.tell() - offset_from_end
        f.seek(pos)
        b = f.read(1)
        f.seek(pos)
        f.write(bytes([b[0] ^ 0xFF]))


def _assert_batches_equal(b1, b2):
    l1, l2 = jax.tree.leaves(b1), jax.tree.leaves(b2)
    assert len(l1) == len(l2)
    for x, y in zip(l1, l2):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def mk_request(uid: int, item_ids) -> ROOSample:
    return ROOSample(
        request_id=uid, user_id=uid,
        ro_dense=np.full((4,), float(uid), np.float32),
        ro_idlist=[uid % 7 + 1],
        history_ids=[1 + uid % 3, 2, 3], history_actions=[1, 0, 1],
        item_ids=[int(i) for i in item_ids],
        item_dense=[np.full((4,), float(i), np.float32) for i in item_ids],
        item_idlist=[[int(i) % 5 + 1] for i in item_ids],
        labels=[{"click": 0.0, "view_sec": 0.0} for _ in item_ids])


def echo_score_fn(params, batch):
    return batch.item_ids.astype(jnp.float32)


# ---------------------------------------------------------------------------
# the fault plan itself
# ---------------------------------------------------------------------------

class TestFaultPlan:
    def test_parse_roundtrip(self):
        text = "seed=7;shard.read:corrupt@0.05;engine.score:error@0.3x5"
        plan = FaultPlan.parse(text)
        assert plan.seed == 7
        assert plan.specs["shard.read"].kind == "corrupt"
        assert plan.specs["engine.score"].max_fires == 5
        again = FaultPlan.parse(plan.to_env())
        assert again.seed == plan.seed and again.specs == plan.specs

    def test_comma_separator_and_defaults(self):
        plan = FaultPlan.parse("prefetch.io:error@1")
        assert plan.seed == 0
        assert plan.specs["prefetch.io"].p == 1.0
        assert plan.specs["prefetch.io"].max_fires is None
        plan2 = FaultPlan.parse("seed=1,ckpt.write:torn@0.5")
        assert plan2.seed == 1 and "ckpt.write" in plan2.specs

    def test_bad_clauses_raise(self):
        with pytest.raises(ValueError):
            FaultPlan.parse("shard.read:bogus@0.5")    # unknown kind
        with pytest.raises(ValueError):
            FaultPlan.parse("nonsense")                # no site:kind@p
        with pytest.raises(ValueError):
            FaultSpec("s", "error", p=1.5)             # p out of range

    def test_seeded_determinism(self):
        def fires(seed):
            plan = FaultPlan([FaultSpec("x", "error", p=0.3)], seed=seed)
            return [plan.fire("x") is not None for _ in range(200)]
        assert fires(11) == fires(11)
        assert fires(11) != fires(12)

    def test_sites_independent(self):
        """Extra draws at one site never perturb another site's sequence."""
        a = FaultPlan([FaultSpec("x", "error", p=0.3),
                       FaultSpec("y", "error", p=0.3)], seed=5)
        b = FaultPlan([FaultSpec("x", "error", p=0.3),
                       FaultSpec("y", "error", p=0.3)], seed=5)
        for _ in range(50):
            a.fire("x")                               # a drains x first
        seq_a = [a.fire("y") is not None for _ in range(50)]
        seq_b = [b.fire("y") is not None for _ in range(50)]
        assert seq_a == seq_b

    def test_max_fires_and_stats(self):
        plan = FaultPlan([FaultSpec("x", "error", p=1.0, max_fires=3)])
        hits = sum(plan.fire("x") is not None for _ in range(10))
        assert hits == 3
        assert plan.stats.visits["x"] == 10
        assert plan.stats.fires["x"] == 3

    def test_use_plan_restores_previous(self):
        from repro.reliability import faults as f
        before = f.active_plan()
        with use_plan(FaultPlan([FaultSpec("x", "error")])) as plan:
            assert f.active_plan() is plan
        assert f.active_plan() is before


# ---------------------------------------------------------------------------
# shard CRC + quarantine
# ---------------------------------------------------------------------------

class TestShardIntegrity:
    def test_v2_frame_has_crc_and_roundtrips(self, joined_samples):
        blob = encode_roo_shard(joined_samples[:20])
        assert peek_shard_header(blob)["schema_version"] == SCHEMA_VERSION
        assert len(decode_roo_shard(blob)) == 20

    def test_corrupt_byte_detected(self, joined_samples):
        blob = bytearray(encode_roo_shard(joined_samples[:20]))
        blob[len(blob) - 16] ^= 0xFF
        with pytest.raises(ShardCorruptionError):
            decode_roo_shard(bytes(blob))

    def test_v1_frame_still_readable(self, joined_samples):
        blob = encode_roo_shard(joined_samples[:20], crc=False)
        assert peek_shard_header(blob)["schema_version"] == 1
        assert len(decode_roo_shard(blob)) == 20

    def test_quarantine_keeps_training_alive(self, joined_samples, tmp_path):
        d = str(tmp_path / "shards")
        manifest = write_samples(d, joined_samples, requests_per_shard=40)
        assert len(manifest.shards) >= 2
        _flip_byte(os.path.join(d, manifest.shards[0].filename))
        ds = ShardDataset(d, _bcfg())
        with pytest.warns(RuntimeWarning, match="quarantined"):
            first = ds.shard_batches(0)
        assert first == []                      # poisoned shard yields none
        assert ds.stats.shards_quarantined == 1
        assert ds.stats.quarantined_files == [manifest.shards[0].filename]
        assert len(ds.shard_batches(1)) > 0     # survivors still flow

    def test_strict_mode_raises(self, joined_samples, tmp_path):
        d = str(tmp_path / "shards")
        manifest = write_samples(d, joined_samples, requests_per_shard=40)
        _flip_byte(os.path.join(d, manifest.shards[0].filename))
        ds = ShardDataset(d, _bcfg(), strict=True)
        with pytest.raises(ShardCorruptionError,
                           match=manifest.shards[0].filename):
            ds.shard_batches(0)


class TestShardWriterCrash:
    def test_torn_write_never_reaches_manifest(self, joined_samples,
                                               tmp_path):
        d = str(tmp_path / "shards")
        plan = FaultPlan([FaultSpec("shard.write", "torn", max_fires=1)])
        with use_plan(plan):
            writer = ShardWriter(d, requests_per_shard=40)
            with pytest.raises(InjectedFault):
                writer.extend(joined_samples)
        # the kill left a torn tmp and no manifest
        assert any(n.endswith(".tmp") for n in os.listdir(d))
        assert not os.path.exists(os.path.join(d, "manifest.json"))
        # restarted writer sweeps the tmp and regenerates everything
        writer = ShardWriter(d, requests_per_shard=40)
        assert not any(n.endswith(".tmp") for n in os.listdir(d))
        writer.extend(joined_samples)
        manifest = writer.close()
        for s in manifest.shards:               # every referenced shard loads
            assert os.path.exists(os.path.join(d, s.filename))
        assert len(read_all(d)) == len(joined_samples)


# ---------------------------------------------------------------------------
# checkpoint verify-on-restore
# ---------------------------------------------------------------------------

def _state(v: float):
    return {"w": np.full((4, 2), v, np.float32),
            "step": np.asarray(int(v), np.int32)}


class TestCheckpointReliability:
    def test_verify_and_fallback_to_latest_valid(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_last=4)
        mgr.save(1, _state(1.0))
        mgr.save(2, _state(2.0))
        _flip_byte(str(tmp_path / "step_000000000002" / "arrays.npz"), 8)
        assert mgr.verify(1) and not mgr.verify(2)
        assert mgr.all_steps() == [1, 2]        # 2 is committed but rotten
        assert mgr.valid_steps() == [1]
        assert mgr.latest_valid_step() == 1
        restored = mgr.restore()                # silently skips step 2
        np.testing.assert_array_equal(restored["w"], _state(1.0)["w"])
        with pytest.raises(CheckpointCorruptionError):
            mgr.restore(2)                      # explicit ask fails loudly

    def test_tmp_dirs_swept_on_init(self, tmp_path):
        junk = tmp_path / "step_000000000005.tmp"
        junk.mkdir()
        (junk / "arrays.npz").write_bytes(b"partial")
        CheckpointManager(str(tmp_path))
        assert not junk.exists()

    def test_injected_torn_write(self, tmp_path):
        plan = FaultPlan([FaultSpec("ckpt.write", "torn", max_fires=1)])
        with use_plan(plan):
            mgr = CheckpointManager(str(tmp_path))
            mgr.save(1, _state(1.0))            # torn: never committed
            assert mgr.all_steps() == []
            mgr.save(2, _state(2.0))            # fires exhausted: commits
        assert mgr.all_steps() == [2]
        # the second save's _gc swept the torn step_1 tmp dir
        assert not any(n.endswith(".tmp") for n in os.listdir(tmp_path))
        np.testing.assert_array_equal(mgr.restore()["w"], _state(2.0)["w"])

    def test_injected_corrupt_write_caught_by_digest(self, tmp_path):
        plan = FaultPlan([FaultSpec("ckpt.write", "corrupt", max_fires=1)])
        with use_plan(plan):
            mgr = CheckpointManager(str(tmp_path), keep_last=4)
            mgr.save(1, _state(1.0))            # committed, then bit-rotted
            assert not mgr.verify(1)
            mgr.save(2, _state(2.0))
        assert mgr.latest_valid_step() == 2
        np.testing.assert_array_equal(mgr.restore()["w"], _state(2.0)["w"])


# ---------------------------------------------------------------------------
# prefetch retry / stall watchdog / shutdown
# ---------------------------------------------------------------------------

class TestPrefetchReliability:
    def _baseline(self, shard_dir):
        with PrefetchLoader(ShardDataset(shard_dir, _bcfg()),
                            prefetch=False, epochs=1) as loader:
            return list(loader.batches())

    def test_transient_errors_retried_stream_identical(self, shard_dir):
        base = self._baseline(shard_dir)
        plan = FaultPlan([FaultSpec("prefetch.io", "error", max_fires=2)])
        with use_plan(plan):
            loader = PrefetchLoader(ShardDataset(shard_dir, _bcfg()),
                                    prefetch=True, epochs=1,
                                    retry_backoff_s=0.001)
            with loader:
                out = list(loader.batches())
        assert loader.stats.read_retries == 2
        assert loader.stats.read_failures == 0
        assert len(out) == len(base)
        for (b1, c1), (b2, c2) in zip(out, base):
            assert c1 == c2
            _assert_batches_equal(b1, b2)

    def test_retry_budget_exhausted_surfaces(self, shard_dir):
        plan = FaultPlan([FaultSpec("prefetch.io", "error")])  # every visit
        with use_plan(plan):
            loader = PrefetchLoader(ShardDataset(shard_dir, _bcfg()),
                                    prefetch=True, epochs=1, max_retries=1,
                                    retry_backoff_s=0.001)
            with loader:
                with pytest.raises(TransientFault):
                    list(loader.batches())
        assert loader.stats.read_failures == 1

    def test_stall_watchdog_restarts_producer(self, shard_dir):
        base = self._baseline(shard_dir)
        plan = FaultPlan([FaultSpec("prefetch.stall", "stall", max_fires=1)])
        with use_plan(plan):
            loader = PrefetchLoader(ShardDataset(shard_dir, _bcfg()),
                                    prefetch=True, epochs=1,
                                    stall_timeout_s=0.3)
            with loader:
                out = list(loader.batches())
        assert loader.stats.producer_restarts == 1
        assert len(out) == len(base)
        for (b1, c1), (b2, c2) in zip(out, base):
            assert c1 == c2
            _assert_batches_equal(b1, b2)

    def test_close_joins_producer_threads(self, shard_dir):
        loader = PrefetchLoader(ShardDataset(shard_dir, _bcfg()),
                                prefetch=True, epochs=1)
        it = loader.batches()
        next(it)                                 # producer is now running
        it.close()
        loader.close()
        alive = [t for t in threading.enumerate()
                 if t.name.startswith("roo-prefetch-") and t.is_alive()]
        assert alive == []


# ---------------------------------------------------------------------------
# scoring engine: isolation + circuit breaker
# ---------------------------------------------------------------------------

class TestEngineIsolation:
    def test_failed_batch_is_isolated(self):
        reqs = [mk_request(i, [10 * i, 10 * i + 1]) for i in range(8)]
        plan = FaultPlan([FaultSpec("engine.score", "error", max_fires=1)])
        with use_plan(plan):
            engine = ScoringEngine(None, echo_score_fn,
                                   policy=EnginePolicy(max_requests=4,
                                                       max_impressions=16))
            out = engine.score_requests(reqs)
        assert len(out) == 8
        failed = [i for i, s in enumerate(out) if isinstance(s, ScoreError)]
        healthy = [i for i in range(8) if i not in failed]
        assert failed and healthy                # blast radius = one batch
        for i in healthy:                        # survivors stay aligned
            np.testing.assert_array_equal(
                out[i], np.asarray(reqs[i].item_ids, np.float32))
        assert engine.stats.n_failed_batches == 1
        assert engine.stats.n_failed_requests == len(failed)

    def test_split_request_poisoned_not_truncated(self):
        # the failing piece poisons the whole request: a partial score
        # array misaligned with item_ids must never escape
        big = mk_request(1, list(range(40)))     # splits across batches
        plan = FaultPlan([FaultSpec("engine.score", "error", max_fires=1)])
        with use_plan(plan):
            engine = ScoringEngine(None, echo_score_fn,
                                   policy=EnginePolicy(max_requests=4,
                                                       max_impressions=16))
            (out,) = engine.score_requests([big])
        assert isinstance(out, ScoreError)

    def test_breaker_opens_sheds_and_recovers(self):
        t = [0.0]
        plan = FaultPlan([FaultSpec("engine.score", "error", max_fires=2)])
        with use_plan(plan):
            engine = ScoringEngine(
                None, echo_score_fn,
                policy=EnginePolicy(max_requests=4, max_impressions=16,
                                    breaker_threshold=2,
                                    breaker_cooldown_s=5.0),
                clock=lambda: t[0])
            r1 = engine.score_requests([mk_request(1, [1, 2])])[0]
            r2 = engine.score_requests([mk_request(2, [3, 4])])[0]
            assert isinstance(r1, ScoreError) and not r1.shed
            assert isinstance(r2, ScoreError) and not r2.shed
            assert engine.stats.n_breaker_opens == 1
            # open: work is shed without touching the model
            r3 = engine.score_requests([mk_request(3, [5, 6])])[0]
            assert isinstance(r3, ScoreError) and r3.shed
            assert engine.stats.n_shed_requests == 1
            assert plan.stats.visits["engine.score"] == 2   # batch 3 skipped
            # cooldown elapsed: half-open trial succeeds, breaker closes
            t[0] = 6.0
            r4 = engine.score_requests([mk_request(4, [7, 8])])[0]
            np.testing.assert_array_equal(r4, np.asarray([7., 8.],
                                                         np.float32))
            r5 = engine.score_requests([mk_request(5, [9])])[0]
            np.testing.assert_array_equal(r5, np.asarray([9.], np.float32))
        assert engine.stats.n_failed_batches == 2
        assert engine.stats.n_batches == 2


# ---------------------------------------------------------------------------
# trainer non-finite guard
# ---------------------------------------------------------------------------

def _toy_batches(start):
    for step in range(start, 10_000):
        yield jnp.full((4,), 1.0 + 0.1 * step, jnp.float32)


def _toy_loss(params, batch, rng):
    return jnp.mean((params["w"] * batch - 1.0) ** 2)


def _toy_init():
    return {"w": jnp.ones((4,), jnp.float32)}


def _table_loss(params, batch, rng):
    from repro.embeddings import collection as ec
    e = ec.seq_lookup(params["item_emb"], batch["ids"])
    return jnp.mean((jnp.sum(e, -1) * params["w"] - batch["y"]) ** 2)


def _table_init():
    return {"item_emb": jnp.linspace(-1.0, 1.0, 256 * 8).reshape(256, 8),
            "w": jnp.ones((4,), jnp.float32)}


def _table_batches(start):
    for step in range(start, 10_000):
        yield {"y": jnp.full((3, 4), 1.0 + 0.1 * step, jnp.float32),
               "ids": (jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
                       * 7 + step) % 256}


def _mixed_opt():
    from repro.train.optim import (adam, default_is_embedding, make_mixed,
                                   rowwise_adagrad)
    return make_mixed(adam(0.01), rowwise_adagrad(0.1), default_is_embedding)


# toy: one dense leaf under SGD; tables: a 256-row table that the default
# step updates through its touched rows, beside an Adam leaf
GUARD_CASES = {
    "toy": (_toy_loss, lambda: sgd(lr=0.1), _toy_init, _toy_batches),
    "tables": (_table_loss, _mixed_opt, _table_init, _table_batches),
}


class TestTrainerGuard:
    @pytest.mark.parametrize("case", sorted(GUARD_CASES))
    def test_nan_batches_skipped_params_unpoisoned(self, case):
        loss, make_opt, init, batches_from = GUARD_CASES[case]
        rng = jax.random.PRNGKey(0)
        cfg = TrainLoopConfig(total_steps=6, log_every=100,
                              halt_after_skips=10)
        plan = FaultPlan([FaultSpec("train.batch", "nan", max_fires=2)])
        with use_plan(plan):
            tr = Trainer(loss, make_opt(), cfg, init)
            state = tr.run(lambda s: batches_from(s), rng)
        assert tr.skipped_steps == 2
        for leaf in jax.tree.leaves(state["params"]):
            assert np.isfinite(np.asarray(leaf)).all()
        # reference: steps 0 and 1 were frozen, so the final params equal
        # applying only steps 2..5 (same batches, same fold_in keys)
        opt = make_opt()
        step_fn = make_train_step(loss, opt)
        params = init()
        ref = {"params": params, "opt": opt.init(params),
               "step": jnp.zeros((), jnp.int32), "rng": rng}
        batches = list(b for _, b in zip(range(6), batches_from(0)))
        # a NaN batch leaves every leaf, table and accumulator
        # included, bit for bit as it was, and reports the skip
        skipped, met = step_fn(ref, _poison_batch(batches[0]), rng)
        assert int(met["skipped"]) == 1
        for a, b in zip(jax.tree.leaves(skipped["params"])
                        + jax.tree.leaves(skipped["opt"]),
                        jax.tree.leaves(ref["params"])
                        + jax.tree.leaves(ref["opt"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for step in range(2, 6):
            ref, _ = step_fn(ref, batches[step],
                             jax.random.fold_in(rng, step))
        for a, b in zip(jax.tree.leaves(state["params"]),
                        jax.tree.leaves(ref["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_consecutive_skips_halt(self):
        cfg = TrainLoopConfig(total_steps=50, log_every=100,
                              halt_after_skips=3)
        plan = FaultPlan([FaultSpec("train.batch", "nan")])   # every step
        with use_plan(plan):
            tr = Trainer(_toy_loss, sgd(lr=0.1), cfg, _toy_init)
            with pytest.raises(NonFiniteLossError):
                tr.run(lambda s: _toy_batches(s), jax.random.PRNGKey(0))
        assert tr.skipped_steps == 3

    def test_guard_passive_by_default(self):
        cfg = TrainLoopConfig(total_steps=4, log_every=2)
        plan = FaultPlan([FaultSpec("train.batch", "nan", max_fires=1)])
        with use_plan(plan):
            tr = Trainer(_toy_loss, sgd(lr=0.1), cfg, _toy_init)
            state = tr.run(lambda s: _toy_batches(s), jax.random.PRNGKey(0))
        assert np.isfinite(np.asarray(state["params"]["w"])).all()
        assert any("skipped" in row for row in tr.history)


# ---------------------------------------------------------------------------
# end-to-end chaos: kill-and-restart under transient faults
# ---------------------------------------------------------------------------

class TestChaosKillAndRestart:
    def _make_trainer(self, ckpt_dir, total=12):
        def loss_fn(params, batch, rng):
            pred = batch.ro_dense @ params["w"]
            tgt = jax.ops.segment_sum(batch.labels[:, 0],
                                      batch.segment_ids,
                                      num_segments=batch.b_ro + 1)[:-1]
            return jnp.mean((pred[:, 0] - tgt) ** 2)

        cfg = TrainLoopConfig(total_steps=total, ckpt_every=4,
                              log_every=100, ckpt_dir=ckpt_dir)
        return Trainer(loss_fn, sgd(lr=0.01), cfg,
                       lambda: {"w": jnp.ones((16, 1))})

    def _source(self, shard_dir, cursor_dir):
        loader = PrefetchLoader(ShardDataset(shard_dir, _bcfg()),
                                prefetch=True, max_retries=6,
                                retry_backoff_s=0.001)
        return PipelineDataSource(loader, CursorStore(cursor_dir))

    def _chaos_plan(self):
        # fresh plan per (simulated) process: same seeded draws each run
        return FaultPlan([FaultSpec("prefetch.io", "error", p=0.15),
                          FaultSpec("shard.read", "error", p=0.1)], seed=3)

    def test_resume_bit_identical_under_transient_faults(self, shard_dir,
                                                         tmp_path):
        rng = jax.random.PRNGKey(0)
        # fault-free uninterrupted reference
        with self._source(shard_dir, str(tmp_path / "cur_full")) as src:
            t_full = self._make_trainer(str(tmp_path / "full"))
            s_full = t_full.run(src.batch_iter_fn, rng,
                                on_checkpoint=src.on_checkpoint)
        # chaos run killed at step 6 (last commit: step 4) ...
        with use_plan(self._chaos_plan()):
            with self._source(shard_dir, str(tmp_path / "cur")) as src_a:
                t_a = self._make_trainer(str(tmp_path / "pre"))
                t_a.run(src_a.batch_iter_fn, rng, stop_after=6,
                        on_checkpoint=src_a.on_checkpoint)
        assert CursorStore(str(tmp_path / "cur")).steps() == [4]
        # ... restarted in a new "process" with its own chaos plan
        with use_plan(self._chaos_plan()):
            with self._source(shard_dir, str(tmp_path / "cur")) as src_b:
                t_b = self._make_trainer(str(tmp_path / "pre"))
                s_res = t_b.run(src_b.batch_iter_fn, rng,
                                on_checkpoint=src_b.on_checkpoint)
        assert int(s_res["step"]) == 12
        np.testing.assert_array_equal(np.asarray(s_full["params"]["w"]),
                                      np.asarray(s_res["params"]["w"]))


# ---------------------------------------------------------------------------
# env-driven chaos (what the CI chaos job runs at fixed seeds)
# ---------------------------------------------------------------------------

DEFAULT_CHAOS = ("seed=3;shard.read:error@0.1;prefetch.io:error@0.1;"
                 "engine.score:error@0.2x3;train.batch:nan@0.1x2;"
                 "ckpt.write:torn@0.2x1")


class TestEnvDrivenChaos:
    def test_pipeline_survives_env_plan(self, joined_samples, tmp_path):
        """Write -> train(+resume) -> serve under the REPRO_FAULTS plan
        (or a default storm): the job must finish, healthy requests must
        still get aligned scores."""
        text = os.environ.get(ENV_VAR, "").strip() or DEFAULT_CHAOS
        d = str(tmp_path / "shards")
        write_samples(d, joined_samples, requests_per_shard=40)
        rng = jax.random.PRNGKey(0)
        with use_plan(FaultPlan.parse(text)):
            loader = PrefetchLoader(ShardDataset(d, _bcfg()),
                                    prefetch=True, max_retries=8,
                                    retry_backoff_s=0.001,
                                    stall_timeout_s=2.0)
            src = PipelineDataSource(loader,
                                     CursorStore(str(tmp_path / "cur")))
            tr = TestChaosKillAndRestart()._make_trainer(
                str(tmp_path / "ckpt"), total=8)
            with src:
                state = tr.run(src.batch_iter_fn, rng, stop_after=5,
                               on_checkpoint=src.on_checkpoint)
            # restart from whatever survived on disk
            with self._fresh_source(d, tmp_path) as src2:
                tr2 = TestChaosKillAndRestart()._make_trainer(
                    str(tmp_path / "ckpt"), total=8)
                state = tr2.run(src2.batch_iter_fn, rng,
                                on_checkpoint=src2.on_checkpoint)
            assert int(state["step"]) == 8
            assert np.isfinite(np.asarray(state["params"]["w"])).all()
            # serving keeps answering under injected scorer failures
            engine = ScoringEngine(None, echo_score_fn,
                                   policy=EnginePolicy(max_requests=4,
                                                       max_impressions=16))
            reqs = [mk_request(i, [i, i + 1]) for i in range(12)]
            out = engine.score_requests(reqs)
            assert len(out) == len(reqs)
            healthy = 0
            for r, s in zip(reqs, out):
                if isinstance(s, ScoreError):
                    continue
                healthy += 1
                np.testing.assert_array_equal(
                    s, np.asarray(r.item_ids, np.float32))
            assert healthy > 0

    def _fresh_source(self, shard_dir, tmp_path):
        loader = PrefetchLoader(ShardDataset(shard_dir, _bcfg()),
                                prefetch=True, max_retries=8,
                                retry_backoff_s=0.001, stall_timeout_s=2.0)
        return PipelineDataSource(loader,
                                  CursorStore(str(tmp_path / "cur")))
