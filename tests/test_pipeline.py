"""Request-log pipeline: shard codec roundtrip (incl. property tests),
watermark joiner semantics, prefetch loader determinism, and the
kill-and-restart (shard, offset) cursor resume contract."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.joiner import ROOSample, expand_roo_samples
from repro.data.batcher import BatcherConfig
from repro.data.events import EventSimulator, EventStreamConfig
from repro.data.storage import (SCHEMA_VERSION, decode_impression_shard,
                                decode_roo_shard, encode_impression_shard,
                                encode_roo_shard, peek_shard_header)
from repro.pipeline import (Cursor, CursorStore, OnlineJoinConfig,
                            PipelineDataSource, PrefetchLoader, ShardDataset,
                            WatermarkJoiner, load_manifest, read_all,
                            write_samples)


def _assert_samples_equal(a: ROOSample, b: ROOSample):
    assert a.request_id == b.request_id
    assert a.user_id == b.user_id
    np.testing.assert_array_equal(np.asarray(a.ro_dense, np.float32),
                                  np.asarray(b.ro_dense))
    assert [int(x) for x in a.ro_idlist] == b.ro_idlist
    assert [int(x) for x in a.history_ids] == b.history_ids
    assert [int(x) for x in a.history_actions] == b.history_actions
    assert [int(x) for x in a.item_ids] == b.item_ids
    assert len(a.item_dense) == len(b.item_dense)
    for da, db in zip(a.item_dense, b.item_dense):
        np.testing.assert_array_equal(np.asarray(da, np.float32),
                                      np.asarray(db))
    assert [[int(x) for x in l] for l in a.item_idlist] == b.item_idlist
    assert len(a.labels) == len(b.labels)
    for la, lb in zip(a.labels, b.labels):
        assert set(la) == set(lb)
        for k in la:
            assert np.float32(la[k]) == np.float32(lb[k])


def _assert_batches_equal(b1, b2):
    l1, l2 = jax.tree.leaves(b1), jax.tree.leaves(b2)
    assert len(l1) == len(l2)
    for x, y in zip(l1, l2):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _random_samples(seed: int):
    """Random ROO samples with ragged/empty/zero-impression structure."""
    r = np.random.RandomState(seed)
    out = []
    for i in range(r.randint(1, 6)):
        n_imp = int(r.randint(0, 4))          # zero-impression requests too
        out.append(ROOSample(
            request_id=int(r.randint(0, 2 ** 31)),
            user_id=int(r.randint(0, 2 ** 31)),
            ro_dense=r.normal(size=(r.randint(0, 6),)).astype(np.float32),
            ro_idlist=r.randint(0, 2 ** 31,
                                size=r.randint(0, 5)).tolist(),
            history_ids=r.randint(0, 2 ** 31,
                                  size=r.randint(0, 5)).tolist(),
            history_actions=r.randint(0, 2,
                                      size=r.randint(0, 5)).tolist(),
            item_ids=r.randint(0, 2 ** 31, size=n_imp).tolist(),
            item_dense=[r.normal(size=(r.randint(0, 4),)).astype(np.float32)
                        for _ in range(n_imp)],
            item_idlist=[r.randint(0, 2 ** 31,
                                   size=r.randint(0, 4)).tolist()
                         for _ in range(n_imp)],
            labels=[{"click": float(r.randint(0, 2)),
                     "view_sec": float(np.float32(r.rand() * 100))}
                    for _ in range(n_imp)]))
    return out


@pytest.fixture(scope="module")
def joined_samples():
    cfg = EventStreamConfig(n_requests=120, hist_init_max=40, seed=0,
                            late_fraction=0.2)
    return WatermarkJoiner().join(EventSimulator(cfg).stream())


class TestShardCodec:
    def test_roundtrip_simulator_data(self, joined_samples):
        blob = encode_roo_shard(joined_samples)
        out = decode_roo_shard(blob)
        assert len(out) == len(joined_samples)
        for a, b in zip(joined_samples, out):
            _assert_samples_equal(a, b)

    def test_roundtrip_uncompressed(self, joined_samples):
        sub = joined_samples[:10]
        blob_c = encode_roo_shard(sub, compress=True)
        blob_u = encode_roo_shard(sub, compress=False)
        assert len(blob_c) < len(blob_u)
        for a, b in zip(decode_roo_shard(blob_c), decode_roo_shard(blob_u)):
            _assert_samples_equal(a, b)

    @settings(max_examples=30, deadline=None)
    @given(st.integers(0, 9999))
    def test_property_roundtrip(self, seed):
        """Ragged id-lists, empty payloads, zero-impression requests."""
        samples = _random_samples(seed)
        out = decode_roo_shard(encode_roo_shard(samples))
        assert len(out) == len(samples)
        for a, b in zip(samples, out):
            _assert_samples_equal(a, b)

    def test_zero_impression_request(self):
        s = ROOSample(request_id=7, user_id=3,
                      ro_dense=np.zeros((0,), np.float32), ro_idlist=[],
                      history_ids=[], history_actions=[], item_ids=[],
                      item_dense=[], item_idlist=[], labels=[])
        (out,) = decode_roo_shard(encode_roo_shard([s]))
        _assert_samples_equal(s, out)

    def test_empty_shard(self):
        assert decode_roo_shard(encode_roo_shard([])) == []

    def test_ro_payload_dedup(self):
        base = _random_samples(0)[0]
        import dataclasses
        dup = [dataclasses.replace(base, request_id=i) for i in range(20)]
        hdr = peek_shard_header(encode_roo_shard(dup))
        assert hdr["pool_sizes"]["ro_dense"] == 1
        assert hdr["pool_sizes"]["history"] == 1
        assert hdr["ro_pool_size"] == 3
        for a, b in zip(dup, decode_roo_shard(encode_roo_shard(dup))):
            _assert_samples_equal(a, b)

    def test_schema_version_gate(self, joined_samples):
        import json
        import struct
        blob = encode_roo_shard(joined_samples[:2])
        hdr = peek_shard_header(blob)
        hdr["schema_version"] = SCHEMA_VERSION + 1
        new_hdr = json.dumps(hdr, sort_keys=True).encode()
        (old_len,) = struct.unpack_from("<I", blob, 8)
        doctored = (blob[:8] + struct.pack("<I", len(new_hdr)) + new_hdr
                    + blob[12 + old_len:])
        with pytest.raises(ValueError, match="newer than supported"):
            decode_roo_shard(doctored)
        with pytest.raises(ValueError, match="bad magic"):
            decode_roo_shard(b"NOTASHRD" + blob[8:])

    def test_impression_codec_roundtrip(self, joined_samples):
        imp = expand_roo_samples(joined_samples[:40])
        out = decode_impression_shard(encode_impression_shard(imp))
        assert len(out) == len(imp)
        for a, b in zip(imp, out):
            assert (a.request_id, a.user_id, a.item_id) == \
                (b.request_id, b.user_id, b.item_id)
            np.testing.assert_array_equal(
                np.asarray(a.ro_dense, np.float32), b.ro_dense)
            np.testing.assert_array_equal(
                np.asarray(a.item_dense, np.float32), b.item_dense)
            assert [int(x) for x in a.history_ids] == b.history_ids
            for k in a.labels:
                assert np.float32(a.labels[k]) == np.float32(b.labels[k])


class TestShardFiles:
    def test_write_read_manifest(self, joined_samples, tmp_path):
        man = write_samples(str(tmp_path), joined_samples,
                            requests_per_shard=32,
                            provenance={"label_wait_s": 600.0, "seed": 0})
        assert len(man.shards) == -(-len(joined_samples) // 32)
        assert man.n_requests == len(joined_samples)
        assert man.n_impressions == sum(
            s.num_impressions for s in joined_samples)
        # real files, real sizes, no torn tmp files left behind
        for s in man.shards:
            assert os.path.getsize(os.path.join(tmp_path, s.filename)) \
                == s.n_bytes
        assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
        man2 = load_manifest(str(tmp_path))
        assert man2 == man
        back = read_all(str(tmp_path), man2)
        for a, b in zip(joined_samples, back):
            _assert_samples_equal(a, b)

    def test_missing_manifest(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            load_manifest(str(tmp_path))


class TestWatermarkJoiner:
    def _events(self, late_fraction):
        cfg = EventStreamConfig(n_requests=200, hist_init_max=30, seed=1,
                                late_fraction=late_fraction)
        return list(EventSimulator(cfg).stream())

    def test_deterministic(self):
        events = self._events(0.3)
        a = WatermarkJoiner().join(events)
        b = WatermarkJoiner().join(events)
        assert len(a) == len(b)
        for x, y in zip(a, b):
            _assert_samples_equal(x, encode_and_back(y))

    def test_late_conversions_counted_not_silent(self):
        events = self._events(0.4)
        j = WatermarkJoiner(OnlineJoinConfig(label_wait_s=120.0))
        j.join(events)
        assert j.stats.conversions_late > 0
        assert j.stats.conversions_joined > 0
        assert 0.0 < j.stats.label_completeness < 1.0

    def test_label_wait_tradeoff(self):
        """Longer label wait -> more labels joined but staler emits."""
        events = self._events(0.2)
        short = WatermarkJoiner(OnlineJoinConfig(label_wait_s=120.0))
        long = WatermarkJoiner(OnlineJoinConfig(label_wait_s=1800.0))
        short.join(events)
        long.join(events)
        assert long.stats.label_completeness > short.stats.label_completeness
        assert long.stats.mean_close_lag_s > short.stats.mean_close_lag_s
        # both saw every request
        assert long.stats.requests_emitted == short.stats.requests_emitted

    def test_no_request_lost_vs_core_joiner(self):
        from repro.core.joiner import RequestLevelJoiner
        events = self._events(0.0)
        wm = WatermarkJoiner().join(events)
        core = RequestLevelJoiner().join(events)
        assert {(s.user_id, s.request_id) for s in wm} == \
            {(s.user_id, s.request_id) for s in core}
        assert sum(s.num_impressions for s in wm) == \
            sum(s.num_impressions for s in core)


def encode_and_back(s):
    (out,) = decode_roo_shard(encode_roo_shard([s]))
    return out


@pytest.fixture(scope="module")
def shard_dir(joined_samples, tmp_path_factory):
    d = tmp_path_factory.mktemp("shards")
    write_samples(str(d), joined_samples, requests_per_shard=40)
    return str(d)


def _bcfg():
    return BatcherConfig(b_ro=16, b_nro=128, hist_len=64)


class TestPrefetchLoader:
    def test_prefetch_equals_sync(self, shard_dir):
        ds = ShardDataset(shard_dir, _bcfg())
        on = list(PrefetchLoader(ds, prefetch=True, epochs=1).batches())
        off = list(PrefetchLoader(ds, prefetch=False, epochs=1).batches())
        assert len(on) == len(off) > 1
        for (b1, c1), (b2, c2) in zip(on, off):
            assert c1 == c2
            _assert_batches_equal(b1, b2)

    def test_cursor_resume_bit_identical(self, shard_dir):
        ds = ShardDataset(shard_dir, _bcfg())
        full = list(PrefetchLoader(ds, prefetch=False, epochs=1).batches())
        for k in (1, len(full) // 2, len(full) - 1):
            resume_at = full[k - 1][1]
            resumed = list(PrefetchLoader(ds, prefetch=True,
                                          epochs=1).batches(resume_at))
            assert len(resumed) == len(full) - k
            for (b1, c1), (b2, c2) in zip(full[k:], resumed):
                assert c1 == c2
                _assert_batches_equal(b1, b2)

    def test_epochs_cycle_and_cursor_epoch(self, shard_dir):
        ds = ShardDataset(shard_dir, _bcfg())
        one = list(PrefetchLoader(ds, prefetch=False, epochs=1).batches())
        two = list(PrefetchLoader(ds, prefetch=False, epochs=2).batches())
        assert len(two) == 2 * len(one)
        assert two[len(one) - 1][1] == Cursor(epoch=1, shard=0, batch=0)
        for (b1, _), (b2, _) in zip(one, two[len(one):]):
            _assert_batches_equal(b1, b2)

    def test_empty_dir_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            ShardDataset(str(tmp_path), _bcfg())


class TestCursorStore:
    def test_save_load(self, tmp_path):
        store = CursorStore(str(tmp_path))
        assert store.load(4) is None
        store.save(4, Cursor(epoch=1, shard=2, batch=3))
        assert store.load(4) == Cursor(1, 2, 3)
        assert store.steps() == [4]

    def test_fingerprint_mismatch_raises(self, tmp_path):
        store = CursorStore(str(tmp_path))
        store.save(4, Cursor(0, 1, 2), fingerprint="aaaa")
        assert store.load(4, fingerprint="aaaa") == Cursor(0, 1, 2)
        with pytest.raises(ValueError, match="different batch stream"):
            store.load(4, fingerprint="bbbb")

    def test_keep_last_prunes(self, tmp_path):
        store = CursorStore(str(tmp_path), keep_last=2)
        for s in (10, 20, 30, 40):
            store.save(s, Cursor(0, 0, s))
        assert store.steps() == [30, 40]

    def test_source_rejects_changed_batcher_cfg(self, shard_dir, tmp_path):
        """A cursor saved under one BatcherConfig must not silently drive
        a stream packed under another."""
        import dataclasses
        src = PipelineDataSource(
            PrefetchLoader(ShardDataset(shard_dir, _bcfg()),
                           prefetch=False),
            CursorStore(str(tmp_path)))
        it = src.batch_iter_fn(0)
        for _ in range(3):
            next(it)
        src.on_checkpoint(2)
        other_cfg = dataclasses.replace(_bcfg(), b_nro=64)
        src2 = PipelineDataSource(
            PrefetchLoader(ShardDataset(shard_dir, other_cfg),
                           prefetch=False),
            CursorStore(str(tmp_path)))
        with pytest.raises(ValueError, match="different batch stream"):
            src2.batch_iter_fn(2)

    def test_out_of_range_cursor_raises(self, shard_dir):
        loader = PrefetchLoader(ShardDataset(shard_dir, _bcfg()),
                                prefetch=False, epochs=1)
        with pytest.raises(ValueError, match="out of range"):
            next(loader.batches(Cursor(epoch=0, shard=0, batch=999)))

    def test_fallback_replay_without_cursor(self, shard_dir, tmp_path):
        """No persisted cursor -> deterministic replay-and-skip."""
        ds = ShardDataset(shard_dir, _bcfg())
        loader = PrefetchLoader(ds, prefetch=False)
        src = PipelineDataSource(loader, CursorStore(str(tmp_path)))
        it_full = src.batch_iter_fn(0)
        ref = [next(it_full) for _ in range(6)]
        src2 = PipelineDataSource(PrefetchLoader(ds, prefetch=False),
                                  CursorStore(str(tmp_path / "other")))
        it_skip = src2.batch_iter_fn(3)
        for want in ref[3:]:
            _assert_batches_equal(want, next(it_skip))


class TestTrainerKillAndRestart:
    """events -> join -> shards -> prefetch loader -> Trainer, killed and
    restarted: the (shard, offset) cursor must resume with bit-identical
    batches (checked via bit-identical final params vs an uninterrupted
    run — any divergence in the replayed batch stream would show up)."""

    def _make_trainer(self, ckpt_dir, total=12):
        from repro.train.loop import Trainer, TrainLoopConfig
        from repro.train.optim import sgd

        def loss_fn(params, batch, rng):
            pred = batch.ro_dense @ params["w"]
            tgt = jax.ops.segment_sum(batch.labels[:, 0],
                                      batch.segment_ids,
                                      num_segments=batch.b_ro + 1)[:-1]
            return jnp.mean((pred[:, 0] - tgt) ** 2)

        def init_params():
            return {"w": jnp.ones((16, 1))}

        cfg = TrainLoopConfig(total_steps=total, ckpt_every=4,
                              log_every=100, ckpt_dir=ckpt_dir)
        return Trainer(loss_fn, sgd(lr=0.01), cfg, init_params)

    def _source(self, shard_dir, cursor_dir, prefetch=True):
        loader = PrefetchLoader(ShardDataset(shard_dir, _bcfg()),
                                prefetch=prefetch)
        return PipelineDataSource(loader, CursorStore(cursor_dir))

    def test_resume_bit_identical(self, shard_dir, tmp_path):
        rng = jax.random.PRNGKey(0)
        # uninterrupted reference
        src = self._source(shard_dir, str(tmp_path / "cur_full"))
        t_full = self._make_trainer(str(tmp_path / "full"))
        s_full = t_full.run(src.batch_iter_fn, rng,
                            on_checkpoint=src.on_checkpoint)
        # killed at step 6 (last commit: step 4), restarted in a fresh
        # process sim with a fresh loader
        src_a = self._source(shard_dir, str(tmp_path / "cur_pre"))
        t_a = self._make_trainer(str(tmp_path / "pre"))
        t_a.run(src_a.batch_iter_fn, rng, stop_after=6,
                on_checkpoint=src_a.on_checkpoint)
        store = CursorStore(str(tmp_path / "cur_pre"))
        assert store.steps() == [4]          # cursor committed with ckpt
        src_b = self._source(shard_dir, str(tmp_path / "cur_pre"),
                             prefetch=False)  # resume works in either mode
        t_b = self._make_trainer(str(tmp_path / "pre"))
        s_res = t_b.run(src_b.batch_iter_fn, rng,
                        on_checkpoint=src_b.on_checkpoint)
        assert int(s_res["step"]) == 12
        np.testing.assert_array_equal(np.asarray(s_full["params"]["w"]),
                                      np.asarray(s_res["params"]["w"]))
