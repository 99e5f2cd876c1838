"""Jagged tensors, batcher invariants, embeddings — incl. hypothesis
property tests on the system's core data invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.data.jagged import JaggedTensor
from repro.embeddings.bag import bag_lookup, bag_lookup_dense


class TestJaggedTensor:
    def test_roundtrip_padded(self):
        rows = [[1, 2, 3], [4], [], [5, 6]]
        jt = JaggedTensor.from_lists(rows, capacity=16)
        dense, mask = jt.to_padded(4)
        np.testing.assert_array_equal(np.asarray(dense[0, :3]), [1, 2, 3])
        np.testing.assert_array_equal(np.asarray(mask.sum(1)), [3, 1, 0, 2])

    def test_segment_ids_mark_padding(self):
        jt = JaggedTensor.from_lists([[1, 2], [3]], capacity=8)
        seg = np.asarray(jt.segment_ids())
        np.testing.assert_array_equal(seg[:3], [0, 0, 1])
        assert (seg[3:] == 2).all()

    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.lists(st.integers(0, 99), max_size=6), min_size=1,
                    max_size=8))
    def test_property_offsets_consistent(self, rows):
        cap = max(sum(len(r) for r in rows), 1) + 4
        jt = JaggedTensor.from_lists(rows, capacity=cap)
        offs = np.asarray(jt.offsets)
        lens = np.asarray(jt.lengths)
        assert offs[0] == 0
        np.testing.assert_array_equal(np.diff(offs), lens[:-1])
        # values round-trip
        vals = np.asarray(jt.values)
        for i, r in enumerate(rows):
            np.testing.assert_array_equal(vals[offs[i]:offs[i] + len(r)], r)

    def test_from_dense_roundtrip(self):
        dense = jnp.arange(12.0).reshape(3, 4)
        lengths = jnp.asarray([2, 4, 1])
        jt = JaggedTensor.from_dense(dense, lengths, capacity=8)
        back, mask = jt.to_padded(4)
        for i, l in enumerate([2, 4, 1]):
            np.testing.assert_array_equal(np.asarray(back[i, :l]),
                                          np.asarray(dense[i, :l]))


class TestBatcher:
    def test_request_locality_per_shard(self, roo_samples):
        """The invariant fanout_local depends on: a request's impressions
        live in the request's shard region."""
        from repro.data.batcher import BatcherConfig, ROOBatcher
        cfg = BatcherConfig(b_ro=32, b_nro=256, n_shards=4)
        for batch in ROOBatcher(cfg).batches(roo_samples):
            seg = np.asarray(batch.segment_ids)
            per_ro = cfg.b_ro // cfg.n_shards
            per_nro = cfg.b_nro // cfg.n_shards
            for slot in range(cfg.b_nro):
                if seg[slot] < cfg.b_ro:
                    assert seg[slot] // per_ro == slot // per_nro

    def test_no_impression_lost(self, roo_samples):
        from repro.data.batcher import BatcherConfig, ROOBatcher
        cfg = BatcherConfig(b_ro=32, b_nro=256)
        total = 0
        for batch in ROOBatcher(cfg).batches(roo_samples):
            total += int(batch.num_valid_impressions())
        expect = sum(min(s.num_impressions, 256) for s in roo_samples)
        assert total == expect

    def test_local_segment_ids_mode(self, roo_samples):
        from repro.data.batcher import BatcherConfig, ROOBatcher
        cfg = BatcherConfig(b_ro=32, b_nro=256, n_shards=4,
                            local_segment_ids=True)
        batch = next(ROOBatcher(cfg).batches(roo_samples))
        seg = np.asarray(batch.segment_ids)
        assert seg.max() <= cfg.b_ro // cfg.n_shards   # local ids

    @staticmethod
    def _mk_request(uid, n_items):
        from repro.core.joiner import ROOSample
        return ROOSample(
            request_id=uid, user_id=uid,
            ro_dense=np.ones((4,), np.float32), ro_idlist=[1],
            history_ids=[1, 2], history_actions=[1, 0],
            item_ids=list(range(n_items)),
            item_dense=[np.ones((4,), np.float32)] * n_items,
            item_idlist=[[1]] * n_items,
            labels=[{"click": 0.0, "view_sec": 0.0}] * n_items)

    def test_truncation_counted_and_warned(self):
        """Oversize requests used to be truncated silently; drops are now a
        per-batch stat + warning so training-data loss is observable."""
        from repro.data.batcher import BatcherConfig, ROOBatcher
        batcher = ROOBatcher(BatcherConfig(b_ro=4, b_nro=8))
        with pytest.warns(UserWarning, match="dropped 12 impression"):
            out = list(batcher.batches_with_plan([self._mk_request(1, 20)]))
        assert len(out) == 1
        _, plan = out[0]
        (p,) = plan.requests
        assert (p.n_total, p.n_packed, p.n_dropped) == (20, 8, 12)
        assert batcher.stats.n_impressions_dropped == 12
        assert batcher.stats.n_requests_truncated == 1
        assert batcher.stats.n_impressions_packed == 8

    def test_no_warning_without_truncation(self, roo_samples):
        import warnings as _warnings
        from repro.data.batcher import BatcherConfig, ROOBatcher
        batcher = ROOBatcher(BatcherConfig(b_ro=32, b_nro=256))
        with _warnings.catch_warnings():
            _warnings.simplefilter("error")
            list(batcher.batches_with_plan(roo_samples))
        assert batcher.stats.n_impressions_dropped == 0
        assert batcher.stats.n_requests == len(roo_samples)

    def test_plan_slot_mapping(self, roo_samples):
        """Plan invariants: a request's impressions are the contiguous slots
        [slot_start, slot_start+n_packed) of its row; real slots are covered
        exactly once; every input request appears in exactly one plan."""
        from repro.data.batcher import BatcherConfig, ROOBatcher
        cfg = BatcherConfig(b_ro=16, b_nro=128)
        seen = []
        for batch, plan in ROOBatcher(cfg).batches_with_plan(roo_samples):
            seg = np.asarray(batch.segment_ids)
            covered = np.zeros((cfg.b_nro,), bool)
            for p in plan.requests:
                seen.append(p.request_index)
                sl = slice(p.slot_start, p.slot_start + p.n_packed)
                assert (seg[sl] == p.row).all()
                assert not covered[sl].any()
                covered[sl] = True
            assert covered.sum() == (seg < cfg.b_ro).sum()
        assert sorted(seen) == list(range(len(roo_samples)))


class TestEmbeddingBag:
    @pytest.mark.parametrize("pooling", ["sum", "mean", "max"])
    def test_pooling_modes(self, pooling, rng):
        table = jax.random.normal(rng, (50, 8))
        jt = JaggedTensor.from_lists([[1, 2, 3], [4], []], capacity=8)
        out = bag_lookup(table, jt, pooling)
        t = np.asarray(table)
        if pooling == "sum":
            want0 = t[1] + t[2] + t[3]
        elif pooling == "mean":
            want0 = (t[1] + t[2] + t[3]) / 3
        else:
            want0 = np.maximum(np.maximum(t[1], t[2]), t[3])
        np.testing.assert_allclose(np.asarray(out[0]), want0, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(out[2]), 0.0)   # empty bag

    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 8), st.integers(1, 6), st.integers(0, 999))
    def test_property_dense_jagged_agree(self, b, l, seed):
        r = np.random.RandomState(seed)
        table = jnp.asarray(r.normal(size=(40, 4)).astype(np.float32))
        ids = r.randint(0, 40, size=(b, l)).astype(np.int32)
        lens = r.randint(0, l + 1, size=(b,)).astype(np.int32)
        dense = bag_lookup_dense(table, jnp.asarray(ids), jnp.asarray(lens))
        rows = [ids[i, :lens[i]].tolist() for i in range(b)]
        jt = JaggedTensor.from_lists(rows, capacity=b * l + 1)
        jagged = bag_lookup(table, jt, "sum")
        np.testing.assert_allclose(np.asarray(dense), np.asarray(jagged),
                                   atol=1e-5)


class TestShardedLookupSubprocess:
    def test_sharded_equals_replicated(self):
        """Row-sharded shard_map lookup == plain bag (4-device subprocess)."""
        import subprocess, sys, os
        code = '''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.embeddings.sharded import sharded_bag_lookup
from repro.embeddings.bag import bag_lookup_dense
from repro.launch.mesh import auto_mesh
mesh = auto_mesh((2, 2), ("data", "model"))
rng = jax.random.PRNGKey(0)
table = jax.random.normal(rng, (64, 8))
ids = jax.random.randint(rng, (8, 5), 0, 64)
lens = jax.random.randint(jax.random.fold_in(rng, 1), (8,), 0, 6)
out = sharded_bag_lookup(table, ids, lens, mesh=mesh, vocab=64)
want = bag_lookup_dense(table, ids, lens)
np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
# grads flow to the sharded table identically
def loss_sharded(t):
    return jnp.sum(sharded_bag_lookup(t, ids, lens, mesh=mesh, vocab=64) ** 2)
def loss_plain(t):
    return jnp.sum(bag_lookup_dense(t, ids, lens) ** 2)
g1 = jax.grad(loss_sharded)(table)
g2 = jax.grad(loss_plain)(table)
np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)
print("SHARDED_OK")
'''
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"     # the child must never claim a chip
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, env=env, timeout=300)
        assert "SHARDED_OK" in r.stdout, r.stderr[-2000:]
