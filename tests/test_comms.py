"""Unit tests for repro.distributed.comms — the compressed/overlapped
sparse-exchange layer (ISSUE 10).

Single-device: quantizer round-trip bounds (hypothesis property tests),
the straight-through estimator, wire-byte accounting, the error-feedback
residual's 50-step boundedness (dense and SparseRows), and the CommsStats
obs mirror.  The multi-device trajectory-parity tests live in
tests/test_distributed_train.py::TestCompressedOverlappedExchange.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st

from repro.distributed import comms
from repro.embeddings.sparse import SparseRows
from repro.obs import metrics as obs_metrics


# ---------------------------------------------------------------------------
# Quantizer round-trip bounds
# ---------------------------------------------------------------------------

class TestQuantizerBounds:
    @settings(max_examples=30, deadline=None)
    @given(st.integers(min_value=0, max_value=2**31 - 1),
           st.integers(min_value=1, max_value=6),
           st.sampled_from([8, 16, 32, 64, 128]),
           st.floats(min_value=1e-3, max_value=1e3))
    def test_int8_per_block_error_bound(self, seed, rows, block, scale):
        """Per-block symmetric int8: |x - dq(q(x))| <= blockmax/254 + eps
        elementwise, where blockmax is the max-abs of the element's own
        scale block (scale = blockmax/127, rounding error <= scale/2)."""
        x = (np.asarray(jax.random.normal(
            jax.random.PRNGKey(seed), (rows, block * 2))) * scale)
        out = np.asarray(comms.fake_quant(jnp.asarray(x), "int8", block))
        xb = x.reshape(rows, 2, block)
        blockmax = np.max(np.abs(xb), axis=-1, keepdims=True)
        bound = blockmax / 254.0 + 1e-6
        err = np.abs(xb - out.reshape(rows, 2, block))
        assert np.all(err <= bound), (err.max(), bound.min())

    @settings(max_examples=30, deadline=None)
    @given(st.integers(min_value=0, max_value=2**31 - 1),
           st.floats(min_value=1e-3, max_value=1e3))
    def test_bf16_relative_error_bound(self, seed, scale):
        """bf16 keeps 8 significand bits: relative round-trip error is at
        most 2^-8 (half-ulp 2^-9, bound doubled for safety margin)."""
        x = (np.asarray(jax.random.normal(jax.random.PRNGKey(seed), (64,)))
             * scale)
        out = np.asarray(comms.fake_quant(jnp.asarray(x), "bf16", 0))
        rel = np.abs(x - out) / np.maximum(np.abs(x), 1e-30)
        assert np.all(rel <= 2.0 ** -8), rel.max()

    def test_none_is_identity(self):
        x = jnp.arange(12.0).reshape(3, 4)
        np.testing.assert_array_equal(np.asarray(
            comms.fake_quant(x, "none", 0)), np.asarray(x))

    def test_unknown_mode_raises(self):
        with pytest.raises(ValueError, match="unknown comms"):
            comms.fake_quant(jnp.zeros((2, 2)), "fp4", 0)

    def test_effective_block_falls_back_to_row(self):
        # block divides evenly -> used; otherwise one scale per row
        assert comms._effective_block(128, 32) == 32
        assert comms._effective_block(96, 128) == 96
        assert comms._effective_block(100, 32) == 100

    def test_int8_scale_shape(self):
        q, s = comms.quantize_int8(jnp.ones((4, 64)), 32)
        assert q.shape == (4, 2, 32) and q.dtype == jnp.int8
        assert s.shape == (4, 2, 1)

    def test_ste_gradient_is_identity(self):
        g = jax.grad(lambda x: jnp.sum(
            comms.wire_transform(x, "int8", 8)))(jnp.linspace(-2, 2, 16))
        np.testing.assert_array_equal(np.asarray(g), 1.0)


# ---------------------------------------------------------------------------
# Wire-byte accounting
# ---------------------------------------------------------------------------

class TestWireBytes:
    def test_per_mode_ratios(self):
        shape = (32, 128)
        f32 = comms.wire_bytes(shape, "none")
        assert f32 == 32 * 128 * 4
        assert f32 / comms.wire_bytes(shape, "bf16") == 2.0
        # int8 + one f32 scale per 128-block: 4 / (1 + 4/128) ~ 3.88
        assert f32 / comms.wire_bytes(shape, "int8", 128) >= 2.0

    def test_int8_scale_overhead_counted(self):
        # D=8, block 8: per row 8 bytes payload + 4 bytes scale
        assert comms.wire_bytes((2, 8), "int8", 8) == 2 * (8 + 4)

    def test_empty_tensor(self):
        assert comms.wire_bytes((0, 128), "int8", 128) == 0


# ---------------------------------------------------------------------------
# Error feedback
# ---------------------------------------------------------------------------

class TestErrorFeedback:
    def test_dense_residual_bounded_over_50_steps(self):
        """EF telescopes: sum of applied (sent) grads differs from the sum
        of true grads by exactly the final residual, which is bounded by a
        single quantization step — independent of the step count."""
        rng = np.random.default_rng(0)
        e = jnp.zeros((16, 32))
        sent_sum = np.zeros((16, 32))
        true_sum = np.zeros((16, 32))
        max_step_bound = 0.0
        for _ in range(50):
            g = jnp.asarray(rng.normal(size=(16, 32)) * 0.01)
            sent, e = comms.ef_compress_step(
                {"t": g}, {"t": e}, "int8", 32)
            e = e["t"]
            sent_sum += np.asarray(sent["t"])
            true_sum += np.asarray(g)
            max_step_bound = max(
                max_step_bound,
                float(jnp.max(jnp.abs(g + e))) / 254.0 + 1e-6)
        drift = np.max(np.abs(sent_sum - true_sum))
        # drift == |final residual| <= one quantization step
        np.testing.assert_allclose(drift, float(jnp.max(jnp.abs(e))),
                                   rtol=1e-4, atol=1e-7)
        assert drift <= max_step_bound, (drift, max_step_bound)

    def test_sparse_rows_residual_scatter(self):
        """SparseRows EF: only touched unique rows ride the quantizer, the
        residual lands on exactly those rows, and padding (ids == vocab)
        is dropped."""
        V, D = 8, 16
        e0 = jnp.zeros((V, D))
        ids = jnp.array([1, 3, 3, V], dtype=jnp.int32)   # dup + padding
        rows = jnp.ones((4, D)) * jnp.array([1.0, 2.0, 3.0, 99.0])[:, None]
        g = SparseRows(ids, rows, V)
        sent, e1 = comms.ef_compress_step(
            {"t": g}, {"t": e0}, "int8", D)
        s, e1 = sent["t"], e1["t"]
        assert s.unique
        merged = np.zeros((V, D))
        m = g.merged()
        # reconstruct dense from sent COO and compare to true dense grad
        for i, r in zip(np.asarray(s.ids), np.asarray(s.rows)):
            if i < V:
                merged[i] += r
        dense_true = np.zeros((V, D))
        dense_true[1] = 1.0
        dense_true[3] = 5.0                       # 2 + 3 merged
        np.testing.assert_allclose(merged + np.asarray(e1), dense_true,
                                   atol=1e-5)
        # untouched rows keep zero residual; padding row 99.0 never lands
        untouched = np.setdiff1d(np.arange(V), np.asarray(m.ids))
        assert np.all(np.asarray(e1)[untouched] == 0.0)

    def test_mode_none_passthrough(self):
        g = {"t": jnp.ones((4, 4))}
        sent, res = comms.ef_compress_step(g, {"t": jnp.zeros((4, 4))},
                                           "none", 4)
        assert sent is g

    def test_ef_init_selects_sharded_tables_only(self):
        from repro.distributed.spmd import SHARD_MIN_ROWS
        params = {
            "big_emb": jnp.zeros((SHARD_MIN_ROWS * 2, 8)),
            "tiny_emb": jnp.zeros((SHARD_MIN_ROWS // 2, 8)),
            "dense": {"w": jnp.zeros((8, 8))},
        }
        ef = comms.ef_init(params, plan=None)
        assert set(ef) == {"big_emb"}
        assert ef["big_emb"].shape == (SHARD_MIN_ROWS * 2, 8)
        assert ef["big_emb"].dtype == jnp.float32


# ---------------------------------------------------------------------------
# CommsStats + obs mirror
# ---------------------------------------------------------------------------

class TestCommsStats:
    def test_snapshot_and_obs_mirror(self):
        # NOTE: no obs_metrics.reset() here — it would unregister mirrors
        # that only install at module import (reliability.faults); the
        # comms mirror re-registers itself on every record call, which is
        # the property this test relies on
        comms.STATS.reset()
        comms.STATS.record_exchange("lookup:t0", (32, 128), mode="int8",
                                    block=128, dedup=True)
        comms.STATS.record_exchange("grad:t0", (64, 128), mode="int8",
                                    block=128, kind="grad")
        comms.STATS.record_overlap(4, True)
        snap = comms.STATS.snapshot()
        assert snap["exchanges"] == 2
        assert snap["dedup_exchanges"] == 1
        assert snap["compression_ratio"] >= 2.0
        assert snap["overlap"]["occupancy"] == 0.75
        assert snap["overlap"]["deferred_grad_exchanges_per_step"] == 3
        # mirrored into the unified obs snapshot (re-registers after reset)
        assert (obs_metrics.snapshot()["components"]["distributed.comms"]
                ["exchanges"] == 2)

    def test_retrace_overwrites_site(self):
        comms.STATS.reset()
        for _ in range(3):     # retraces must not double-count
            comms.STATS.record_exchange("lookup:t0", (8, 8), mode="bf16")
        assert comms.STATS.snapshot()["exchanges"] == 1

    def test_psum_scatter_halves_bytes(self):
        comms.STATS.reset()
        comms.STATS.record_exchange("a", (8, 8), mode="none")
        full = comms.STATS.snapshot()["f32_bytes_per_step"]
        comms.STATS.reset()
        comms.STATS.record_exchange("a", (8, 8), mode="none",
                                    collective="psum_scatter")
        assert comms.STATS.snapshot()["f32_bytes_per_step"] == full // 2


class TestKnobs:
    def test_knob_ladder_and_validation(self):
        from repro.scenario.knobs import UNSET
        assert comms.compress_mode() == "none"
        assert comms.block_size() == 128
        assert not comms.overlap_enabled()
        comms.COMPRESS_KNOB.set_default("int8")
        try:
            assert comms.compress_mode() == "int8"
            assert comms.compress_mode("bf16") == "bf16"   # explicit wins
        finally:
            comms.COMPRESS_KNOB.set_default(UNSET)
        with pytest.raises(ValueError):
            comms.compress_mode("fp4")
