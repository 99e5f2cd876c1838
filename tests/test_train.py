"""Optimizers, metrics, checkpoint fault-tolerance, gradient compression,
elastic reshard, and the preemption-resume integration test."""
import os

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.train.checkpoint import CheckpointManager
from repro.train.compression import (compressed_bytes, ef_compress_grads,
                                     ef_init)
from repro.train.loop import Trainer, TrainLoopConfig
from repro.train.metrics import auc, normalized_entropy
from repro.train.optim import (adam, default_is_embedding, make_mixed,
                               rowwise_adagrad, sgd)


class TestOptim:
    def test_adam_minimizes_quadratic(self):
        opt = adam(lr=0.1)
        params = {"w": jnp.asarray([5.0, -3.0])}
        state = opt.init(params)
        for _ in range(200):
            grads = {"w": 2 * params["w"]}
            params, state = opt.update(grads, state, params)
        assert float(jnp.max(jnp.abs(params["w"]))) < 1e-2

    def test_rowwise_adagrad_state_is_per_row(self):
        opt = rowwise_adagrad(lr=0.1)
        params = [jnp.ones((10, 4))]
        state = opt.init(params)
        assert state["acc"][0].shape == (10,)
        grads = [jnp.ones((10, 4))]
        new_p, state = opt.update(grads, state, params)
        assert new_p[0].shape == (10, 4)
        assert float(jnp.max(new_p[0])) < 1.0

    def test_mixed_routes_by_path(self):
        params = {"item_emb": jnp.ones((8, 4)), "mlp": {"w": jnp.ones((4, 4))}}
        opt = make_mixed(adam(1e-2), rowwise_adagrad(0.1),
                         default_is_embedding)
        state = opt.init(params)
        grads = jax.tree.map(jnp.ones_like, params)
        new_p, state = opt.update(grads, state, params)
        assert new_p["item_emb"].shape == (8, 4)
        assert "acc" in state["emb"]
        assert "m" in state["dense"]

    def test_mixed_under_jit(self):
        params = {"item_emb": jnp.ones((8, 4)), "w": jnp.ones((4,))}
        opt = make_mixed(adam(1e-2), rowwise_adagrad(0.1),
                         default_is_embedding)
        state = opt.init(params)

        @jax.jit
        def step(p, s):
            g = jax.tree.map(jnp.ones_like, p)
            return opt.update(g, s, p)
        new_p, _ = step(params, state)
        assert float(new_p["w"][0]) < 1.0


class TestMetrics:
    def test_ne_perfect_predictor_below_one(self):
        labels = jnp.asarray([0., 1., 0., 1., 0., 0., 1., 0.] * 32)
        good = (labels * 2 - 1) * 4.0
        ne_good = float(normalized_entropy(good, labels))
        base = jnp.zeros_like(labels) + jnp.log(3 / 5)   # logit of base rate
        ne_base = float(normalized_entropy(base, labels))
        assert ne_good < 0.4
        assert 0.95 < ne_base < 1.05

    def test_auc_orders(self):
        labels = jnp.asarray([0., 1.] * 256)
        logits = (labels * 2 - 1) * 3.0
        assert float(auc(logits, labels)) > 0.95

    def test_ne_surfaced_in_trainer_history(self):
        """make_ne_metrics plugs into Trainer(metrics_fn=...) and every
        logged history row carries a finite, shrinking NE."""
        from repro.train.metrics import make_ne_metrics
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(rng, (256, 8))
        w_true = jax.random.normal(jax.random.fold_in(rng, 1), (8,))
        y = (x @ w_true > 0).astype(jnp.float32)
        batch = {"x": x, "y": y}

        def logits_fn(p, b):
            return b["x"] @ p["w"], b["y"]

        def loss(p, b, r):
            logits = logits_fn(p, b)[0]
            return jnp.mean(jnp.maximum(logits, 0) - logits * b["y"]
                            + jnp.log1p(jnp.exp(-jnp.abs(logits))))

        trainer = Trainer(loss, sgd(0.5),
                          TrainLoopConfig(total_steps=30, log_every=5),
                          lambda: {"w": jnp.zeros((8,))},
                          metrics_fn=make_ne_metrics(logits_fn))
        trainer.run(lambda s: iter(lambda: batch, None), rng)
        nes = [row["ne"] for row in trainer.history]
        assert all(np.isfinite(nes))
        assert nes[-1] < nes[0] < 1.05       # learning shows up in NE


class TestCheckpoint:
    def test_atomic_save_restore(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_last=2)
        state = {"w": jnp.arange(6.0).reshape(2, 3), "step": jnp.asarray(7)}
        mgr.save(7, state)
        out = mgr.restore()
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(state["w"]))

    def test_keep_last_gc(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_last=2)
        for s in (1, 2, 3, 4):
            mgr.save(s, {"x": jnp.asarray(s)})
        assert mgr.all_steps() == [3, 4]

    def test_partial_write_ignored(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_last=3)
        mgr.save(5, {"x": jnp.asarray(5)})
        os.makedirs(os.path.join(str(tmp_path), "step_000000000009.tmp"))
        assert mgr.latest_step() == 5

    def test_async_save(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"x": jnp.ones((128, 128))}, blocking=False)
        mgr.wait()
        assert mgr.latest_step() == 1


class TestPreemptionResume:
    """Fault tolerance: kill training mid-run, restart, verify the resumed
    run continues exactly (same final params as an uninterrupted run)."""

    def _mk_trainer(self, ckpt_dir):
        def loss_fn(params, batch, rng):
            return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

        def init_params():
            return {"w": jnp.ones((4, 1))}

        cfg = TrainLoopConfig(total_steps=40, ckpt_every=10, log_every=100,
                              ckpt_dir=ckpt_dir)
        return Trainer(loss_fn, sgd(lr=0.05), cfg, init_params)

    def _batches(self, start_step):
        def gen():
            step = start_step
            while True:
                rng = np.random.RandomState(step)   # deterministic per step
                x = rng.normal(size=(8, 4)).astype(np.float32)
                yield {"x": jnp.asarray(x),
                       "y": jnp.asarray(x.sum(1, keepdims=True))}
                step += 1
        return gen()

    def test_resume_bit_continuation(self, tmp_path):
        rng = jax.random.PRNGKey(0)
        # uninterrupted
        t_full = self._mk_trainer(str(tmp_path / "full"))
        s_full = t_full.run(self._batches, rng)
        # preempted at step 25, restarted
        t_a = self._mk_trainer(str(tmp_path / "pre"))
        t_a.run(self._batches, rng, stop_after=25)
        t_b = self._mk_trainer(str(tmp_path / "pre"))   # fresh process sim
        s_resumed = t_b.run(self._batches, rng)
        assert int(s_resumed["step"]) == 40
        np.testing.assert_allclose(np.asarray(s_full["params"]["w"]),
                                   np.asarray(s_resumed["params"]["w"]),
                                   rtol=1e-6)

    def _mk_rng_trainer(self, ckpt_dir):
        """Loss that *uses* the per-step rng, so base-key provenance shows
        up in the final params."""
        def loss_fn(params, batch, rng):
            scale = jax.random.uniform(rng, (), minval=0.5, maxval=1.5)
            return scale * jnp.mean(
                (batch["x"] @ params["w"] - batch["y"]) ** 2)

        cfg = TrainLoopConfig(total_steps=40, ckpt_every=10, log_every=100,
                              ckpt_dir=ckpt_dir)
        # init away from the optimum so grads (and the rng loss scale)
        # actually move the params
        return Trainer(loss_fn, sgd(lr=0.05), cfg,
                       lambda: {"w": jnp.zeros((4, 1))})

    def test_rng_is_checkpointed_state(self, tmp_path):
        """The contract says state = {params, opt, step, rng}: the base key
        is part of the checkpoint, so a resume with a DIFFERENT rng argument
        still bit-continues the original run."""
        rng_a = jax.random.PRNGKey(0)
        rng_b = jax.random.PRNGKey(12345)
        t_full = self._mk_rng_trainer(str(tmp_path / "full"))
        s_full = t_full.run(self._batches, rng_a)
        assert "rng" in s_full                       # contract holds
        t_pre = self._mk_rng_trainer(str(tmp_path / "pre"))
        t_pre.run(self._batches, rng_a, stop_after=25)
        t_res = self._mk_rng_trainer(str(tmp_path / "pre"))
        s_res = t_res.run(self._batches, rng_b)      # different key arg
        np.testing.assert_array_equal(np.asarray(s_full["params"]["w"]),
                                      np.asarray(s_res["params"]["w"]))
        np.testing.assert_array_equal(np.asarray(s_full["rng"]),
                                      np.asarray(rng_a))
        # sanity: a full run under rng_b would NOT match
        t_other = self._mk_rng_trainer(str(tmp_path / "other"))
        s_other = t_other.run(self._batches, rng_b)
        assert not np.array_equal(np.asarray(s_full["params"]["w"]),
                                  np.asarray(s_other["params"]["w"]))


class TestGradAccumRng:
    """Regression: the grad-accumulation scan reused ONE rng for every
    microbatch, so dropout/sampling were identical across microbatches."""

    def test_microbatches_see_distinct_rng(self):
        from repro.train.loop import make_train_step

        def loss_fn(params, batch, rng):
            # gradient wrt w IS the rng draw — exposes rng reuse directly
            return params["w"] * jax.random.uniform(rng, ())

        m = 4
        rng = jax.random.PRNGKey(123)
        step = make_train_step(loss_fn, sgd(lr=0.0), microbatches=m)
        params = {"w": jnp.asarray(1.0)}
        state = {"params": params, "opt": sgd(lr=0.0).init(params),
                 "step": jnp.asarray(0)}
        batch = {"x": jnp.zeros((m, 1))}
        _, metrics = step(state, batch, rng)

        draws = np.array([float(jax.random.uniform(
            jax.random.fold_in(rng, i), ())) for i in range(m)])
        reused = float(jax.random.uniform(rng, ()))
        got = float(metrics["grad_norm"])   # |mean of per-microbatch draws|
        assert abs(got - draws.mean()) < 1e-5
        assert abs(got - reused) > 1e-4     # the old (buggy) value
        assert abs(float(metrics["loss"]) - draws.mean()) < 1e-5


class TestCompression:
    def test_error_feedback_unbiased(self):
        """Sum of transported grads + residual == sum of true grads."""
        grads = {"w": jnp.asarray(np.random.RandomState(0)
                                  .normal(size=(64,)).astype(np.float32))}
        err = ef_init(grads)
        total_sent = jnp.zeros((64,))
        total_true = jnp.zeros((64,))
        for i in range(20):
            g = {"w": grads["w"] * (i + 1) / 10.0}
            sent, err = ef_compress_grads(g, err, mode="bf16")
            total_sent = total_sent + sent["w"]
            total_true = total_true + g["w"]
        resid = err["w"]
        np.testing.assert_allclose(np.asarray(total_sent + resid),
                                   np.asarray(total_true), rtol=1e-3,
                                   atol=1e-4)

    def test_bytes_halved(self):
        g = {"w": jnp.ones((1000,), jnp.float32)}
        assert compressed_bytes(g, "bf16") == 2000
        assert compressed_bytes(g, "int8") == 1000

    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 1000))
    def test_int8_ef_bounded_error(self, seed):
        rng = np.random.RandomState(seed)
        g = {"w": jnp.asarray(rng.normal(size=(32,)).astype(np.float32))}
        err = ef_init(g)
        sent, err = ef_compress_grads(g, err, mode="int8")
        # one-step error bounded by quantization bin
        scale = float(jnp.max(jnp.abs(g["w"]))) / 127
        assert float(jnp.max(jnp.abs(err["w"]))) <= scale + 1e-6


class TestElasticReshard:
    def test_restore_onto_different_topology(self, tmp_path):
        """Save on one 'mesh', restore re-sharded (simulated on 1 device via
        device_put with None shardings — the reshard API contract)."""
        mgr = CheckpointManager(str(tmp_path))
        state = {"table": jnp.arange(64.0).reshape(16, 4)}
        mgr.save(3, state)
        out = mgr.restore_resharded({"table": None})
        np.testing.assert_array_equal(np.asarray(out["table"]),
                                      np.asarray(state["table"]))
