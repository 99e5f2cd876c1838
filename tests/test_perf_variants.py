"""The §Perf optimized variants must preserve training semantics: the
sparse-update / sparse-exchange DLRM steps and the hoisted MACE path
compute the same math as their baselines (small-scale, real mesh)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np


class TestMACEHoistEquivalence:
    def test_bit_identical(self, rng):
        from repro.models.gnn.mace import MACEConfig, mace_forward, mace_init
        cfg = MACEConfig(channels=8, n_feat_in=4)
        p = mace_init(rng, cfg)
        r = np.random.RandomState(0)
        n, e, g = 20, 48, 2
        args = (jnp.asarray(r.normal(size=(n, 4)).astype(np.float32)),
                jnp.asarray(r.normal(size=(n, 3)).astype(np.float32)),
                jnp.asarray(r.randint(0, n, (e, 2)).astype(np.int32)),
                jnp.ones((e,), bool),
                jnp.asarray(np.sort(r.randint(0, g, n)).astype(np.int32)), g)
        a = mace_forward(p, cfg, *args)["energy"]
        b = mace_forward(p, cfg, *args, hoist_gathers=True)["energy"]
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestSparseRowUpdateEquivalence:
    def test_matches_dense_rowwise_adagrad(self, rng):
        """_sparse_row_update (no mesh) == dense row-wise adagrad on the
        touched rows, when ids are unique."""
        from repro.configs.recsys_cells import _sparse_row_update
        from repro.distributed.sharding import replicated_plan
        v, d, b = 64, 8, 12
        table = jax.random.normal(rng, (v, d))
        acc = jnp.zeros((v,))
        ids = jnp.asarray(np.random.RandomState(0).choice(v, b, replace=False)
                          .astype(np.int32))
        g = jax.random.normal(jax.random.fold_in(rng, 1), (b, d))
        new_t, new_a = _sparse_row_update(table, acc, ids, g,
                                          plan=replicated_plan(),
                                          sharded=False, lr=0.1, eps=1e-8)
        # dense reference
        gd = jnp.zeros((v, d)).at[ids].add(g)
        acc_ref = acc + jnp.zeros((v,)).at[ids].add(jnp.mean(g * g, -1))
        scale = 0.1 / (jnp.sqrt(acc_ref) + 0.0)
        upd = jnp.where(acc_ref[:, None] > 0,
                        gd * (0.1 * jax.lax.rsqrt(acc_ref + 1e-8))[:, None],
                        0.0)
        np.testing.assert_allclose(np.asarray(new_t), np.asarray(table - upd),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(new_a), np.asarray(acc_ref),
                                   atol=1e-6)

    def test_sharded_exchange_equals_local(self):
        """opt2's shard_map sparse exchange == single-device update
        (4-device subprocess)."""
        code = '''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.recsys_cells import _sparse_row_update
from repro.distributed.sharding import plan_for_mesh, replicated_plan
from repro.launch.mesh import auto_mesh
mesh = auto_mesh((2, 2), ("data", "model"))
plan = plan_for_mesh(mesh)
rng = jax.random.PRNGKey(0)
v, d, b = 64, 8, 16
table = jax.random.normal(rng, (v, d))
acc = jnp.zeros((v,))
ids = jnp.asarray(np.random.RandomState(0).choice(v, b, replace=False).astype(np.int32))
g = jax.random.normal(jax.random.fold_in(rng, 1), (b, d))
t1, a1 = _sparse_row_update(table, acc, ids, g, plan=replicated_plan(),
                            sharded=False, lr=0.1, eps=1e-8)
with mesh:
    t2, a2 = jax.jit(lambda t, a, i, gg: _sparse_row_update(
        t, a, i, gg, plan=plan, sharded=True, lr=0.1, eps=1e-8))(
        table, acc, ids, g)
np.testing.assert_allclose(np.asarray(t1), np.asarray(t2), atol=1e-5)
np.testing.assert_allclose(np.asarray(a1), np.asarray(a2), atol=1e-6)
print("SPARSE_EXCHANGE_OK")
'''
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"     # the child must never claim a chip
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                         "src")
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, env=env, timeout=300)
        assert "SPARSE_EXCHANGE_OK" in r.stdout, r.stderr[-2000:]


class TestLMSpmdLayerEquivalence:
    def test_megatron_sp_matches_gspmd_path(self):
        """The explicit shard_map layer == the constraint-based layer
        (tiny model, 4-device subprocess)."""
        code = '''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.models.lm.transformer import LMConfig, lm_init, lm_forward
from repro.distributed.sharding import plan_for_mesh
from repro.launch.mesh import auto_mesh
mesh = auto_mesh((2, 2), ("data", "model"))
plan = plan_for_mesh(mesh)
cfg = LMConfig(name="t", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
               d_head=8, d_ff=64, vocab=128, compute_dtype="float32")
p = lm_init(jax.random.PRNGKey(0), cfg)
toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 128)
with mesh:
    h1 = jax.jit(lambda pp, t: lm_forward(pp, cfg, t, plan))(p, toks)
    cfg2 = dataclasses.replace(cfg, use_spmd_layer=True)
    h2 = jax.jit(lambda pp, t: lm_forward(pp, cfg2, t, plan))(p, toks)
np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=2e-4)
print("SPMD_LAYER_OK")
'''
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"     # the child must never claim a chip
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                         "src")
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, env=env, timeout=300)
        assert "SPMD_LAYER_OK" in r.stdout, r.stderr[-2000:]
