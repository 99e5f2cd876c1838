"""dlrm-mlperf on the normal path: the published widths, the ROOBatch loss
against the plain reference (``chipbench/reference/dlrm_mlperf.py``) at a
small size, the fanout against the impression-level forward, and the
registered scenario training through ``Trainer`` on ``ROOBatch``es."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.models.dlrm_mlperf import Model  # noqa: E402
from chipbench.reference import dlrm_mlperf as ref  # noqa: E402
from repro.core.fanout import fanout  # noqa: E402
from repro.models.dlrm import (MLPERF_VOCABS, DLRMConfig,  # noqa: E402
                               dlrm_batch_fields, dlrm_flops_per_example,
                               dlrm_forward_impression, dlrm_init,
                               dlrm_logits_roo, dlrm_loss, dlrm_sample)

OPT = {"lr_dense": 1e-3, "adam_b1": 0.9, "adam_b2": 0.999, "adam_eps": 1e-8,
       "lr_emb": 0.05, "adagrad_eps": 1e-8}
# 26 tables of at most 1,000 rows, the widths cut
SMALL = {"model": "dlrm_mlperf", "n_dense": 13, "embed_dim": 16,
         "bot_mlp": [13, 32, 16], "top_mlp": [64, 32, 1], "n_ro_fields": 13,
         "multi_hot": 1, "hist_len": 0,
         "vocabs": [min(v, 1000) for v in MLPERF_VOCABS],
         "optimizer": OPT, "train": {"b_ro": 8, "b_nro": 48}}
TRAFFIC = {"mode": "train",
           "impressions": {"law": "lognormal", "median": 4, "sigma": 0.8,
                           "min": 1, "max": 16},
           "ids": {"law": "zipf", "exponent": 1.05},
           "dense": {"law": "normal", "mean": 0.0, "sigma": 1.0},
           "label_rates": [0.3], "pool_batches": 3}
SEED = 2 ** 35 + 17


@pytest.fixture(scope="module")
def setup():
    m = Model(SMALL)
    return m, m.make_params(SEED), m.train_pool(TRAFFIC, SEED)


def test_full_width_parameter_shapes():
    cfg = DLRMConfig()
    shapes = jax.eval_shape(lambda: dlrm_init(jax.random.PRNGKey(0), cfg))
    top = [(lyr["w"].shape, lyr["b"].shape)
           for lyr in shapes["top_mlp"]["layers"]]
    dims = [479, 1024, 1024, 512, 256, 1]
    assert top == [((a, b), (b,)) for a, b in zip(dims[:-1], dims[1:])]
    assert [lyr["w"].shape for lyr in shapes["bot_mlp"]["layers"]] == [
        (13, 512), (512, 256), (256, 128)]
    tables = shapes["tables"]
    assert len(tables) == 26
    assert all(tables[f"t{i}"].shape == (cfg.padded_vocab(v), 128)
               for i, v in enumerate(MLPERF_VOCABS))
    assert sum(MLPERF_VOCABS) == 187_767_399
    mlp = 2 * (13 * 512 + 512 * 256 + 256 * 128 + 479 * 1024 + 1024 * 1024
               + 1024 * 512 + 512 * 256 + 256)
    assert dlrm_flops_per_example(cfg) == mlp + 2 * 27 * 27 * 128


@jax.jit
def _program(params, batch):
    cfg = Model(SMALL).cfg       # traced once: the config is static
    return (dlrm_logits_roo(params, cfg, batch),
            jax.value_and_grad(lambda p: dlrm_loss(p, cfg, batch))(params))


@jax.jit
def _reference(params, x):
    n = x["labels"].shape[0]
    return (ref.logits(params, x, SMALL),
            jax.value_and_grad(lambda p: ref.loss_sum(p, x, SMALL) / n)(params))


def test_loss_logits_and_gradients_match_the_reference(setup):
    m, params, pool = setup
    batch, x = pool.batches[0], pool.reference[0]
    n = x["labels"].shape[0]
    with jax.default_matmul_precision("highest"):
        logits, (loss, grads) = _program(params, batch)
        want, (ref_loss, ref_grads) = _reference(
            params, {k: jnp.asarray(v) for k, v in x.items()})
    # the real impressions fill the first slots, in request order
    np.testing.assert_allclose(np.asarray(logits[:n]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert np.all(np.asarray(batch.segment_ids[n:]) == batch.b_ro)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref_flat = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    assert len(flat) == len(ref_flat) == 26 + 2 * 2 + 2 * 3
    for path, g in flat:
        r = ref_flat[path]
        scale = float(jnp.max(jnp.abs(r))) + 1e-12
        gap = float(jnp.max(jnp.abs(g - r))) / scale
        assert gap < 1e-4, (jax.tree_util.keystr(path), gap)


def test_three_trainer_steps_match_the_reference(setup):
    from repro.train.loop import Trainer, TrainLoopConfig
    from repro.train.optim import (adam, default_is_embedding, make_mixed,
                                   rowwise_adagrad)
    m, params, pool = setup
    opt = make_mixed(adam(OPT["lr_dense"], OPT["adam_b1"], OPT["adam_b2"],
                          OPT["adam_eps"]),
                     rowwise_adagrad(OPT["lr_emb"], OPT["adagrad_eps"]),
                     default_is_embedding)
    trainer = Trainer(m.loss_fn(), opt, TrainLoopConfig(), lambda: params)
    state, losses = trainer.init_state(), []
    with jax.default_matmul_precision("highest"):
        for i in range(3):
            state, met = trainer.step_fn(state, pool.batches[i],
                                         jax.random.PRNGKey(i))
            losses.append(float(met["loss"]))
        ref_losses, _, ref_change, names = ref.train_steps(
            m.make_params(SEED), pool.reference, SMALL, OPT)
    # both in float32 on the host: they part by summation order only, which
    # Adam's first steps can carry into ~1e-6 of a leaf's change
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)
    start = jax.tree_util.tree_leaves(m.make_params(SEED))
    change = [float(jnp.linalg.norm(a - b)) for a, b in
              zip(jax.tree_util.tree_leaves(state["params"]), start)]
    assert len(change) == len(names)
    np.testing.assert_allclose(change, ref_change, rtol=1e-4)
    assert min(ref_change) > 0


def test_roo_batch_equals_the_impression_level_forward(setup):
    m, params, pool = setup
    cfg, batch = m.cfg, pool.batches[1]
    ro_ids, ro_len, nro_ids, nro_len = dlrm_batch_fields(cfg, batch)
    seg = batch.segment_ids
    expanded = jax.jit(lambda p: dlrm_forward_impression(
        p, cfg, fanout(batch.ro_dense, seg),
        jnp.concatenate([fanout(ro_ids, seg), nro_ids], axis=1),
        jnp.concatenate([fanout(ro_len, seg), nro_len], axis=1)))(params)
    valid = np.asarray(batch.impression_mask())
    assert valid.sum() == pool.impressions[1]
    np.testing.assert_allclose(
        np.asarray(jax.jit(dlrm_logits_roo, static_argnums=1)(
            params, cfg, batch))[valid],
        np.asarray(expanded)[valid], rtol=1e-5, atol=1e-6)


def test_fields_are_read_back_as_packed(setup):
    m, _, pool = setup
    ro_ids, ro_len, nro_ids, nro_len = dlrm_batch_fields(m.cfg,
                                                         pool.batches[0])
    reqs = pool.requests[0]
    assert ro_ids.shape == (8, 13, 1) and nro_ids.shape == (48, 13, 1)
    np.testing.assert_array_equal(np.asarray(ro_ids)[:, :, 0],
                                  [r.ro_idlist for r in reqs])
    items = [ids for r in reqs for ids in r.item_idlist]
    np.testing.assert_array_equal(np.asarray(nro_ids)[:len(items), :, 0],
                                  items)
    assert np.all(np.asarray(ro_len) == 1)
    lens = np.asarray(nro_len)
    assert np.all(lens[:len(items)] == 1) and np.all(lens[len(items):] == 0)


def test_stream_samples_keep_a_users_and_an_items_rows(roo_samples):
    cfg = DLRMConfig(vocabs=tuple(min(v, 1000) for v in MLPERF_VOCABS))
    out = [dlrm_sample(s, cfg) for s in roo_samples[:40]]
    by_user, by_item = {}, {}
    for s, d in zip(roo_samples, out):
        assert d.ro_dense.shape == (13,) and d.history_ids == []
        assert len(d.ro_idlist) == 13
        assert by_user.setdefault(s.user_id, d.ro_idlist) == d.ro_idlist
        for item, ids in zip(s.item_ids, d.item_idlist):
            assert by_item.setdefault(item, ids) == ids
        ids = np.asarray([d.ro_idlist] + d.item_idlist)
        assert np.all(ids >= 0)
        assert np.all(ids[0] < cfg.vocabs[:13])
        assert np.all(ids[1:] < cfg.vocabs[13:])


def test_stages_are_named_in_the_lowered_step(setup):
    m, params, pool = setup
    text = jax.jit(jax.grad(lambda p: dlrm_loss(p, m.cfg, pool.batches[0]))
                   ).lower(params).as_text(debug_info=True)
    for stage in ("bottom_mlp", "lookup_ro", "lookup_nro", "fanout",
                  "interaction", "top_mlp"):
        assert f"dlrm.{stage}" in text, stage


def test_registered_scenario_trains_on_roo_batches_at_published_widths():
    from repro.configs.registry import scenario
    from repro.scenario.build import train_from_scenario
    spec = scenario("dlrm-mlperf", {"train.steps": 2, "train.log_every": 1,
                                    "data.n_requests": 120})
    trainer, state = train_from_scenario(spec, prints=False)
    assert int(state["step"]) == 2
    assert all(np.isfinite(r["loss"]) for r in trainer.history)
    p = state["params"]
    assert [lyr["w"].shape[1] for lyr in p["top_mlp"]["layers"]] == [
        1024, 1024, 512, 256, 1]
    assert p["top_mlp"]["layers"][0]["w"].shape[0] == 479
    assert [p["tables"][f"t{i}"].shape for i in range(26)] == [
        (min(v, 1000), 128) for v in MLPERF_VOCABS]
