"""Cell builders for the recsys architectures (ROO is native here).

Shapes (assigned):
  train_batch     batch=65 536   -> ROO train step (B_NRO=65 536, B_RO=16 384)
  serve_p99       batch=512      -> online inference (B_RO=128)
  serve_bulk      batch=262 144  -> offline scoring (B_RO=65 536)
  retrieval_cand  batch=1, n_candidates=10⁶ -> one user vs 1 000 448 items
                  (padded to a 512-multiple), batched dot — never a loop.

``batch`` counts impressions (B_NRO); B_RO = batch/4 reflects the paper's
4–7 impressions-per-request regime (Fig. 2). Embedding tables are
row-sharded over `model`; batch tensors shard over the (pod,)data axes.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import Cell, sds
from repro.core.roo_batch import ROOBatch
from repro.distributed.sharding import ShardingPlan
from repro.models.dlrm import (DLRMConfig, dlrm_flops_per_example,
                               dlrm_forward_roo, dlrm_init)
from repro.models.din_dien import DIENConfig, dien_init, dien_logits_roo
from repro.models.bert4rec import (BERT4RecConfig, bert4rec_init, encode as b4r_encode)
from repro.models.mind import MINDConfig, interest_capsules, mind_init
from repro.train.metrics import bce
from repro.train.optim import adam, default_is_embedding, make_mixed, rowwise_adagrad

RECSYS_SHAPES = {
    "train_batch": dict(kind="train", b_nro=65536, b_ro=16384),
    "serve_p99": dict(kind="serve", b_nro=512, b_ro=128),
    "serve_bulk": dict(kind="serve", b_nro=262144, b_ro=65536),
    "retrieval_cand": dict(kind="serve", b_nro=1000448, b_ro=32),
}

N_ITEMS = 8388608          # 2^23-row item catalog (production-scale table)


def _mk_batch(history_ids, history_lengths, item_ids, segment_ids, labels,
              ro_dense=None, hist_cap=None):
    """Assemble a ROOBatch from plain tensors (unused fields zeroed)."""
    b_ro = history_ids.shape[0]
    b_nro = item_ids.shape[0]
    nl = labels if labels is not None else jnp.zeros((b_nro, 2), jnp.float32)
    return ROOBatch(
        ro_dense=(ro_dense if ro_dense is not None
                  else jnp.zeros((b_ro, 1), jnp.float32)),
        ro_sparse=None,
        history_ids=history_ids,
        history_actions=jnp.zeros_like(history_ids),
        history_lengths=history_lengths,
        nro_dense=jnp.zeros((b_nro, 1), jnp.float32),
        nro_sparse=None,
        item_ids=item_ids,
        labels=nl,
        num_impressions=jnp.full((b_ro,), b_nro // b_ro, jnp.int32),
        segment_ids=segment_ids)


def _mixed_opt():
    return make_mixed(adam(1e-3), rowwise_adagrad(0.05), default_is_embedding)


def _train_cell(arch, shape_name, sh, plan, init_fn, cell_loss, specs_fn,
                pspecs_fn, param_pspecs, flops):
    """Generic recsys train cell: cell_loss(params, inputs) + mixed opt."""
    opt = _mixed_opt()

    def abstract_state():
        params = jax.eval_shape(init_fn)
        return {"params": params, "opt": jax.eval_shape(opt.init, params),
                "step": sds((), jnp.int32)}

    def state_pspecs(plan):
        params = jax.eval_shape(init_fn)
        pp = param_pspecs(params)
        flat, _ = jax.tree_util.tree_flatten_with_path(params)
        emb_mask = [default_is_embedding(tuple(str(k) for k in path))
                    for path, _ in flat]
        pp_leaves = jax.tree.leaves(pp, is_leaf=lambda x: isinstance(x, P))
        emb_specs = [s for s, m in zip(pp_leaves, emb_mask) if m]
        dense_specs = [s for s, m in zip(pp_leaves, emb_mask) if not m]
        # row-wise adagrad state: (rows,) per table -> first axis of the spec
        emb_acc = [P(s[0]) if len(s) else P() for s in emb_specs]
        return {"params": pp,
                "opt": {"emb": {"acc": emb_acc},
                        "dense": {"m": dense_specs, "v": dense_specs,
                                  "t": P()}},
                "step": P()}

    def step(state, inputs):
        loss, grads = jax.value_and_grad(
            lambda p: cell_loss(p, inputs))(state["params"])
        new_p, new_opt = opt.update(grads, state["opt"], state["params"])
        return {"params": new_p, "opt": new_opt,
                "step": state["step"] + 1}, loss

    return Cell(arch, shape_name, "train", step, abstract_state, state_pspecs,
                specs_fn, pspecs_fn, flops)


def _serve_cell(arch, shape_name, plan, init_fn, fwd_fn, specs_fn, pspecs_fn,
                param_pspecs, flops):
    def abstract_state():
        return {"params": jax.eval_shape(init_fn)}

    def state_pspecs(plan):
        return {"params": param_pspecs(jax.eval_shape(init_fn))}

    def step(state, inputs):
        return fwd_fn(state["params"], inputs)

    return Cell(arch, shape_name, "serve", step, abstract_state, state_pspecs,
                specs_fn, pspecs_fn, flops)


# ---------------------------------------------------------------------------
# dlrm-mlperf
# ---------------------------------------------------------------------------

def build_dlrm_cell(shape_name: str, plan: ShardingPlan,
                    opt_level: str = "baseline") -> Cell:
    """opt_level:
      impression — pre-ROO baseline: RO features looked up at B_NRO
                   (user-side lookups duplicated per impression);
      baseline   — paper-faithful ROO (RO side at B_RO, one fanout);
      opt        — beyond-paper: bf16 embedding collectives + SPARSE
                   row-wise-Adagrad updates (no dense (V,D) gradient /
                   optimizer sweep; only touched rows move).
    """
    sh = RECSYS_SHAPES[shape_name]
    b_ro, b_nro = sh["b_ro"], sh["b_nro"]
    cfg = DLRMConfig()
    m = plan.model_axis
    if opt_level == "impression" and sh["kind"] == "train":
        return _build_dlrm_impression(shape_name, sh, plan, cfg)
    if opt_level == "opt" and sh["kind"] == "train":
        return _build_dlrm_opt(shape_name, sh, plan, cfg)
    if opt_level == "opt2" and sh["kind"] == "train":
        return _build_dlrm_opt(shape_name, sh, plan, cfg,
                               sparse_exchange=True)

    def init_fn():
        return dlrm_init(jax.random.PRNGKey(0), cfg)

    def param_pspecs(params):
        # big tables row-sharded over `model`; tiny ones replicated
        return {
            "tables": {k: (P(m, None)
                           if params["tables"][k].shape[0]
                           >= DLRMConfig.SHARD_MIN_ROWS else P(None, None))
                       for k in params["tables"]},
            "bot_mlp": jax.tree.map(lambda _: P(), params["bot_mlp"]),
            "top_mlp": jax.tree.map(lambda _: P(), params["top_mlp"]),
        }

    def fwd(p, inputs):
        ones_ro = jnp.ones((b_ro, cfg.n_ro_fields), jnp.int32)
        ones_nro = jnp.ones((b_nro, cfg.n_sparse - cfg.n_ro_fields), jnp.int32)
        return dlrm_forward_roo(p, cfg, inputs["ro_dense"], inputs["ro_ids"],
                                ones_ro, inputs["nro_ids"], ones_nro,
                                inputs["segment_ids"])

    def cell_loss(p, inputs):
        return bce(fwd(p, inputs), inputs["labels"])

    def specs_fn():
        s = {"ro_dense": sds((b_ro, 13)),
             "ro_ids": sds((b_ro, cfg.n_ro_fields, 1), jnp.int32),
             "nro_ids": sds((b_nro, cfg.n_sparse - cfg.n_ro_fields, 1),
                            jnp.int32),
             "segment_ids": sds((b_nro,), jnp.int32)}
        if sh["kind"] == "train":
            s["labels"] = sds((b_nro,))
        return s

    def pspecs_fn(plan):
        ba = plan.batch_axes
        s = {"ro_dense": P(ba, None), "ro_ids": P(ba, None, None),
             "nro_ids": P(ba, None, None), "segment_ids": P(ba)}
        if sh["kind"] == "train":
            s["labels"] = P(ba)
        return s

    flops = dlrm_flops_per_example(cfg) * b_nro * (3 if sh["kind"] == "train" else 1)
    if sh["kind"] == "train":
        return _train_cell("dlrm-mlperf", shape_name, sh, plan, init_fn,
                           cell_loss, specs_fn, pspecs_fn, param_pspecs, flops)
    return _serve_cell("dlrm-mlperf", shape_name, plan, init_fn,
                       lambda p, i: fwd(p, i), specs_fn, pspecs_fn,
                       param_pspecs, flops)


def _build_dlrm_impression(shape_name, sh, plan, cfg) -> Cell:
    """Pre-ROO ablation: user-side lookups run at B_NRO (duplicated)."""
    from repro.core.fanout import fanout
    b_ro, b_nro = sh["b_ro"], sh["b_nro"]

    def init_fn():
        return dlrm_init(jax.random.PRNGKey(0), cfg)

    base = build_dlrm_cell(shape_name, plan, "baseline")

    def cell_loss(p, inputs):
        ones_ro = jnp.ones((b_nro, cfg.n_ro_fields), jnp.int32)
        ones_nro = jnp.ones((b_nro, cfg.n_sparse - cfg.n_ro_fields), jnp.int32)
        # expand RO ids/dense to impression level FIRST (the waste ROO removes)
        ro_ids_nro = fanout(inputs["ro_ids"], inputs["segment_ids"])
        ro_dense_nro = fanout(inputs["ro_dense"], inputs["segment_ids"])
        from repro.models.dlrm import _field_lookup, dlrm_forward_from_embs
        ro_embs = _field_lookup(p, cfg, ro_ids_nro, ones_ro,
                                range(cfg.n_ro_fields))
        nro_embs = _field_lookup(p, cfg, inputs["nro_ids"], ones_nro,
                                 range(cfg.n_ro_fields, cfg.n_sparse))
        logits = dlrm_forward_from_embs(
            p, cfg, ro_dense_nro, ro_embs, nro_embs,
            jnp.arange(b_nro, dtype=jnp.int32))
        return bce(logits, inputs["labels"])

    opt = _mixed_opt()

    def step(state, inputs):
        loss, grads = jax.value_and_grad(
            lambda p: cell_loss(p, inputs))(state["params"])
        new_p, new_opt = opt.update(grads, state["opt"], state["params"])
        return {"params": new_p, "opt": new_opt,
                "step": state["step"] + 1}, loss

    return Cell("dlrm-mlperf", shape_name, "train", step,
                base.abstract_state, base.state_pspecs, base.input_specs,
                base.input_pspecs, base.model_flops,
                notes="impression-level ablation (pre-ROO)")


def _sparse_row_update(table, acc, ids, g, *, plan, sharded: bool,
                       lr: float, eps: float):
    """Row-wise-Adagrad on touched rows ONLY, with sparse (ids, grads)
    exchange across data shards (TorchRec all-to-all semantics) instead of
    the dense table-sized all-reduce GSPMD would otherwise emit.

    table: (V, D) P(model, None) if sharded else replicated; acc: (V,);
    ids: (B,) and g: (B, D) batch-sharded.
    """
    if not plan.enabled:
        acc2 = acc.at[ids].add(jnp.mean(g * g, axis=-1))
        scale = lr * jax.lax.rsqrt(jnp.take(acc2, ids) + eps)
        return table.at[ids].add(-(scale[:, None] * g).astype(table.dtype)), acc2

    m, ba = plan.model_axis, plan.batch_axes
    P_ = P

    def fn(tbl, ac, ids_l, g_l):
        # sparse exchange: every device learns every (id, grad) pair —
        # O(touched rows), not O(table)
        ids_all = jax.lax.all_gather(ids_l, ba, axis=0, tiled=True)
        g_all = jax.lax.all_gather(g_l, ba, axis=0, tiled=True).astype(
            jnp.float32)
        rows = tbl.shape[0]
        if sharded:
            shard = jax.lax.axis_index(m)
            local = ids_all - shard * rows
            ok = (local >= 0) & (local < rows)
        else:
            local = ids_all
            ok = (local >= 0) & (local < rows)
        li = jnp.where(ok, local, rows)                    # park OOB
        okf = ok.astype(jnp.float32)
        ac2 = ac.at[li].add(jnp.mean(g_all * g_all, -1) * okf, mode="drop")
        scale = lr * jax.lax.rsqrt(
            jnp.take(ac2, jnp.clip(li, 0, rows - 1)) + eps) * okf
        tbl2 = tbl.at[li].add(-(scale[:, None] * g_all).astype(tbl.dtype),
                              mode="drop")
        return tbl2, ac2

    t_spec = P_(m, None) if sharded else P_(None, None)
    a_spec = P_(m) if sharded else P_(None)
    return jax.shard_map(
        fn, mesh=plan.mesh,
        in_specs=(t_spec, a_spec, P_(ba), P_(ba, None)),
        out_specs=(t_spec, a_spec),
        check_vma=False)(table, acc, ids, g)


def _build_dlrm_opt(shape_name, sh, plan, cfg, sparse_exchange=False) -> Cell:
    """Beyond-paper: bf16 embedding collectives + sparse row updates.
    ``sparse_exchange``: iter-4 variant — exchange (ids, grads) pairs under
    shard_map instead of letting GSPMD densify the scatter across data."""
    b_ro, b_nro = sh["b_ro"], sh["b_nro"]
    base = build_dlrm_cell(shape_name, plan, "baseline")
    adam_opt = adam(1e-3)
    lr_emb, eps = 0.05, 1e-8

    def init_fn():
        return dlrm_init(jax.random.PRNGKey(0), cfg)

    def step(state, inputs):
        params = state["params"]
        tables = params["tables"]
        dense_params = {"bot_mlp": params["bot_mlp"],
                        "top_mlp": params["top_mlp"]}
        names = sorted(tables.keys(), key=lambda k: int(k[1:]))
        ro_names = names[:cfg.n_ro_fields]
        nro_names = names[cfg.n_ro_fields:]
        # explicit gathers in bf16 (halves the lookup psum bytes);
        # differentiate wrt the GATHERED rows, not the (V,D) tables
        ro_g = [jnp.take(tables[n].astype(jnp.bfloat16),
                         jnp.clip(inputs["ro_ids"][:, j, 0], 0,
                                  tables[n].shape[0] - 1), axis=0)
                for j, n in enumerate(ro_names)]
        nro_g = [jnp.take(tables[n].astype(jnp.bfloat16),
                          jnp.clip(inputs["nro_ids"][:, j, 0], 0,
                                   tables[n].shape[0] - 1), axis=0)
                 for j, n in enumerate(nro_names)]

        from repro.models.dlrm import dlrm_forward_from_embs

        def loss_fn(dp, rg, ng):
            ro_embs = jnp.stack([e.astype(jnp.float32) for e in rg], 1)
            nro_embs = jnp.stack([e.astype(jnp.float32) for e in ng], 1)
            logits = dlrm_forward_from_embs(
                {**dp, "tables": tables}, cfg, inputs["ro_dense"],
                ro_embs, nro_embs, inputs["segment_ids"])
            return bce(logits, inputs["labels"])

        loss, grads = jax.value_and_grad(
            loss_fn, argnums=(0, 1, 2))(dense_params, ro_g, nro_g)
        g_dense, g_ro, g_nro = grads

        # dense params: adam (same as baseline; state is leaf-list based)
        dense_leaves, dense_def = jax.tree_util.tree_flatten(dense_params)
        g_leaves = jax.tree.leaves(g_dense)
        new_leaves, new_adam = adam_opt.update(g_leaves,
                                               state["opt"]["dense"],
                                               dense_leaves)
        new_dense = jax.tree_util.tree_unflatten(dense_def, new_leaves)
        # tables: SPARSE row-wise adagrad — touch only looked-up rows
        accs = list(state["opt"]["emb"]["acc"])
        new_tables = dict(tables)
        # acc list order == pytree order of emb leaves (sorted key strings)
        acc_order = sorted(names)
        acc_by_name = dict(zip(acc_order, accs))
        for j, n in enumerate(ro_names + nro_names):
            ids_arr = (inputs["ro_ids"][:, j, 0] if j < cfg.n_ro_fields
                       else inputs["nro_ids"][:, j - cfg.n_ro_fields, 0])
            g = (g_ro[j] if j < cfg.n_ro_fields
                 else g_nro[j - cfg.n_ro_fields]).astype(jnp.float32)
            ids_arr = jnp.clip(ids_arr, 0, tables[n].shape[0] - 1)
            if sparse_exchange:
                is_sharded = tables[n].shape[0] >= DLRMConfig.SHARD_MIN_ROWS
                new_tables[n], acc_by_name[n] = _sparse_row_update(
                    tables[n], acc_by_name[n], ids_arr, g, plan=plan,
                    sharded=is_sharded, lr=lr_emb, eps=eps)
            else:
                acc = acc_by_name[n]
                acc = acc.at[ids_arr].add(jnp.mean(g * g, axis=-1))
                scale = lr_emb * jax.lax.rsqrt(jnp.take(acc, ids_arr) + eps)
                new_tables[n] = tables[n].at[ids_arr].add(
                    -(scale[:, None] * g).astype(tables[n].dtype))
                acc_by_name[n] = acc
        new_accs = [acc_by_name[n] for n in acc_order]
        new_params = {"tables": new_tables, "bot_mlp": new_dense["bot_mlp"],
                      "top_mlp": new_dense["top_mlp"]}
        new_opt = {"emb": {"acc": new_accs}, "dense": new_adam}
        return {"params": new_params, "opt": new_opt,
                "step": state["step"] + 1}, loss

    return Cell("dlrm-mlperf", shape_name, "train", step,
                base.abstract_state, base.state_pspecs, base.input_specs,
                base.input_pspecs, base.model_flops,
                notes="bf16 collectives + sparse row-wise adagrad")


# ---------------------------------------------------------------------------
# mind
# ---------------------------------------------------------------------------

def build_mind_cell(shape_name: str, plan: ShardingPlan) -> Cell:
    sh = RECSYS_SHAPES[shape_name]
    b_ro, b_nro = sh["b_ro"], sh["b_nro"]
    cfg = MINDConfig(n_items=N_ITEMS, hist_len=64)
    m = plan.model_axis
    n_neg = 8192

    def init_fn():
        return mind_init(jax.random.PRNGKey(0), cfg)

    def param_pspecs(params):
        return {"item_emb": P(m, None), "S": P()}

    def user_caps(p, inputs):
        return interest_capsules(p, cfg, inputs["history_ids"],
                                 inputs["history_lengths"])

    def cell_loss(p, inputs):
        """Sampled-softmax over shared negatives, positives = clicks."""
        from repro.core.fanout import fanout
        caps = user_caps(p, inputs)                           # (B_RO,K,d)
        caps_nro = fanout(caps, inputs["segment_ids"])
        tgt = jnp.take(p["item_emb"],
                       jnp.clip(inputs["item_ids"], 0, cfg.n_items - 1), axis=0)
        att = jax.nn.softmax(cfg.pow_p * jnp.einsum("bkd,bd->bk", caps_nro, tgt), -1)
        u = jnp.einsum("bk,bkd->bd", att, caps_nro)
        pos = jnp.sum(u * tgt, -1) / 0.1                      # (B_NRO,)
        neg_emb = jnp.take(p["item_emb"],
                           jnp.clip(inputs["neg_ids"], 0, cfg.n_items - 1),
                           axis=0)                            # (n_neg, d)
        neg = (u @ neg_emb.T) / 0.1                           # (B_NRO, n_neg)
        lse = jnp.logaddexp(jax.scipy.special.logsumexp(neg, -1), pos)
        nll = lse - pos
        w = inputs["labels"]
        return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)

    def serve_fwd(p, inputs):
        caps = user_caps(p, inputs)                           # (B_RO,K,d)
        cand = jnp.take(p["item_emb"],
                        jnp.clip(inputs["item_ids"], 0, cfg.n_items - 1), axis=0)
        if shape_name == "retrieval_cand":
            scores = jnp.einsum("bkd,cd->bkc", caps, cand)    # (B_RO,K,C)
            return jnp.max(scores, axis=1)                    # (B_RO, C)
        from repro.core.fanout import fanout
        caps_nro = fanout(caps, inputs["segment_ids"])
        return jnp.max(jnp.einsum("bkd,bd->bk", caps_nro, cand), -1)

    def specs_fn():
        s = {"history_ids": sds((b_ro, cfg.hist_len), jnp.int32),
             "history_lengths": sds((b_ro,), jnp.int32),
             "item_ids": sds((b_nro,), jnp.int32)}
        if shape_name != "retrieval_cand":
            s["segment_ids"] = sds((b_nro,), jnp.int32)
        if sh["kind"] == "train":
            s["labels"] = sds((b_nro,))
            s["neg_ids"] = sds((n_neg,), jnp.int32)
        return s

    def pspecs_fn(plan):
        ba = plan.batch_axes
        s = {"history_ids": P(ba, None), "history_lengths": P(ba),
             "item_ids": P(ba)}
        if shape_name != "retrieval_cand":
            s["segment_ids"] = P(ba)
        if sh["kind"] == "train":
            s["labels"] = P(ba)
            s["neg_ids"] = P(None)
        return s

    d, kk = cfg.embed_dim, cfg.n_interests
    flops = (b_ro * cfg.capsule_iters * 2 * cfg.hist_len * kk * d   # routing
             + b_ro * 2 * cfg.hist_len * d * d                      # S map
             + b_nro * 2 * kk * d
             + (b_nro * 2 * n_neg * d if sh["kind"] == "train" else 0))
    flops *= 3 if sh["kind"] == "train" else 1
    if sh["kind"] == "train":
        return _train_cell("mind", shape_name, sh, plan, init_fn, cell_loss,
                           specs_fn, pspecs_fn, param_pspecs, flops)
    return _serve_cell("mind", shape_name, plan, init_fn, serve_fwd, specs_fn,
                       pspecs_fn, param_pspecs, flops)


# ---------------------------------------------------------------------------
# bert4rec
# ---------------------------------------------------------------------------

def build_bert4rec_cell(shape_name: str, plan: ShardingPlan) -> Cell:
    sh = RECSYS_SHAPES[shape_name]
    b_ro, b_nro = sh["b_ro"], sh["b_nro"]
    cfg = BERT4RecConfig(n_items=N_ITEMS, seq_len=200)
    m = plan.model_axis
    n_neg = 8192
    n_mask = 16

    def init_fn():
        return bert4rec_init(jax.random.PRNGKey(0), cfg)

    def param_pspecs(params):
        return {"item_emb": P(m, None), "pos_emb": P(),
                "blocks": jax.tree.map(lambda _: P(), params["blocks"]),
                "out_bias": P(m)}

    def cell_loss(p, inputs):
        """Sampled cloze: mask the last n_mask valid positions, score vs
        positives + shared negatives."""
        ids = inputs["history_ids"]
        lens = inputs["history_lengths"]
        b = ids.shape[0]
        # mask the trailing n_mask valid positions per row
        pos_idx = jnp.maximum(lens[:, None] - 1 - jnp.arange(n_mask)[None], 0)
        tgt = jnp.take_along_axis(ids, pos_idx, axis=1)       # (B, n_mask)
        masked = jnp.asarray(ids).at[
            jnp.arange(b)[:, None], pos_idx].set(1)           # MASK token
        enc = b4r_encode(p, cfg, masked, lens)                # (B,S,d)
        q = jnp.take_along_axis(
            enc, pos_idx[..., None].astype(jnp.int32), axis=1)  # (B,n_mask,d)
        tgt_e = jnp.take(p["item_emb"],
                         jnp.clip(tgt, 0, cfg.n_items - 1), axis=0)
        pos_s = jnp.sum(q * tgt_e, -1)                        # (B, n_mask)
        neg_e = jnp.take(p["item_emb"],
                         jnp.clip(inputs["neg_ids"], 0, cfg.n_items - 1), axis=0)
        neg_s = jnp.einsum("bmd,nd->bmn", q, neg_e)
        lse = jnp.logaddexp(jax.scipy.special.logsumexp(neg_s, -1), pos_s)
        nll = lse - pos_s
        w = (pos_idx > 0).astype(nll.dtype)
        return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)

    def serve_fwd(p, inputs):
        ids = inputs["history_ids"]
        lens = jnp.minimum(inputs["history_lengths"], cfg.seq_len - 1)
        b = ids.shape[0]
        ids_ext = jnp.asarray(ids).at[jnp.arange(b), lens].set(1)
        enc = b4r_encode(p, cfg, ids_ext, lens + 1)
        q = enc[jnp.arange(b), lens]                          # (B_RO, d)
        cand = jnp.take(p["item_emb"],
                        jnp.clip(inputs["item_ids"], 0, cfg.n_items - 1), axis=0)
        if shape_name == "retrieval_cand":
            return q @ cand.T                                 # (B_RO, C)
        from repro.core.fanout import fanout
        return jnp.sum(fanout(q, inputs["segment_ids"]) * cand, -1)

    def specs_fn():
        s = {"history_ids": sds((b_ro, cfg.seq_len), jnp.int32),
             "history_lengths": sds((b_ro,), jnp.int32)}
        if sh["kind"] == "train":
            s["neg_ids"] = sds((n_neg,), jnp.int32)
            s["labels"] = sds((b_nro,))
        else:
            s["item_ids"] = sds((b_nro,), jnp.int32)
            if shape_name != "retrieval_cand":
                s["segment_ids"] = sds((b_nro,), jnp.int32)
        return s

    def pspecs_fn(plan):
        ba = plan.batch_axes
        s = {"history_ids": P(ba, None), "history_lengths": P(ba)}
        if sh["kind"] == "train":
            s["neg_ids"] = P(None)
            s["labels"] = P(ba)
        else:
            s["item_ids"] = P(ba)
            if shape_name != "retrieval_cand":
                s["segment_ids"] = P(ba)
        return s

    d, sl = cfg.embed_dim, cfg.seq_len
    enc_flops = b_ro * cfg.n_blocks * (8 * sl * d * d + 4 * sl * sl * d
                                       + 4 * sl * d * cfg.d_ff)
    flops = enc_flops + (b_ro * n_mask * n_neg * 2 * d
                         if sh["kind"] == "train" else b_nro * 2 * d)
    flops *= 3 if sh["kind"] == "train" else 1
    if sh["kind"] == "train":
        return _train_cell("bert4rec", shape_name, sh, plan, init_fn,
                           cell_loss, specs_fn, pspecs_fn, param_pspecs, flops)
    return _serve_cell("bert4rec", shape_name, plan, init_fn, serve_fwd,
                       specs_fn, pspecs_fn, param_pspecs, flops)


# ---------------------------------------------------------------------------
# dien
# ---------------------------------------------------------------------------

def build_dien_cell(shape_name: str, plan: ShardingPlan) -> Cell:
    sh = RECSYS_SHAPES[shape_name]
    b_ro, b_nro = sh["b_ro"], sh["b_nro"]
    cfg = DIENConfig(n_items=N_ITEMS, seq_len=100, n_ro_dense=16)
    m = plan.model_axis

    def init_fn():
        return dien_init(jax.random.PRNGKey(0), cfg)

    def param_pspecs(params):
        pp = jax.tree.map(lambda _: P(), params)
        pp["item_emb"] = P(m, None)
        return pp

    def fwd(p, inputs):
        batch = _mk_batch(inputs["history_ids"], inputs["history_lengths"],
                          inputs["item_ids"], inputs["segment_ids"],
                          inputs.get("labels_2d"),
                          ro_dense=inputs["ro_dense"])
        return dien_logits_roo(p, cfg, batch)

    def cell_loss(p, inputs):
        return bce(fwd(p, inputs), inputs["labels"])

    def specs_fn():
        s = {"history_ids": sds((b_ro, cfg.seq_len), jnp.int32),
             "history_lengths": sds((b_ro,), jnp.int32),
             "ro_dense": sds((b_ro, cfg.n_ro_dense)),
             "item_ids": sds((b_nro,), jnp.int32),
             "segment_ids": sds((b_nro,), jnp.int32)}
        if sh["kind"] == "train":
            s["labels"] = sds((b_nro,))
        return s

    def pspecs_fn(plan):
        ba = plan.batch_axes
        s = {"history_ids": P(ba, None), "history_lengths": P(ba),
             "ro_dense": P(ba, None), "item_ids": P(ba),
             "segment_ids": P(ba)}
        if sh["kind"] == "train":
            s["labels"] = P(ba)
        return s

    d, h, t = cfg.embed_dim, cfg.gru_dim, cfg.seq_len
    gru = 6 * (d * h + h * h)
    flops = (b_ro * t * gru                       # extraction GRU (RO!)
             + b_nro * t * (6 * (h * h + h * h))  # AUGRU at B_NRO
             + b_nro * t * 2 * (2 * h + d) * 64   # attention MLP
             + b_nro * 2 * (h + d + 16) * 200)
    flops *= 3 if sh["kind"] == "train" else 1
    if sh["kind"] == "train":
        return _train_cell("dien", shape_name, sh, plan, init_fn, cell_loss,
                           specs_fn, pspecs_fn, param_pspecs, flops)
    return _serve_cell("dien", shape_name, plan, init_fn,
                       lambda p, i: fwd(p, i), specs_fn, pspecs_fn,
                       param_pspecs, flops)
