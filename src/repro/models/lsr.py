"""Late-stage ranking (LSR) model — the paper's Fig. 6 architecture.

Pipeline:   RO side (B_RO):  dense MLP + sparse bags + HSTU history encoder
                             -> UserArch (LCE compress)          [§3.2]
            fanout once      (the ROO amortization point)
            NRO side (B_NRO): item embeddings + dense
            interaction:      DCNv2 over flattened features
            top MLP:          multi-task logits (engagement, consumption)

Modes reproduce the paper's LSR ablation rows (Table 7):
  baseline      — no UserArch, no HSTU (plain DLRM-ish)
  userarch      — + LCE UserArch
  userarch_hstu — + HSTU history encoder feeding UserArch ("+HSTU" row)
  hstu_ranking  — + ROO sequential targets (core.sequence; GR-style ranking)
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.fanout import fanout
from repro.core.hstu import HSTUConfig, hstu_apply, hstu_init
from repro.core.lce import LCEConfig, lce_apply, lce_init
from repro.core.masks import causal_spec
from repro.core.roo_batch import ROOBatch
from repro.core.sequence import (ROOSequenceConfig, encode_roo,
                                 gather_targets_to_ro, roo_sequence_init,
                                 scatter_targets_to_nro)
from repro.embeddings import collection as ec
from repro.models.interactions import dcnv2_apply, dcnv2_init
from repro.models.mlp import mlp_apply, mlp_init


@dataclasses.dataclass(frozen=True)
class LSRConfig:
    n_items: int
    n_user_cats: int = 200
    n_item_cats: int = 200
    embed_dim: int = 64
    n_ro_dense: int = 16
    n_item_dense: int = 8
    hist_len: int = 64
    m_targets: int = 16
    mode: str = "userarch_hstu"   # baseline|userarch|userarch_hstu|hstu_ranking
    lce_n_out: int = 8
    lce_d_out: int = 64
    n_cross_layers: int = 3
    top_mlp: Tuple[int, ...] = (512, 256,)
    n_tasks: int = 2
    hstu: Optional[HSTUConfig] = None
    attn_backend: Optional[str] = None   # kernels/dispatch.py backend knob


def _hstu_cfg(cfg: LSRConfig) -> HSTUConfig:
    return cfg.hstu or HSTUConfig(d_model=cfg.embed_dim, n_heads=2,
                                  d_qk=32, d_v=32, n_layers=2,
                                  max_rel_pos=cfg.hist_len,
                                  attn_backend=cfg.attn_backend)


def lsr_init(rng: jax.Array, cfg: LSRConfig, dtype=jnp.float32) -> Dict:
    ks = jax.random.split(rng, 10)
    d = cfg.embed_dim
    # user features entering UserArch: dense proj + cat bag + hist summary
    n_user_feats = 3
    params = {
        "item_emb": (jax.random.normal(ks[0], (cfg.n_items, d)) * 0.02).astype(dtype),
        "user_cat_emb": (jax.random.normal(ks[1], (cfg.n_user_cats, d)) * 0.02).astype(dtype),
        "item_cat_emb": (jax.random.normal(ks[2], (cfg.n_item_cats, d)) * 0.02).astype(dtype),
        "dense_proj": mlp_init(ks[3], (cfg.n_ro_dense, d), dtype),
        "item_dense_proj": mlp_init(ks[4], (cfg.n_item_dense, d), dtype),
        "act_emb": (jax.random.normal(ks[5], (4, d)) * 0.02).astype(dtype),
    }
    if cfg.mode in ("userarch", "userarch_hstu", "hstu_ranking"):
        params["lce"] = lce_init(
            ks[6], LCEConfig(n_in=n_user_feats, d_in=d,
                             n_out=cfg.lce_n_out, d_out=cfg.lce_d_out), dtype)
        user_width = cfg.lce_n_out * cfg.lce_d_out
    else:
        user_width = n_user_feats * d
    if cfg.mode in ("userarch_hstu", "hstu_ranking"):
        params["hstu"] = hstu_init(ks[7], _hstu_cfg(cfg), dtype)
    if cfg.mode == "hstu_ranking":
        params["seq"] = roo_sequence_init(
            ks[8], ROOSequenceConfig(_hstu_cfg(cfg), cfg.hist_len,
                                     cfg.m_targets), dtype)
        item_width = 3 * d
    else:
        item_width = 2 * d
    inter_dim = user_width + item_width
    params["cross"] = dcnv2_init(ks[9], inter_dim, cfg.n_cross_layers, dtype=dtype)
    params["top_mlp"] = mlp_init(
        jax.random.fold_in(rng, 99),
        (inter_dim,) + cfg.top_mlp + (cfg.n_tasks,), dtype)
    return params


def _user_side(params: Dict, cfg: LSRConfig, batch: ROOBatch,
               cats_override: jnp.ndarray = None, plan=None) -> jnp.ndarray:
    """All RO computation -> (B_RO, user_width). Runs at B_RO under ROO.

    All embedding reads route through ``embeddings/collection.py``: dedup'd
    gathers locally, explicit psum lookups when an SPMD ``plan`` row-shards
    the table (each costs one B_RO-sized psum — the RO-side collective ROO
    shrinks, §2.2 Fig. 3), and ``GatheredTable`` proxies transparently under
    sparse-gradient training.
    """
    d = cfg.embed_dim
    dense = mlp_apply(params["dense_proj"], batch.ro_dense)          # (B_RO,d)
    if cats_override is not None:
        cats = cats_override
    elif batch.ro_sparse is not None:
        cats = ec.bag_lookup(params["user_cat_emb"],
                             batch.ro_sparse["user_ids"],
                             pooling="mean", plan=plan)
    else:
        cats = jnp.zeros_like(dense)
    if cfg.mode in ("userarch_hstu", "hstu_ranking"):
        hist_emb = ec.seq_lookup(params["item_emb"], batch.history_ids,
                                 vocab=cfg.n_items, plan=plan)
        act = ec.seq_lookup(params["act_emb"], batch.history_actions, vocab=4)
        spec = causal_spec(batch.history_lengths, cfg.hist_len)
        enc = hstu_apply(params["hstu"], _hstu_cfg(cfg), hist_emb + act, spec,
                         plan=plan)
        valid = (jnp.arange(cfg.hist_len)[None] < batch.history_lengths[:, None])
        hist = jnp.sum(enc * valid[..., None], 1) / jnp.maximum(
            batch.history_lengths, 1).astype(enc.dtype)[:, None]
    else:
        hist = ec.bag_lookup_dense(params["item_emb"], batch.history_ids,
                                   batch.history_lengths, pooling="mean",
                                   vocab=cfg.n_items, plan=plan)
    feats = jnp.stack([dense, cats, hist], axis=1)                   # (B_RO,3,d)
    if "lce" in params:
        out = lce_apply(params["lce"], jnp.transpose(feats, (0, 2, 1)))
        return out.reshape(out.shape[0], -1)                         # LCE flat
    return feats.reshape(feats.shape[0], -1)


def _item_side(params: Dict, cfg: LSRConfig, batch: ROOBatch,
               plan=None) -> jnp.ndarray:
    emb = ec.row_lookup(params["item_emb"], batch.item_ids,
                        vocab=cfg.n_items, plan=plan)
    dense = mlp_apply(params["item_dense_proj"], batch.nro_dense)
    return jnp.concatenate([emb, dense], axis=-1)                    # (B_NRO,2d)


def lsr_user_repr(params: Dict, cfg: LSRConfig, batch: ROOBatch,
                  plan=None) -> jnp.ndarray:
    """Request-only half of the LSR forward: (B_RO, user_width).

    Split out so serving can run it independently (once per unique request)
    and memoize the result across repeat candidates (serve/user_cache.py).
    """
    return _user_side(params, cfg, batch, plan=plan)


def lsr_logits_from_user(params: Dict, cfg: LSRConfig, batch: ROOBatch,
                         user: jnp.ndarray, plan=None) -> jnp.ndarray:
    """NRO half of the LSR forward, given a precomputed (B_RO, user_width)
    RO representation (from ``lsr_user_repr`` or a serving cache)."""
    user_at_nro = fanout(user, batch.segment_ids)
    item = _item_side(params, cfg, batch, plan=plan)
    if cfg.mode == "hstu_ranking":
        # ROO sequential targets: encode [history | m targets] once/request
        hist_emb = ec.seq_lookup(params["item_emb"], batch.history_ids,
                                 vocab=cfg.n_items, plan=plan)
        act = ec.seq_lookup(params["act_emb"], batch.history_actions, vocab=4)
        tgt_nro = ec.row_lookup(params["item_emb"], batch.item_ids,
                                vocab=cfg.n_items, plan=plan)
        tgt_ro = gather_targets_to_ro(tgt_nro, batch, cfg.m_targets)
        seq_cfg = ROOSequenceConfig(_hstu_cfg(cfg), cfg.hist_len, cfg.m_targets)
        enc = encode_roo(params["seq"], seq_cfg, hist_emb + act,
                         batch.history_lengths, tgt_ro, batch.num_impressions)
        seq_feat = scatter_targets_to_nro(enc, batch, cfg.m_targets)
        item = jnp.concatenate([item, seq_feat], axis=-1)
    x = jnp.concatenate([user_at_nro, item], axis=-1)
    x = dcnv2_apply(params["cross"], x)
    return mlp_apply(params["top_mlp"], x)


def lsr_logits_roo(params: Dict, cfg: LSRConfig, batch: ROOBatch,
                   plan=None) -> jnp.ndarray:
    """(B_NRO, n_tasks) multi-task logits, ROO path."""
    return lsr_logits_from_user(params, cfg, batch,
                                lsr_user_repr(params, cfg, batch, plan=plan),
                                plan=plan)


def lsr_logits_impression(params: Dict, cfg: LSRConfig, batch: ROOBatch) -> jnp.ndarray:
    """Impression-level baseline: RO features pre-expanded to B_NRO, user
    side computed B_NRO times (what ROO training eliminates)."""
    from repro.core.expansion import expand
    eb = expand(batch)
    fake = ROOBatch(
        ro_dense=eb.ro_dense, ro_sparse=None, history_ids=eb.history_ids,
        history_actions=eb.history_actions, history_lengths=eb.history_lengths,
        nro_dense=eb.nro_dense, nro_sparse=batch.nro_sparse,
        item_ids=eb.item_ids, labels=eb.labels,
        num_impressions=jnp.ones((batch.b_nro,), jnp.int32),
        segment_ids=jnp.arange(batch.b_nro, dtype=jnp.int32))
    # the jagged user-cat bag cannot be row-duplicated without re-packing;
    # expand its pooled result instead (identical math per impression)
    cats = ec.bag_lookup(params["user_cat_emb"], batch.ro_sparse["user_ids"],
                         pooling="mean") if batch.ro_sparse is not None else None
    cats_nro = fanout(cats, batch.segment_ids) if cats is not None else None
    user = _user_side(params, cfg, fake, cats_override=cats_nro)  # at B_NRO — the duplicated work
    item = _item_side(params, cfg, fake)
    if cfg.mode == "hstu_ranking":
        tgt = ec.row_lookup(params["item_emb"], fake.item_ids,
                            vocab=cfg.n_items)
        hist_emb = ec.seq_lookup(params["item_emb"], fake.history_ids,
                                 vocab=cfg.n_items)
        act = ec.seq_lookup(params["act_emb"], fake.history_actions, vocab=4)
        from repro.core.sequence import encode_per_impression
        seq_cfg = ROOSequenceConfig(_hstu_cfg(cfg), cfg.hist_len, cfg.m_targets)
        seq_feat = encode_per_impression(params["seq"], seq_cfg, hist_emb + act,
                                         fake.history_lengths, tgt)
        item = jnp.concatenate([item, seq_feat], axis=-1)
    x = jnp.concatenate([user, item], axis=-1)
    x = dcnv2_apply(params["cross"], x)
    return mlp_apply(params["top_mlp"], x)


def lsr_table_ids(cfg: LSRConfig, batch: ROOBatch) -> Dict[str, jnp.ndarray]:
    """Every id the ROO forward looks up, per embedding table — the
    declaration ``embeddings.sparse.make_sparse_value_and_grad`` gathers
    (and dedups) before differentiating w.r.t. the touched rows only."""
    ids = {
        "item_emb": jnp.concatenate([batch.history_ids.reshape(-1),
                                     batch.item_ids.reshape(-1)]),
        "act_emb": batch.history_actions.reshape(-1),
    }
    if batch.ro_sparse is not None:
        ids["user_cat_emb"] = batch.ro_sparse["user_ids"].values.reshape(-1)
    return ids


def lsr_loss(params: Dict, cfg: LSRConfig, batch: ROOBatch,
             roo: bool = True, plan=None) -> jnp.ndarray:
    logits = (lsr_logits_roo(params, cfg, batch, plan=plan) if roo
              else lsr_logits_impression(params, cfg, batch))
    y = batch.labels[:, :cfg.n_tasks]
    if y.shape[1] < cfg.n_tasks:
        y = jnp.pad(y, ((0, 0), (0, cfg.n_tasks - y.shape[1])))
    # task 1 (view_sec) binarized as consumption label
    y = jnp.stack([y[:, 0], (y[:, min(1, y.shape[1] - 1)] > 0).astype(y.dtype)], -1)
    w = batch.impression_mask().astype(logits.dtype)[:, None]
    bce = jnp.maximum(logits, 0) - logits * y + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    return jnp.sum(bce * w) / jnp.maximum(jnp.sum(w) * cfg.n_tasks, 1.0)
