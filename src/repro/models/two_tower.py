"""Two-tower retrieval + early-stage ranking (ESR) models (paper §3.1, Fig 4).

The user tower consumes only RO features, so under ROO it runs at B_RO and
its output is fanned out once per request. The item tower runs at B_NRO.
Retrieval trains with in-batch sampled softmax (logQ-corrected); ESR adds a
lightweight user-item interaction head (BCE).

``user_tower_mode``: "mlp" (baseline), "hstu" (paper's scaled-up tower —
history encoded by an HSTU stack; the 6.8x-FLOPs-per-example model of
Table 6 that ROO brings back to ~1x).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.fanout import fanout
from repro.core.hstu import HSTUConfig, hstu_apply, hstu_init
from repro.core.masks import causal_spec
from repro.core.roo_batch import ROOBatch
from repro.embeddings import collection as ec
from repro.models.mlp import mlp_apply, mlp_init


@dataclasses.dataclass(frozen=True)
class TwoTowerConfig:
    n_items: int
    n_user_cats: int = 200
    embed_dim: int = 64
    n_ro_dense: int = 16
    n_item_dense: int = 8
    hist_len: int = 64
    user_mlp: Tuple[int, ...] = (256, 128, 64)
    item_mlp: Tuple[int, ...] = (128, 64)
    user_tower_mode: str = "mlp"          # "mlp" | "hstu"
    hstu: Optional[HSTUConfig] = None
    esr_head: bool = False                 # adds interaction MLP head (ESR)
    esr_mlp: Tuple[int, ...] = (128, 64, 1)


def two_tower_init(rng: jax.Array, cfg: TwoTowerConfig, dtype=jnp.float32) -> Dict:
    ks = jax.random.split(rng, 8)
    d = cfg.embed_dim
    params = {
        "item_emb": (jax.random.normal(ks[0], (cfg.n_items, d)) * 0.02).astype(dtype),
        "user_cat_emb": (jax.random.normal(ks[1], (cfg.n_user_cats, d)) * 0.02).astype(dtype),
        "user_mlp": mlp_init(ks[2], (cfg.n_ro_dense + 2 * d,) + cfg.user_mlp, dtype),
        "item_mlp": mlp_init(ks[3], (cfg.n_item_dense + d,) + cfg.item_mlp, dtype),
    }
    if cfg.user_tower_mode == "hstu":
        assert cfg.hstu is not None
        params["hstu"] = hstu_init(ks[4], cfg.hstu, dtype)
        params["act_emb"] = (jax.random.normal(ks[5], (4, d)) * 0.02).astype(dtype)
    if cfg.esr_head:
        params["esr_mlp"] = mlp_init(
            ks[6], (cfg.user_mlp[-1] + cfg.item_mlp[-1] + 1,) + cfg.esr_mlp, dtype)
    return params


def user_tower(params: Dict, cfg: TwoTowerConfig, batch: ROOBatch) -> jnp.ndarray:
    """RO-only computation -> (B_RO, d_user)."""
    d = cfg.embed_dim
    if cfg.user_tower_mode == "hstu":
        hist_emb = ec.seq_lookup(params["item_emb"], batch.history_ids,
                                 vocab=cfg.n_items)
        act_emb = ec.seq_lookup(params["act_emb"], batch.history_actions,
                                vocab=4)
        seq = hist_emb + act_emb
        spec = causal_spec(batch.history_lengths, cfg.hist_len)
        enc = hstu_apply(params["hstu"], cfg.hstu, seq, spec)
        # mean-pool valid positions as the user interest summary
        valid = (jnp.arange(cfg.hist_len)[None] < batch.history_lengths[:, None])
        pooled = jnp.sum(enc * valid[..., None], 1) / jnp.maximum(
            batch.history_lengths, 1).astype(enc.dtype)[:, None]
    else:
        pooled = ec.bag_lookup_dense(params["item_emb"], batch.history_ids,
                                     batch.history_lengths, pooling="mean",
                                     vocab=cfg.n_items)
    cats = ec.bag_lookup(params["user_cat_emb"], batch.ro_sparse["user_ids"],
                         pooling="mean") if batch.ro_sparse is not None else \
        jnp.zeros((batch.b_ro, d))
    x = jnp.concatenate([batch.ro_dense, pooled, cats], axis=-1)
    u = mlp_apply(params["user_mlp"], x)
    return u / (jnp.linalg.norm(u, axis=-1, keepdims=True) + 1e-6)


def item_tower(params: Dict, cfg: TwoTowerConfig, item_ids: jnp.ndarray,
               item_dense: jnp.ndarray) -> jnp.ndarray:
    emb = ec.row_lookup(params["item_emb"], item_ids, vocab=cfg.n_items)
    x = jnp.concatenate([item_dense, emb], axis=-1)
    v = mlp_apply(params["item_mlp"], x)
    return v / (jnp.linalg.norm(v, axis=-1, keepdims=True) + 1e-6)


def retrieval_loss_roo(params: Dict, cfg: TwoTowerConfig, batch: ROOBatch,
                       temperature: float = 0.05) -> jnp.ndarray:
    """In-batch softmax over all B_NRO items; positives = clicked impressions.

    User tower at B_RO (ROO dedup); logits via one (B_RO, B_NRO) matmul.
    """
    u = user_tower(params, cfg, batch)                       # (B_RO, d)
    v = item_tower(params, cfg, batch.item_ids, batch.nro_dense)  # (B_NRO, d)
    logits = (u @ v.T) / temperature                          # (B_RO, B_NRO)
    imp_valid = batch.impression_mask()
    logits = jnp.where(imp_valid[None, :], logits, -1e9)
    pos = batch.labels[:, 0] > 0.5                            # clicked
    seg = jnp.minimum(batch.segment_ids, batch.b_ro - 1)
    logp = jax.nn.log_softmax(logits, axis=-1)                # (B_RO, B_NRO)
    nro_idx = jnp.arange(batch.b_nro)
    pos_logp = logp[seg, nro_idx]                             # (B_NRO,)
    w = (pos & imp_valid).astype(logits.dtype)
    return -jnp.sum(pos_logp * w) / jnp.maximum(jnp.sum(w), 1.0)


def esr_logits_from_user(params: Dict, cfg: TwoTowerConfig, batch: ROOBatch,
                         u: jnp.ndarray) -> jnp.ndarray:
    """ESR NRO half, given a precomputed (B_RO, d) user representation
    (from ``user_tower`` or a serving cache)."""
    u_at_nro = fanout(u, batch.segment_ids)
    v = item_tower(params, cfg, batch.item_ids, batch.nro_dense)
    dot = jnp.sum(u_at_nro * v, axis=-1, keepdims=True)
    x = jnp.concatenate([u_at_nro, v, dot], axis=-1)
    return mlp_apply(params["esr_mlp"], x)[:, 0]


def esr_logits_roo(params: Dict, cfg: TwoTowerConfig, batch: ROOBatch) -> jnp.ndarray:
    """ESR: fanned-out user repr + item repr -> interaction MLP -> logit."""
    return esr_logits_from_user(params, cfg, batch,
                                user_tower(params, cfg, batch))


def esr_loss_roo(params: Dict, cfg: TwoTowerConfig, batch: ROOBatch) -> jnp.ndarray:
    logits = esr_logits_roo(params, cfg, batch)
    y = batch.labels[:, 0]
    w = batch.impression_mask().astype(logits.dtype)
    bce = jnp.maximum(logits, 0) - logits * y + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    return jnp.sum(bce * w) / jnp.maximum(jnp.sum(w), 1.0)
