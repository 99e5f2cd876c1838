"""BERT4Rec (Sun et al. 2019, arXiv:1904.06690) — assigned recsys arch.

Config: embed_dim=64, n_blocks=2, n_heads=2, seq_len=200; bidirectional
self-attention over the user's item sequence, trained with the cloze
(masked-item) objective.

ROO applicability: the encoder consumes only the user history (RO). Under
ROO it runs once per request; the m candidates are scored against the
encoded representation at the mask position. Encoder-only: no decode shapes.

Embedding path: lookups route through embeddings/collection.py (dedup'd
gathers), but the cloze head's full softmax (``enc @ item_emb.T``) reads
every table row, so BERT4Rec trains with dense embedding gradients (the
train step's recording pass sees the matmul and keeps the table dense).
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from repro.core.roo_batch import ROOBatch
from repro.core.fanout import fanout
from repro.embeddings import collection as ec
from repro.models.mlp import mlp_apply, mlp_init

MASK_TOKEN = 1   # reserved id


@dataclasses.dataclass(frozen=True)
class BERT4RecConfig:
    n_items: int
    embed_dim: int = 64
    n_blocks: int = 2
    n_heads: int = 2
    seq_len: int = 200
    d_ff: int = 256
    mask_prob: float = 0.2


def _ln(x, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    v = jnp.var(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(v + eps)


def bert4rec_init(rng: jax.Array, cfg: BERT4RecConfig, dtype=jnp.float32) -> Dict:
    ks = jax.random.split(rng, 2 + cfg.n_blocks)
    d = cfg.embed_dim
    blocks = []
    for k in ks[2:]:
        k1, k2, k3, k4 = jax.random.split(k, 4)
        s = 1.0 / jnp.sqrt(d)
        blocks.append({
            "wqkv": (jax.random.normal(k1, (d, 3 * d)) * s).astype(dtype),
            "wo": (jax.random.normal(k2, (d, d)) * s).astype(dtype),
            "ff1": mlp_init(k3, (d, cfg.d_ff), dtype),
            "ff2": mlp_init(k4, (cfg.d_ff, d), dtype),
        })
    return {
        "item_emb": (jax.random.normal(ks[0], (cfg.n_items, d)) * 0.02).astype(dtype),
        "pos_emb": (jax.random.normal(ks[1], (cfg.seq_len, d)) * 0.02).astype(dtype),
        "blocks": blocks,
        "out_bias": jnp.zeros((cfg.n_items,), dtype),
    }


def encode(params: Dict, cfg: BERT4RecConfig, ids: jnp.ndarray,
           lengths: jnp.ndarray) -> jnp.ndarray:
    """ids: (B, S) -> (B, S, d) bidirectional encoding (valid-masked)."""
    b, s = ids.shape
    d, h = cfg.embed_dim, cfg.n_heads
    x = ec.seq_lookup(params["item_emb"], ids, vocab=cfg.n_items)
    x = x + params["pos_emb"][None, :s]
    valid = (jnp.arange(s)[None] < lengths[:, None])
    attn_mask = valid[:, None, None, :]                     # keys must be valid
    for blk in params["blocks"]:
        xn = _ln(x)
        qkv = xn @ blk["wqkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, h, d // h).transpose(0, 2, 1, 3)
        k = k.reshape(b, s, h, d // h).transpose(0, 2, 1, 3)
        v = v.reshape(b, s, h, d // h).transpose(0, 2, 1, 3)
        scores = jnp.einsum("bhid,bhjd->bhij", q, k) / jnp.sqrt(d / h)
        scores = jnp.where(attn_mask, scores, -1e9)
        a = jax.nn.softmax(scores, axis=-1)
        av = jnp.einsum("bhij,bhjd->bhid", a, v)
        av = av.transpose(0, 2, 1, 3).reshape(b, s, d)
        x = x + av @ blk["wo"]
        xn = _ln(x)
        x = x + mlp_apply(blk["ff2"], jax.nn.gelu(mlp_apply(blk["ff1"], xn)))
    return _ln(x) * valid[..., None]


def cloze_loss(params: Dict, cfg: BERT4RecConfig, ids: jnp.ndarray,
               lengths: jnp.ndarray, rng: jax.Array,
               n_negatives: int = 128) -> jnp.ndarray:
    """Masked-item prediction with sampled softmax (full softmax if vocab
    small). ids: (B, S)."""
    b, s = ids.shape
    mask = (jax.random.uniform(rng, (b, s)) < cfg.mask_prob) & \
           (jnp.arange(s)[None] < lengths[:, None])
    masked_ids = jnp.where(mask, MASK_TOKEN, ids)
    enc = encode(params, cfg, masked_ids, lengths)          # (B,S,d)
    logits = enc @ params["item_emb"].T + params["out_bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    tgt = jnp.clip(ids, 0, cfg.n_items - 1)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    w = mask.astype(nll.dtype)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def score_candidates_roo(params: Dict, cfg: BERT4RecConfig,
                         batch: ROOBatch) -> jnp.ndarray:
    """ROO scoring: encode history ONCE per request with a MASK appended;
    score the request's m candidates against the mask-position output."""
    b = batch.b_ro
    s = cfg.seq_len
    ids = batch.history_ids[:, : s - 1]
    lengths = jnp.minimum(batch.history_lengths, s - 1)
    # append MASK at position `lengths`
    ids_ext = jnp.pad(ids, ((0, 0), (0, 1)))
    ids_ext = jnp.asarray(ids_ext).at[jnp.arange(b), lengths].set(MASK_TOKEN)
    enc = encode(params, cfg, ids_ext, lengths + 1)          # (B_RO, S, d)
    q = enc[jnp.arange(b), lengths]                          # (B_RO, d) @ MASK
    q_nro = fanout(q, batch.segment_ids)                     # (B_NRO, d)
    cand = ec.row_lookup(params["item_emb"], batch.item_ids,
                         vocab=cfg.n_items)
    return jnp.sum(q_nro * cand, axis=-1) + jnp.take(
        params["out_bias"], jnp.clip(batch.item_ids, 0, cfg.n_items - 1))


def bert4rec_loss(params: Dict, cfg: BERT4RecConfig, batch: ROOBatch,
                  rng: jax.Array) -> jnp.ndarray:
    """Training = cloze over histories (RO-only!) + candidate BCE head."""
    cl = cloze_loss(params, cfg, batch.history_ids[:, :cfg.seq_len],
                    jnp.minimum(batch.history_lengths, cfg.seq_len), rng)
    logits = score_candidates_roo(params, cfg, batch)
    y = batch.labels[:, 0]
    w = batch.impression_mask().astype(logits.dtype)
    bce = jnp.maximum(logits, 0) - logits * y + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    return cl + jnp.sum(bce * w) / jnp.maximum(jnp.sum(w), 1.0)
