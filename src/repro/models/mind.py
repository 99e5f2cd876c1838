"""MIND (Li et al. 2019, arXiv:1904.08030) — assigned recsys arch.

Config: embed_dim=64, n_interests=4, capsule_iters=3, multi-interest.

Behavior-to-Interest (B2I) dynamic routing extracts K interest capsules from
the user's behavior sequence; label-aware attention picks the capsule for a
target at training time.

ROO applicability: the capsule routing is 100 % RO — it runs once per
request and the K interest vectors fan out to the request's candidates
(this is the paper's retrieval regime, its biggest win: 570 %).
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from repro.core.fanout import fanout
from repro.core.roo_batch import ROOBatch
from repro.embeddings import collection as ec


@dataclasses.dataclass(frozen=True)
class MINDConfig:
    n_items: int
    embed_dim: int = 64
    n_interests: int = 4
    capsule_iters: int = 3
    hist_len: int = 64
    pow_p: float = 2.0       # label-aware attention sharpness


def mind_init(rng: jax.Array, cfg: MINDConfig, dtype=jnp.float32) -> Dict:
    k1, k2 = jax.random.split(rng)
    d = cfg.embed_dim
    return {
        "item_emb": (jax.random.normal(k1, (cfg.n_items, d)) * 0.02).astype(dtype),
        # shared bilinear routing map S (d, d) — B2I routing uses one shared map
        "S": (jax.random.normal(k2, (d, d)) / jnp.sqrt(d)).astype(dtype),
    }


def _squash(x: jnp.ndarray, axis=-1) -> jnp.ndarray:
    n2 = jnp.sum(x * x, axis=axis, keepdims=True)
    return (n2 / (1.0 + n2)) * x * jax.lax.rsqrt(n2 + 1e-9)


def interest_capsules(params: Dict, cfg: MINDConfig, hist_ids: jnp.ndarray,
                      lengths: jnp.ndarray) -> jnp.ndarray:
    """B2I dynamic routing. hist_ids: (B, T) -> capsules (B, K, d).

    Routing logits are NON-trainable (stop-gradient per the paper); the
    routing loop is unrolled (capsule_iters=3).
    """
    b, t = hist_ids.shape
    d, kk = cfg.embed_dim, cfg.n_interests
    e = ec.seq_lookup(params["item_emb"], hist_ids,
                      vocab=cfg.n_items)                     # (B,T,d)
    eh = e @ params["S"]                                     # low-level caps
    valid = (jnp.arange(t)[None] < lengths[:, None])
    # deterministic init of routing logits (hash of position) — paper uses
    # random init; a fixed pseudo-random pattern keeps steps reproducible.
    binit = jnp.sin(jnp.arange(t, dtype=jnp.float32)[:, None]
                    * (1.0 + jnp.arange(kk, dtype=jnp.float32))[None, :])
    blog = jnp.broadcast_to(binit[None], (b, t, kk))
    for _ in range(cfg.capsule_iters):
        w = jax.nn.softmax(jnp.where(valid[..., None], blog, -1e9), axis=-1)
        cand = jnp.einsum("btk,btd->bkd", w, eh)
        caps = _squash(cand)
        blog = blog + jnp.einsum("bkd,btd->btk",
                                 jax.lax.stop_gradient(caps), eh).transpose(0, 1, 2)
    return caps                                              # (B,K,d)


def score_candidates_roo(params: Dict, cfg: MINDConfig,
                         batch: ROOBatch) -> jnp.ndarray:
    """ROO path: capsules at B_RO; label-aware max over interests at B_NRO."""
    caps = interest_capsules(params, cfg, batch.history_ids[:, :cfg.hist_len],
                             jnp.minimum(batch.history_lengths, cfg.hist_len))
    caps_nro = fanout(caps, batch.segment_ids)               # (B_NRO,K,d)
    tgt = ec.row_lookup(params["item_emb"], batch.item_ids, vocab=cfg.n_items)
    scores = jnp.einsum("bkd,bd->bk", caps_nro, tgt)         # (B_NRO,K)
    return jnp.max(scores, axis=-1)                          # serving rule


def mind_loss(params: Dict, cfg: MINDConfig, batch: ROOBatch,
              temperature: float = 0.1) -> jnp.ndarray:
    """Sampled-softmax over in-batch items with label-aware attention."""
    caps = interest_capsules(params, cfg, batch.history_ids[:, :cfg.hist_len],
                             jnp.minimum(batch.history_lengths, cfg.hist_len))
    tgt = ec.row_lookup(params["item_emb"], batch.item_ids, vocab=cfg.n_items)
    caps_nro = fanout(caps, batch.segment_ids)               # (B_NRO,K,d)
    att = jax.nn.softmax(
        cfg.pow_p * jnp.einsum("bkd,bd->bk", caps_nro, tgt), axis=-1)
    u = jnp.einsum("bk,bkd->bd", att, caps_nro)              # label-aware user
    logits = (u @ tgt.T) / temperature                       # (B_NRO, B_NRO)
    valid = batch.impression_mask()
    logits = jnp.where(valid[None, :], logits, -1e9)
    logp = jax.nn.log_softmax(logits, axis=-1)
    pos_logp = jnp.diag(logp)
    w = ((batch.labels[:, 0] > 0.5) & valid).astype(logits.dtype)
    return -jnp.sum(pos_logp * w) / jnp.maximum(jnp.sum(w), 1.0)
