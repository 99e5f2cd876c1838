"""Generative Recommender (GR) on HSTU (paper §3.3; Zhai et al. 2024).

The ROO-enabled architecture: one autoregressive HSTU stack over the user's
interleaved (item, action) history, used two ways:

  * retrieval  — next-item prediction over the history (targets NOT in the
    sequence); sampled softmax against the item vocab.
  * ranking    — the request's m targets appended under the ROO mask
    (core.sequence), multi-task logits read from target positions.

This is the model the paper scales 7x under the same training compute; the
hstu_gr config instantiates it at production width.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.hstu import (HSTUConfig, hstu_apply, hstu_init,
                             hstu_prefix_apply)
from repro.core.masks import causal_spec, prefix_spec
from repro.core.roo_batch import ROOBatch
from repro.core.sequence import (ROOSequenceConfig, encode_roo,
                                 gather_targets_to_ro, scatter_targets_to_nro)
from repro.embeddings import collection as ec
from repro.models.mlp import mlp_apply, mlp_init


@dataclasses.dataclass(frozen=True)
class GRConfig:
    n_items: int
    hstu: HSTUConfig = None
    hist_len: int = 256
    m_targets: int = 16
    n_tasks: int = 2
    mode: str = "ranking"        # "ranking" | "retrieval"

    def seq_cfg(self) -> ROOSequenceConfig:
        return ROOSequenceConfig(self.hstu, self.hist_len, self.m_targets)


def gr_init(rng: jax.Array, cfg: GRConfig, dtype=jnp.float32) -> Dict:
    ks = jax.random.split(rng, 4)
    d = cfg.hstu.d_model
    return {
        "item_emb": (jax.random.normal(ks[0], (cfg.n_items, d)) * 0.02).astype(dtype),
        "act_emb": (jax.random.normal(ks[1], (4, d)) * 0.02).astype(dtype),
        "hstu": hstu_init(ks[2], cfg.hstu, dtype),
        "task_head": mlp_init(ks[3], (d, 2 * d, cfg.n_tasks), dtype),
    }


def _embed_history(params: Dict, cfg: GRConfig, batch: ROOBatch,
                   plan=None) -> jnp.ndarray:
    ids = batch.history_ids[:, :cfg.hist_len]
    acts = batch.history_actions[:, :cfg.hist_len]
    # item table is row-sharded under an SPMD plan: one B_RO-sized psum
    e = ec.seq_lookup(params["item_emb"], ids, vocab=cfg.n_items, plan=plan)
    a = ec.seq_lookup(params["act_emb"], acts, vocab=4)
    return e + a


def gr_history_repr(params: Dict, cfg: GRConfig, batch: ROOBatch,
                    plan=None) -> jnp.ndarray:
    """Request-only half of GR ranking: embedded (item+action) history,
    (B_RO, hist_len, d). The HSTU encode itself consumes the request's
    targets (ROO mask), so the embedding stage is the cacheable RO part."""
    return _embed_history(params, cfg, batch, plan=plan)


def gr_ranking_logits_from_history(params: Dict, cfg: GRConfig,
                                   batch: ROOBatch, hist: jnp.ndarray,
                                   plan=None) -> jnp.ndarray:
    """GR ranking logits given a precomputed history embedding
    (from ``gr_history_repr`` or a serving cache)."""
    lengths = jnp.minimum(batch.history_lengths, cfg.hist_len)
    tgt_nro = ec.row_lookup(params["item_emb"], batch.item_ids,
                            vocab=cfg.n_items, plan=plan)
    tgt_ro = gather_targets_to_ro(tgt_nro, batch, cfg.m_targets)
    enc = encode_roo({"hstu": params["hstu"]}, cfg.seq_cfg(), hist, lengths,
                     tgt_ro, batch.num_impressions,
                     plan=plan)                              # (B_RO, m, d)
    feats = scatter_targets_to_nro(enc, batch, cfg.m_targets)
    return mlp_apply(params["task_head"], feats)


def gr_ranking_logits(params: Dict, cfg: GRConfig, batch: ROOBatch,
                      plan=None) -> jnp.ndarray:
    """ROO ranking: encode [history | m targets] once per request;
    (B_NRO, n_tasks) logits."""
    return gr_ranking_logits_from_history(
        params, cfg, batch, gr_history_repr(params, cfg, batch, plan=plan),
        plan=plan)


class GRUserState(NamedTuple):
    """Per-user incremental serving state: the per-layer history K/V cache.

    Unbatched (as stored per user): k (n_layers, hist_len, H, dqk),
    v (n_layers, hist_len, H, dv), length () int32 — how many history events
    are resident. The serving store stacks these along a leading batch axis.
    """
    k: jnp.ndarray
    v: jnp.ndarray
    length: jnp.ndarray


def gr_state_init(cfg: GRConfig, dtype=jnp.float32) -> GRUserState:
    """Empty (zero-length) user state — extend-from-empty through the prefix
    path computes exactly the full-recompute forward."""
    h = cfg.hstu
    return GRUserState(
        k=jnp.zeros((h.n_layers, cfg.hist_len, h.n_heads, h.d_qk), dtype),
        v=jnp.zeros((h.n_layers, cfg.hist_len, h.n_heads, h.d_v), dtype),
        length=jnp.zeros((), jnp.int32))


def _gr_new_event_emb(params: Dict, cfg: GRConfig, batch: ROOBatch,
                      prefix: jnp.ndarray, n_new: int, plan=None):
    """Embed the n_new not-yet-cached history events of each request (row r
    of request b is history slot ``prefix[b] + r``). Returns
    (emb (B_RO, n_new, d), new_counts (B_RO,))."""
    n_hist = cfg.hist_len
    lengths = jnp.minimum(batch.history_lengths, n_hist).astype(jnp.int32)
    new_counts = jnp.maximum(lengths - prefix, 0)
    ridx = jnp.minimum(prefix[:, None] + jnp.arange(n_new)[None, :],
                       n_hist - 1)
    ids = jnp.take_along_axis(batch.history_ids[:, :n_hist], ridx, axis=1)
    acts = jnp.take_along_axis(batch.history_actions[:, :n_hist], ridx,
                               axis=1)
    e = ec.seq_lookup(params["item_emb"], ids, vocab=cfg.n_items, plan=plan)
    a = ec.seq_lookup(params["act_emb"], acts, vocab=4)
    return e + a, new_counts


def gr_score_from_state(params: Dict, cfg: GRConfig, batch: ROOBatch,
                        state: GRUserState, *, n_new: int,
                        plan=None):
    """Incremental GR ranking: score the request's targets by attending
    [new events | targets] against the per-user K/V cache.

    ``state`` is a batched :class:`GRUserState` (leading B_RO axis);
    ``n_new`` is the static new-event row budget (>= every request's
    uncached-event count; extra rows are masked). With zero-length state and
    ``n_new == cfg.hist_len`` this computes exactly
    :func:`gr_ranking_logits` — the unified fallback path. Returns
    ``(logits (B_NRO, n_tasks), new_state)``.
    """
    prefix = state.length.astype(jnp.int32)
    emb, new_counts = _gr_new_event_emb(params, cfg, batch, prefix, n_new,
                                        plan=plan)
    tgt_nro = ec.row_lookup(params["item_emb"], batch.item_ids,
                            vocab=cfg.n_items, plan=plan)
    tgt_ro = gather_targets_to_ro(tgt_nro, batch, cfg.m_targets)
    x = jnp.concatenate([emb, tgt_ro], axis=1)       # (B_RO, n_new + m, d)
    spec = prefix_spec(prefix, new_counts, batch.num_impressions,
                       cfg.hist_len, n_new)
    scale_len = cfg.hist_len + cfg.m_targets
    x, ks, vs = hstu_prefix_apply(params["hstu"], cfg.hstu, x,
                                  state.k, state.v, spec, scale_len)
    feats = scatter_targets_to_nro(x[:, n_new:, :], batch, cfg.m_targets)
    logits = mlp_apply(params["task_head"], feats)
    return logits, GRUserState(ks, vs, prefix + new_counts)


def gr_extend_user_state(params: Dict, cfg: GRConfig, batch: ROOBatch,
                         state: GRUserState, *, n_new: int,
                         plan=None) -> GRUserState:
    """Extend the per-user K/V cache with the request's new events without
    scoring any targets (prewarm / write-only traffic). The 1/n scale stays
    pinned to ``hist_len + m_targets``, so the resulting cache is bit-equal
    to the one :func:`gr_score_from_state` would have produced."""
    prefix = state.length.astype(jnp.int32)
    emb, new_counts = _gr_new_event_emb(params, cfg, batch, prefix, n_new,
                                        plan=plan)
    spec = prefix_spec(prefix, new_counts,
                       jnp.zeros_like(new_counts), cfg.hist_len, n_new)
    scale_len = cfg.hist_len + cfg.m_targets
    _, ks, vs = hstu_prefix_apply(params["hstu"], cfg.hstu, emb,
                                  state.k, state.v, spec, scale_len)
    return GRUserState(ks, vs, prefix + new_counts)


def gr_ranking_loss(params: Dict, cfg: GRConfig, batch: ROOBatch,
                    plan=None) -> jnp.ndarray:
    logits = gr_ranking_logits(params, cfg, batch, plan=plan)
    y = jnp.stack([batch.labels[:, 0],
                   (batch.labels[:, min(1, batch.labels.shape[1] - 1)] > 0
                    ).astype(logits.dtype)], -1)[:, :cfg.n_tasks]
    w = batch.impression_mask().astype(logits.dtype)[:, None]
    bce = jnp.maximum(logits, 0) - logits * y + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    return jnp.sum(bce * w) / jnp.maximum(jnp.sum(w) * cfg.n_tasks, 1.0)


def gr_retrieval_loss(params: Dict, cfg: GRConfig, batch: ROOBatch,
                      temperature: float = 0.05, plan=None) -> jnp.ndarray:
    """Autoregressive next-item prediction over the history (RO-only) plus
    in-batch candidate softmax — the GR retrieval objective."""
    hist = _embed_history(params, cfg, batch, plan=plan)
    lengths = jnp.minimum(batch.history_lengths, cfg.hist_len)
    spec = causal_spec(lengths, cfg.hist_len)
    enc = hstu_apply(params["hstu"], cfg.hstu, hist, spec,
                     plan=plan)                              # (B_RO, n, d)
    # position t predicts item t+1
    q = enc[:, :-1, :]
    nxt = batch.history_ids[:, 1:cfg.hist_len]
    valid = (jnp.arange(cfg.hist_len - 1)[None] < (lengths - 1)[:, None])
    # sampled softmax against the in-batch item candidates
    cand = ec.row_lookup(params["item_emb"], batch.item_ids,
                         vocab=cfg.n_items, plan=plan)
    logits = jnp.einsum("bnd,cd->bnc", q, cand) / temperature
    tgt_emb = ec.seq_lookup(params["item_emb"], nxt, vocab=cfg.n_items,
                            plan=plan)
    pos = jnp.sum(q * tgt_emb, axis=-1) / temperature        # (B_RO, n-1)
    lse = jnp.logaddexp(jax.scipy.special.logsumexp(logits, axis=-1), pos)
    nll = lse - pos
    w = valid.astype(nll.dtype)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
