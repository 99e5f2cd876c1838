"""DIEN (Zhou et al. 2019, arXiv:1809.03672) — assigned recsys arch.

Config: embed_dim=18, seq_len=100, gru_dim=108, MLP 200-80, AUGRU.

Structure: item+category embeddings -> interest-extraction GRU over the
behavior sequence -> target-conditioned attention -> AUGRU (attention-update
-gate GRU) -> final interest state -> MLP over [interest, target, user].

ROO applicability: the extraction GRU depends only on the user history (RO)
and runs once per request; its hidden states fan out to the request's
impressions. The AUGRU stage is target-conditioned so it runs at B_NRO —
the partial-dedup regime the paper files under LSR-like gains.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.fanout import fanout
from repro.core.roo_batch import ROOBatch
from repro.embeddings import collection as ec
from repro.models.mlp import mlp_apply, mlp_init


@dataclasses.dataclass(frozen=True)
class DIENConfig:
    n_items: int
    embed_dim: int = 18
    seq_len: int = 100
    gru_dim: int = 108
    mlp: Tuple[int, ...] = (200, 80)
    n_ro_dense: int = 16


def _gru_init(rng, d_in, d_h, dtype, extra_gates: int = 0):
    k1, k2 = jax.random.split(rng)
    g = 3
    return {
        "wx": (jax.random.normal(k1, (d_in, g * d_h)) / jnp.sqrt(d_in)).astype(dtype),
        "wh": (jax.random.normal(k2, (d_h, g * d_h)) / jnp.sqrt(d_h)).astype(dtype),
        "b": jnp.zeros((g * d_h,), dtype),
    }


def dien_init(rng: jax.Array, cfg: DIENConfig, dtype=jnp.float32) -> Dict:
    ks = jax.random.split(rng, 6)
    d, h = cfg.embed_dim, cfg.gru_dim
    return {
        "item_emb": (jax.random.normal(ks[0], (cfg.n_items, d)) * 0.02).astype(dtype),
        "gru": _gru_init(ks[1], d, h, dtype),
        "augru": _gru_init(ks[2], h, h, dtype),   # AUGRU consumes GRU states
        "att_mlp": mlp_init(ks[3], (2 * h + d, 64, 1), dtype),
        "out_mlp": mlp_init(ks[4], (h + d + cfg.n_ro_dense,) + cfg.mlp + (1,), dtype),
        "h_proj": mlp_init(ks[5], (d, h), dtype),   # project emb for att space
    }


def gru_scan(p: Dict, xs: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """xs: (B, T, d_in) -> hidden states (B, T, d_h). Masked past lengths."""
    b, t, _ = xs.shape
    d_h = p["wh"].shape[0]

    def step(h, inp):
        x, valid = inp
        gx = x @ p["wx"] + p["b"]
        gh = h @ p["wh"]
        xz, xr, xn = jnp.split(gx, 3, axis=-1)
        hz, hr, hn = jnp.split(gh, 3, axis=-1)
        z = jax.nn.sigmoid(xz + hz)
        r = jax.nn.sigmoid(xr + hr)
        n = jnp.tanh(xn + r * hn)
        h_new = (1 - z) * n + z * h
        h_new = jnp.where(valid[:, None], h_new, h)
        return h_new, h_new

    valid = (jnp.arange(t)[None] < lengths[:, None])
    h0 = jnp.zeros((b, d_h), xs.dtype)
    _, hs = jax.lax.scan(step, h0, (xs.transpose(1, 0, 2), valid.T))
    return hs.transpose(1, 0, 2)


def augru_scan(p: Dict, xs: jnp.ndarray, att: jnp.ndarray,
               lengths: jnp.ndarray) -> jnp.ndarray:
    """AUGRU: update gate scaled by attention score. Returns final state."""
    b, t, _ = xs.shape
    d_h = p["wh"].shape[0]

    def step(h, inp):
        x, a, valid = inp
        gx = x @ p["wx"] + p["b"]
        gh = h @ p["wh"]
        xz, xr, xn = jnp.split(gx, 3, axis=-1)
        hz, hr, hn = jnp.split(gh, 3, axis=-1)
        z = jax.nn.sigmoid(xz + hz) * a[:, None]        # attention-scaled gate
        r = jax.nn.sigmoid(xr + hr)
        n = jnp.tanh(xn + r * hn)
        h_new = (1 - z) * h + z * n
        h_new = jnp.where(valid[:, None], h_new, h)
        return h_new, None

    valid = (jnp.arange(t)[None] < lengths[:, None])
    h0 = jnp.zeros((b, d_h), xs.dtype)
    h_final, _ = jax.lax.scan(
        step, h0, (xs.transpose(1, 0, 2), att.T, valid.T))
    return h_final


def dien_logits_roo(params: Dict, cfg: DIENConfig, batch: ROOBatch) -> jnp.ndarray:
    """ROO path: extraction GRU at B_RO; AUGRU at B_NRO after fanout."""
    t = cfg.seq_len
    hist_ids = batch.history_ids[:, :t]
    lengths = jnp.minimum(batch.history_lengths, t)
    hist = ec.seq_lookup(params["item_emb"], hist_ids, vocab=cfg.n_items)
    # ---- RO: interest extraction runs once per request ----------------------
    states = gru_scan(params["gru"], hist, lengths)           # (B_RO, T, h)
    # ---- fanout hidden states + history embeddings once ---------------------
    states_nro = fanout(states, batch.segment_ids)            # (B_NRO, T, h)
    hist_nro = fanout(hist, batch.segment_ids)
    len_nro = fanout(lengths, batch.segment_ids)
    # ---- NRO: target attention + AUGRU --------------------------------------
    tgt = ec.row_lookup(params["item_emb"], batch.item_ids, vocab=cfg.n_items)
    tgt_h = mlp_apply(params["h_proj"], tgt)                  # (B_NRO, h)
    att_in = jnp.concatenate([
        states_nro, jnp.broadcast_to(tgt_h[:, None, :], states_nro.shape),
        jnp.broadcast_to(tgt[:, None, :], states_nro.shape[:2] + (cfg.embed_dim,))],
        axis=-1)
    scores = mlp_apply(params["att_mlp"], att_in)[..., 0]     # (B_NRO, T)
    valid = (jnp.arange(t)[None] < len_nro[:, None])
    scores = jnp.where(valid, scores, -1e9)
    att = jax.nn.softmax(scores, axis=-1)
    h_final = augru_scan(params["augru"], states_nro, att, len_nro)
    ro_dense_nro = fanout(batch.ro_dense, batch.segment_ids)
    x = jnp.concatenate([h_final, tgt, ro_dense_nro], axis=-1)
    return mlp_apply(params["out_mlp"], x)[:, 0]


def dien_loss(params: Dict, cfg: DIENConfig, batch: ROOBatch) -> jnp.ndarray:
    logits = dien_logits_roo(params, cfg, batch)
    y = batch.labels[:, 0]
    w = batch.impression_mask().astype(logits.dtype)
    bce = jnp.maximum(logits, 0) - logits * y + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    return jnp.sum(bce * w) / jnp.maximum(jnp.sum(w), 1.0)
