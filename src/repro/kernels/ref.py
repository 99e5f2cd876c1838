"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernel tests assert_allclose against, and the
implementations the models use on CPU (the ``jnp`` backends of
kernels/dispatch.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def hstu_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       rab: jnp.ndarray | None,
                       n_hist: int,
                       hist_lengths: jnp.ndarray,
                       target_counts: jnp.ndarray,
                       max_rel_pos: int = 128) -> jnp.ndarray:
    """HSTU pointwise attention with the ROO mask.

    q, k: (B, H, S, Dqk); v: (B, H, S, Dv); rab: (H, 2*max_rel_pos+1) learned
    relative-position bias table or None. S = n_hist + m_targets.
    Mask: history causal; targets attend history + self only; valid lengths.
    Returns (B, H, S, Dv).
    """
    b, h, s, dqk = q.shape
    m_targets = s - n_hist
    scores = jnp.einsum("bhid,bhjd->bhij", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(dqk, jnp.float32))
    if rab is not None:
        pos = jnp.arange(s)
        delta = jnp.clip(pos[:, None] - pos[None, :],
                         -max_rel_pos, max_rel_pos) + max_rel_pos
        scores = scores + rab[:, delta][None].astype(scores.dtype)
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    is_hq, is_hk = i < n_hist, j < n_hist
    struct = (is_hq & is_hk & (j <= i)) | (~is_hq & is_hk) | \
             (~is_hq & ~is_hk & (i == j))
    pos = jnp.arange(s)
    valid = jnp.where(pos[None, :] < n_hist,
                      pos[None, :] < hist_lengths[:, None],
                      (pos[None, :] - n_hist) < target_counts[:, None])
    mask = struct[None] & valid[:, None, :] & valid[:, :, None]   # (B,S,S)
    a = jax.nn.silu(scores) / jnp.asarray(s, jnp.float32)
    a = a * mask[:, None].astype(a.dtype)
    return jnp.einsum("bhij,bhjd->bhid", a.astype(v.dtype), v)


def hstu_attention_prefix_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                              rab: jnp.ndarray | None,
                              n_hist: int, n_new: int,
                              prefix_lengths: jnp.ndarray,
                              new_counts: jnp.ndarray,
                              target_counts: jnp.ndarray,
                              scale_len: int,
                              max_rel_pos: int = 128) -> jnp.ndarray:
    """Cached-prefix HSTU attention (dense oracle).

    Rows are [new events | targets]: q: (B, H, n_new + m, Dqk). Columns are
    the full K/V buffer [history cache | targets]: k: (B, H, n_hist + m, Dqk),
    v: (B, H, n_hist + m, Dv). New event r sits at absolute history position
    ``prefix_lengths[b] + r``; ``scale_len`` is the 1/n normalizer of the
    equivalent full sequence (n_hist + m_targets), pinned by the caller so
    extend-only and extend-and-score calls normalize identically.
    Returns (B, H, n_new + m, Dv).
    """
    from repro.core.masks import PrefixMaskSpec

    b, h, n_rows, dqk = q.shape
    n_cols = k.shape[2]
    scores = jnp.einsum("bhid,bhjd->bhij", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(dqk, jnp.float32))
    if rab is not None:
        r = jnp.arange(n_rows)
        j = jnp.arange(n_cols)
        row_pos = jnp.where((r < n_new)[None, :],
                            prefix_lengths[:, None] + r[None, :],
                            r[None, :] + (n_hist - n_new))           # (B, R)
        delta = jnp.clip(row_pos[:, :, None] - j[None, None, :],
                         -max_rel_pos, max_rel_pos) + max_rel_pos    # (B, R, C)
        bias = jnp.moveaxis(jnp.take(rab, delta, axis=1), 0, 1)      # (B, H, R, C)
        scores = scores + bias.astype(scores.dtype)
    spec = PrefixMaskSpec(n_hist, n_new, prefix_lengths, new_counts,
                          target_counts)
    mask = spec.dense(n_rows, n_cols)                                # (B, R, C)
    a = jax.nn.silu(scores) / jnp.asarray(scale_len, jnp.float32)
    a = a * mask[:, None].astype(a.dtype)
    return jnp.einsum("bhij,bhjd->bhid", a.astype(v.dtype), v)


def embedding_bag_ref(table: jnp.ndarray, ids: jnp.ndarray,
                      lengths: jnp.ndarray,
                      pooling: str = "sum") -> jnp.ndarray:
    """Pooled embedding bag (sum | mean | max). table: (V, D); ids: (B, L);
    lengths: (B,). Matches embeddings/bag.bag_lookup_dense semantics:
    slots past ``lengths`` never contribute and empty bags give zeros."""
    b, l = ids.shape
    valid = jnp.arange(l)[None, :] < lengths[:, None]
    emb = jnp.take(table, jnp.clip(ids, 0, table.shape[0] - 1).reshape(-1),
                   axis=0).reshape(b, l, -1)
    if pooling == "max":
        neg = jnp.full_like(emb, jnp.finfo(emb.dtype).min)
        emb = jnp.where(valid[..., None], emb, neg)
        out = jnp.max(emb, axis=1)
        return jnp.where((lengths > 0)[:, None], out, 0.0)
    out = jnp.sum(emb * valid[..., None].astype(emb.dtype), axis=1)
    if pooling == "mean":
        out = out / jnp.maximum(lengths, 1).astype(out.dtype)[:, None]
    return out


def dot_interaction_ref(dense_out: jnp.ndarray,
                        sparse_embs: jnp.ndarray) -> jnp.ndarray:
    """DLRM dot interaction. dense_out: (B, D); sparse_embs: (B, F, D).
    Returns (B, D + (F+1)F/2) — dense concat strict-lower-tri pairwise dots."""
    t = jnp.concatenate([dense_out[:, None, :], sparse_embs], axis=1)
    z = jnp.einsum("bfd,bgd->bfg", t, t, preferred_element_type=jnp.float32)
    f = t.shape[1]
    i, j = jnp.tril_indices(f, k=-1)
    return jnp.concatenate([dense_out, z[:, i, j].astype(dense_out.dtype)],
                           axis=1)
