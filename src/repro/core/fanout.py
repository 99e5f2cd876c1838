"""Fanout — the single RO->NRO broadcast at the heart of ROO training (§2.2).

In impression-level training every user-side activation exists ``B_NRO``
times. Under ROO the user side is computed once per request (``B_RO`` rows)
and *fanned out* to its impressions exactly once, at the interaction point.
The fanout is a gather by ``segment_ids``; its transpose (used by autodiff
and by request-level pooling) is a segment-sum.

Under the production mesh both ``B_RO`` and ``B_NRO`` leading dims are
sharded over (pod, data) and the batcher guarantees request locality, so the
gather never crosses shards; ``fanout_local`` makes that explicit via
shard_map for the optimized path, while plain ``fanout`` relies on GSPMD.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def fanout(x_ro: jnp.ndarray, segment_ids: jnp.ndarray) -> jnp.ndarray:
    """Broadcast request-level rows to impression slots.

    Args:
      x_ro: (B_RO, ...) request-level activations.
      segment_ids: (B_NRO,) int32 in [0, B_RO]; B_RO marks padding.

    Returns:
      (B_NRO, ...) with padding slots zeroed.
    """
    b_ro = x_ro.shape[0]
    safe = jnp.minimum(segment_ids, b_ro - 1)
    out = jnp.take(x_ro, safe, axis=0)
    valid = (segment_ids < b_ro)
    return out * valid.reshape((-1,) + (1,) * (out.ndim - 1)).astype(out.dtype)


def fanin_sum(x_nro: jnp.ndarray, segment_ids: jnp.ndarray,
              b_ro: int) -> jnp.ndarray:
    """Transpose of fanout: sum impression rows back to their request."""
    return jax.ops.segment_sum(x_nro, segment_ids, num_segments=b_ro + 1)[:b_ro]


def fanin_mean(x_nro: jnp.ndarray, segment_ids: jnp.ndarray,
               b_ro: int) -> jnp.ndarray:
    s = fanin_sum(x_nro, segment_ids, b_ro)
    ones = jnp.ones((x_nro.shape[0],), x_nro.dtype)
    n = fanin_sum(ones, segment_ids, b_ro)
    return s / jnp.maximum(n, 1.0).reshape((-1,) + (1,) * (s.ndim - 1))


def fanout_local(x_ro: jnp.ndarray, segment_ids: jnp.ndarray, mesh,
                 batch_axes=("data",)) -> jnp.ndarray:
    """Shard-local fanout: per-shard gather with *local* segment ids.

    Requires the batcher's request-locality guarantee: impressions of request
    r live on the shard owning row r, and ``segment_ids`` are already local
    (i.e. in [0, B_RO/n_shards] per shard, padding == local b_ro).
    Avoids the all-gather of ``x_ro`` that GSPMD inserts for a global gather.
    """
    n_feat_axes = x_ro.ndim - 1
    in_specs = (P(batch_axes), P(batch_axes))
    out_specs = P(batch_axes)

    def _shard_fn(x, seg):
        b_local = x.shape[0]
        safe = jnp.minimum(seg, b_local - 1)
        out = jnp.take(x, safe, axis=0)
        valid = (seg < b_local)
        return out * valid.reshape((-1,) + (1,) * n_feat_axes).astype(out.dtype)

    return jax.shard_map(_shard_fn, mesh=mesh,
                         in_specs=in_specs,
                         out_specs=out_specs)(x_ro, segment_ids)
