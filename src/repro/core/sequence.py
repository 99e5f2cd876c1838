"""ROO sequential modeling (paper §3.3).

Builds, per request, the sequence ``[history (n) | targets (m)]``, encodes it
ONCE with HSTU under the ROO mask (targets see history + self only), and
scatters the m target outputs back to their NRO impression slots.

The impression-level counterpart (``encode_per_impression``) encodes
(history + 1 target) once *per impression* — the baseline whose cost is
m·(n²d + nd²); equivalence between the two is property-tested, which is what
licenses the (n+m)²d + (n+m)d² amortization.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.hstu import HSTUConfig, hstu_apply, hstu_init
from repro.core.masks import roo_spec
from repro.core.roo_batch import ROOBatch


@dataclasses.dataclass(frozen=True)
class ROOSequenceConfig:
    hstu: HSTUConfig
    n_hist: int                 # padded history length n
    m_targets: int              # padded per-request target capacity m


def roo_sequence_init(rng: jax.Array, cfg: ROOSequenceConfig,
                      dtype=jnp.float32) -> Dict:
    return {"hstu": hstu_init(rng, cfg.hstu, dtype)}


def target_positions(batch: ROOBatch, m_targets: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Map each NRO slot to (request_row, slot_within_request).

    Impressions of a request are contiguous in the NRO axis (batcher
    invariant), so slot-within-request = global_slot - request_offset.
    Returns (seg, k) each (B_NRO,); padding slots get k = m_targets (parked).
    """
    b_ro = batch.b_ro
    seg = batch.segment_ids
    offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(batch.num_impressions.astype(jnp.int32))[:-1]])
    # NRO slots may have per-shard padding gaps; recover the request-local
    # index by ranking valid slots within each segment.
    valid = (seg < b_ro)
    # rank of slot within its segment: cumulative count of same-seg slots before it
    # (segments are contiguous, so a cumsum over a one-hot-free trick works)
    idx = jnp.arange(seg.shape[0], dtype=jnp.int32)
    seg_safe = jnp.minimum(seg, b_ro - 1)
    # padding slots must not pollute segment_min of the segment they alias
    idx_masked = jnp.where(seg < b_ro, idx, jnp.iinfo(jnp.int32).max)
    seg_start = jnp.take(
        jax.ops.segment_min(idx_masked, seg_safe, num_segments=b_ro), seg_safe)
    k = idx - seg_start
    k = jnp.where(valid & (k < m_targets), k, m_targets)
    return seg, k


def encode_roo(params: Dict, cfg: ROOSequenceConfig,
               hist_emb: jnp.ndarray, hist_lengths: jnp.ndarray,
               target_emb_ro: jnp.ndarray, target_counts: jnp.ndarray,
               backend: Optional[str] = None, plan=None) -> jnp.ndarray:
    """ROO path: one (n+m) sequence per request.

    hist_emb: (B_RO, n, d); target_emb_ro: (B_RO, m, d) — targets gathered
    to request-major layout. Returns (B_RO, m, d) encoded target outputs.
    ``backend`` overrides the attention backend (kernels/dispatch.py);
    ``plan`` is the training run's sharding plan.
    """
    x = jnp.concatenate([hist_emb, target_emb_ro], axis=1)   # (B_RO, n+m, d)
    spec = roo_spec(hist_lengths, target_counts, cfg.n_hist)
    y = hstu_apply(params["hstu"], cfg.hstu, x, spec, backend=backend,
                   plan=plan)
    return y[:, cfg.n_hist:, :]


def encode_per_impression(params: Dict, cfg: ROOSequenceConfig,
                          hist_emb: jnp.ndarray, hist_lengths: jnp.ndarray,
                          target_emb: jnp.ndarray,
                          backend: Optional[str] = None) -> jnp.ndarray:
    """Impression-level baseline: (history + 1 target) per impression.

    hist_emb: (B_NRO, n, d) — history duplicated per impression;
    target_emb: (B_NRO, d). Returns (B_NRO, d).
    """
    x = jnp.concatenate([hist_emb, target_emb[:, None, :]], axis=1)
    spec = roo_spec(hist_lengths, jnp.ones_like(hist_lengths), cfg.n_hist)
    y = hstu_apply(params["hstu"], cfg.hstu, x, spec, backend=backend)
    return y[:, cfg.n_hist, :]


def scatter_targets_to_nro(encoded_ro: jnp.ndarray, batch: ROOBatch,
                           m_targets: int) -> jnp.ndarray:
    """(B_RO, m, d) -> (B_NRO, d): route each encoded target to its slot."""
    seg, k = target_positions(batch, m_targets)
    b_ro, m, d = encoded_ro.shape
    flat = encoded_ro.reshape(b_ro * m, d)
    flat = jnp.concatenate([flat, jnp.zeros((1, d), flat.dtype)], axis=0)
    lin = jnp.where((seg < b_ro) & (k < m), seg * m + k, b_ro * m)
    return jnp.take(flat, lin, axis=0)


def gather_targets_to_ro(target_emb_nro: jnp.ndarray, batch: ROOBatch,
                         m_targets: int) -> jnp.ndarray:
    """(B_NRO, d) -> (B_RO, m, d): request-major layout (0-padded)."""
    b_ro = batch.b_ro
    seg, k = target_positions(batch, m_targets)
    d = target_emb_nro.shape[-1]
    out = jnp.zeros((b_ro * m_targets + 1, d), target_emb_nro.dtype)
    lin = jnp.where((seg < b_ro) & (k < m_targets),
                    seg * m_targets + k, b_ro * m_targets)
    out = out.at[lin].set(target_emb_nro, mode="drop")
    return out[:-1].reshape(b_ro, m_targets, d)


def sequence_flops(cfg: ROOSequenceConfig, d: int, roo: bool,
                   b_ro: int, b_nro: int) -> int:
    """§3.3 cost model: m(n²d+nd²) vs (n+m)²d+(n+m)d² (per-request units)."""
    n, m = cfg.n_hist, cfg.m_targets
    if roo:
        s = n + m
        return b_ro * (s * s * d + s * d * d) * cfg.hstu.n_layers
    return b_nro * ((n + 1) * (n + 1) * d + (n + 1) * d * d) * cfg.hstu.n_layers
