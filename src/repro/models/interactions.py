"""Feature-interaction architectures: DLRM dot interaction, DCNv2 cross.

``dot_interaction`` is the MLPerf-DLRM op (pairwise dots between dense
output and the sparse embeddings, lower-triangle flattened, concat dense).
The Pallas kernel version is repro/kernels/dot_interaction.py; this is its
oracle.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def dot_interaction(dense_out: jnp.ndarray, embs: jnp.ndarray,
                    self_interaction: bool = False) -> jnp.ndarray:
    """dense_out: (B, D); embs: (B, F, D) with same D.

    Returns (B, D + F'*(F'+offset)//2) where F' = F+1 (dense row included).
    """
    b, d = dense_out.shape
    t = jnp.concatenate([dense_out[:, None, :], embs], axis=1)  # (B,F+1,D)
    z = jnp.einsum("bfd,bgd->bfg", t, t)                               # (B,F+1,F+1)
    f = t.shape[1]
    i, j = jnp.tril_indices(f, k=0 if self_interaction else -1)
    flat = z[:, i, j]
    return jnp.concatenate([dense_out, flat], axis=1)


def dcnv2_init(rng: jax.Array, dim: int, n_layers: int, rank: int = 0,
               dtype=jnp.float32) -> Dict:
    """DCNv2 cross network; rank>0 uses the low-rank (DCN-Mix) variant."""
    layers = []
    keys = jax.random.split(rng, n_layers)
    for k in keys:
        if rank and rank < dim:
            k1, k2 = jax.random.split(k)
            layers.append({
                "u": (jax.random.normal(k1, (dim, rank)) / jnp.sqrt(dim)).astype(dtype),
                "v": (jax.random.normal(k2, (rank, dim)) / jnp.sqrt(rank)).astype(dtype),
                "b": jnp.zeros((dim,), dtype)})
        else:
            layers.append({
                "w": (jax.random.normal(k, (dim, dim)) / jnp.sqrt(dim)).astype(dtype),
                "b": jnp.zeros((dim,), dtype)})
    return {"layers": layers}


def dcnv2_apply(params: Dict, x0: jnp.ndarray) -> jnp.ndarray:
    """x_{l+1} = x0 * (W x_l + b) + x_l."""
    x = x0
    for lyr in params["layers"]:
        if "u" in lyr:
            wx = (x @ lyr["u"]) @ lyr["v"] + lyr["b"]
        else:
            wx = x @ lyr["w"] + lyr["b"]
        x = x0 * wx + x
    return x
