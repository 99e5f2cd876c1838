"""jit'd public wrappers over the Pallas kernels.

Every wrapper takes a ``backend`` and resolves it through
:mod:`repro.kernels.dispatch` (explicit argument > scoped override >
process default > env var > auto: compiled Pallas on TPU, the jnp path
elsewhere). This module makes no choice of its own, so a kernel runs in
interpret mode only when ``pallas-interpret`` was asked for.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from repro.core.masks import MaskSpec
from repro.kernels import dispatch as _dispatch
from repro.kernels import ref as _ref
from repro.kernels.dot_interaction import dot_interaction as _dot_pallas
from repro.kernels.embedding_bag import embedding_bag as _bag_pallas


@partial(jax.jit, static_argnames=("n_hist", "max_rel_pos", "backend"))
def hstu_attention(q, k, v, rab, hist_lengths, target_counts, *,
                   n_hist: int, max_rel_pos: int = 128,
                   backend: Optional[str] = None):
    spec = MaskSpec(n_hist, hist_lengths, target_counts)
    return _dispatch.hstu_attention(q, k, v, rab, spec, backend=backend,
                                    max_rel_pos=max_rel_pos)


@partial(jax.jit, static_argnames=("pooling", "backend"))
def embedding_bag(table, ids, lengths, *, pooling: str = "sum",
                  backend: Optional[str] = None):
    return _bag_pallas(table, ids, lengths, pooling, backend=backend)


@partial(jax.jit, static_argnames=("backend",))
def dot_interaction(dense_out, sparse_embs, *,
                    backend: Optional[str] = None):
    """DLRM dot interaction on the embedding-kernel backend family."""
    be = _dispatch.resolve_emb_backend(backend)
    if be == "jnp":
        return _ref.dot_interaction_ref(dense_out, sparse_embs)
    return _dot_pallas(dense_out, sparse_embs,
                       interpret=(be == "pallas-interpret"))
