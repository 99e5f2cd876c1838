"""Pallas TPU kernel: fused DLRM dot interaction.

Computes the pairwise-dot Gram matrix of [dense | sparse] feature embeddings
and writes dense ++ strict-lower-triangle in ONE pass: the (F+1, F+1) Gram
block and the triangle gather both live in VMEM, so the (B, F+1, F+1)
intermediate never reaches HBM (the jnp path materializes it).

Grid: (B/bb,); per-step block (bb, F+1, D) -> MXU batched dot -> static
tril gather -> (bb, D + F(F+1)/2) output tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(tril_ref, t_ref, o_ref, *, d: int):
    t = t_ref[...].astype(jnp.float32)                  # (bb, F1, D)
    z = jax.lax.dot_general(t, t, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)  # (bb,F1,F1)
    bb, f1, _ = z.shape
    flat = z.reshape(bb, f1 * f1)
    pairs = jnp.take(flat, tril_ref[...], axis=1)       # (bb, n_pairs)
    dense = t[:, 0, :]                                  # (bb, D)
    o_ref[...] = jnp.concatenate([dense, pairs], axis=1).astype(o_ref.dtype)


def dot_interaction(dense_out: jnp.ndarray, embs: jnp.ndarray,
                    block_b: int = 128, interpret: bool = True) -> jnp.ndarray:
    """dense_out: (B, D); embs: (B, F, D) -> (B, D + (F+1)F/2)."""
    b, d = dense_out.shape
    f = embs.shape[1]
    f1 = f + 1
    t = jnp.concatenate([dense_out[:, None, :], embs], axis=1)
    bb = min(block_b, b)
    assert b % bb == 0, (b, bb)
    i, j = np.tril_indices(f1, k=-1)
    tril = (i * f1 + j).astype(np.int32)
    n_out = d + len(tril)

    kernel = functools.partial(_kernel, d=d)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b // bb,),
            in_specs=[pl.BlockSpec((bb, f1, d), lambda bi, *s: (bi, 0, 0))],
            out_specs=pl.BlockSpec((bb, n_out), lambda bi, *s: (bi, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_out), dense_out.dtype),
        interpret=interpret,
        name="dot_interaction",
    )(jnp.asarray(tril), t)
