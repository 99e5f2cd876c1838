"""Backend dispatch — the one place that decides how the repo's hot compute
paths execute: HSTU attention and the embedding-bag lookup.

HSTU backends (see docs/KERNELS.md for the full table):

  pallas           — fused Pallas TPU kernel, forward + backward
                     (``jax.custom_vjp``), compiled (``interpret=False``)
  pallas-interpret — same kernels through the Pallas interpreter; runs
                     anywhere, used for validation and CI
  jnp-chunked      — blockwise pure-jnp path (core.hstu): scores, bias and
                     mask are produced per q-chunk so no (S, S) tensor ever
                     exists in HBM, even off-TPU
  jnp-dense        — the naive (S, S)-materializing oracle (kernels.ref);
                     ground truth for parity tests only

Both backend families resolve through the shared precedence ladder in
:mod:`repro.scenario.knobs` (explicit ``backend=`` argument >
:func:`use_backend` scoped override > :func:`set_default_backend` /
scenario-spec default > ``REPRO_HSTU_BACKEND`` env var > auto: ``pallas``
on TPU, the jnp fallback elsewhere). Explicitly configured knobs beat the
ambient env var so an exported debug override cannot silently win over a
CLI flag, a pinned ``ServeConfig``, or a scenario spec. Backend resolution
happens at trace time, so a jit'd train step bakes in whichever backend
was active when it first ran.

Embedding-bag backends (docs/EMBEDDINGS.md) have their own knob
(``REPRO_EMB_BACKEND``, ``set_default_emb_backend``, ``use_emb_backend``):

  pallas           — fused Pallas TPU kernel (kernels/embedding_bag.py),
                     forward + COO-row backward (``jax.custom_vjp``)
  pallas-interpret — same kernels through the Pallas interpreter
  jnp              — take + masked reduce oracle (kernels/ref.py)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.masks import MaskSpec, PrefixMaskSpec
from repro.scenario.knobs import UNSET, Knob

BACKENDS = ("pallas", "pallas-interpret", "jnp-chunked", "jnp-dense")
ENV_VAR = "REPRO_HSTU_BACKEND"

EMB_BACKENDS = ("pallas", "pallas-interpret", "jnp")
EMB_ENV_VAR = "REPRO_EMB_BACKEND"

ATTN_KNOB = Knob(
    "attn_backend", ENV_VAR, choices=BACKENDS, kind="backend",
    auto=lambda: "pallas" if jax.default_backend() == "tpu"
    else "jnp-chunked")

EMB_KNOB = Knob(
    "emb_backend", EMB_ENV_VAR, choices=EMB_BACKENDS, kind="backend",
    auto=lambda: "pallas" if jax.default_backend() == "tpu" else "jnp")


# thin compatibility wrappers over the shared ladder; ``None`` means
# "unset" on this API (clear the default / skip the rung), which the
# knob layer spells UNSET

def set_default_backend(backend: Optional[str]) -> None:
    """Process-wide default (used by launch/train.py --attn-backend)."""
    ATTN_KNOB.set_default(UNSET if backend is None else backend)


def get_default_backend() -> Optional[str]:
    return ATTN_KNOB.get_default()


def use_backend(backend: Optional[str]):
    """Scoped backend override (ContextVar, so concurrent servers/threads
    tracing at the same time cannot leak into each other); ``None`` is a
    no-op."""
    return ATTN_KNOB.scoped(UNSET if backend is None else backend)


def resolve_backend(backend: Optional[str] = None) -> str:
    return ATTN_KNOB.resolve(UNSET if backend is None else backend)


def set_default_emb_backend(backend: Optional[str]) -> None:
    """Process-wide default (used by launch/train.py --emb-backend)."""
    EMB_KNOB.set_default(UNSET if backend is None else backend)


def get_default_emb_backend() -> Optional[str]:
    return EMB_KNOB.get_default()


def use_emb_backend(backend: Optional[str]):
    """Scoped embedding-bag backend override; ``None`` is a no-op."""
    return EMB_KNOB.scoped(UNSET if backend is None else backend)


def resolve_emb_backend(backend: Optional[str] = None) -> str:
    return EMB_KNOB.resolve(UNSET if backend is None else backend)


def _per_shard(kernel, plan, q, k, v, rab, hist_lengths, target_counts):
    """Run a Pallas attention kernel once per device under ``plan``'s mesh.

    The compiler cannot partition a Mosaic kernel, so it runs inside
    ``shard_map``: batch rows split over the plan's batch axes, heads over
    the model axis when they divide it (else each model-axis device
    repeats its batch shard's heads). The bias table's gradient is summed
    across batch shards by the map's transpose.

    ``check_vma`` is off because ``pallas_call`` declares its outputs with
    plain shapes that name no mesh axes they vary over; the specs above
    state that instead.
    """
    from jax.sharding import PartitionSpec as P
    n_model = plan.mesh.shape[plan.model_axis]
    m = plan.model_axis if q.shape[1] % n_model == 0 else None
    ba = plan.batch_axes
    qkv = P(ba, m)
    args, specs = [q, k, v, hist_lengths, target_counts], [qkv] * 3 + [P(ba)] * 2
    if rab is not None:
        args.append(rab)
        specs.append(P(m))
    return jax.shard_map(
        lambda q, k, v, hl, tc, rab=None: kernel(q, k, v, rab, hl, tc),
        mesh=plan.mesh, in_specs=tuple(specs), out_specs=qkv,
        check_vma=False)(*args)


def hstu_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   rab: Optional[jnp.ndarray], spec: MaskSpec,
                   backend: Optional[str] = None, *,
                   max_rel_pos: int = 128,
                   block_q: int = 128, block_k: int = 128,
                   plan=None) -> jnp.ndarray:
    """Masked HSTU pointwise attention on the selected backend.

    q, k: (B, H, S, Dqk); v: (B, H, S, Dv); rab: (H, 2*max_rel_pos+1) or
    None; ``spec`` describes the ROO mask structurally (never densified
    except on the jnp-dense oracle). All backends are differentiable and
    agree within test tolerances (tests/test_dispatch.py). Under an enabled
    sharding ``plan`` the Pallas kernels run per device (:func:`_per_shard`);
    the jnp backends are left to the partitioner.
    """
    be = resolve_backend(backend)
    if be in ("pallas", "pallas-interpret"):
        from repro.kernels.hstu_attention import hstu_attention as _pallas

        def kernel(q, k, v, rab, hist_lengths, target_counts):
            return _pallas(q, k, v, rab, spec.n_hist, hist_lengths,
                           target_counts, max_rel_pos, block_q, block_k,
                           interpret=(be == "pallas-interpret"))
        if plan is not None and plan.enabled:
            return _per_shard(kernel, plan, q, k, v, rab, spec.hist_lengths,
                              spec.target_counts)
        return kernel(q, k, v, rab, spec.hist_lengths, spec.target_counts)
    if be == "jnp-chunked":
        from repro.core.hstu import hstu_attention_chunked
        return hstu_attention_chunked(q, k, v, rab, spec,
                                      max_rel_pos=max_rel_pos, chunk=block_q)
    from repro.kernels.ref import hstu_attention_ref
    return hstu_attention_ref(q, k, v, rab, spec.n_hist, spec.hist_lengths,
                              spec.target_counts, max_rel_pos)


def hstu_attention_prefix(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          rab: Optional[jnp.ndarray], spec: PrefixMaskSpec,
                          backend: Optional[str] = None, *,
                          scale_len: int,
                          max_rel_pos: int = 128,
                          block_q: int = 128,
                          block_k: int = 128) -> jnp.ndarray:
    """Cached-prefix HSTU attention (incremental serving; forward only).

    Rows are [new events | targets] (q: (B, H, n_new + m, Dqk)); columns the
    full K/V buffer [history cache | targets] (k, v: (B, H, n_hist + m, ·)).
    ``spec`` carries the per-request prefix/new/target counts; ``scale_len``
    pins the 1/n normalizer to the equivalent full-sequence length so the
    incremental path is numerically the full ROO forward restricted to the
    new rows. Same backend ladder as :func:`hstu_attention`; with
    ``prefix_lengths == 0`` and ``n_new == n_hist`` every backend computes
    exactly its full-recompute counterpart (tests/test_incremental.py).
    """
    be = resolve_backend(backend)
    if be in ("pallas", "pallas-interpret"):
        from repro.kernels.hstu_attention import (
            hstu_attention_prefix as _pallas)
        return _pallas(q, k, v, rab, spec.n_hist, spec.n_new,
                       spec.prefix_lengths, spec.new_counts,
                       spec.target_counts, scale_len, max_rel_pos,
                       block_q, block_k,
                       interpret=(be == "pallas-interpret"))
    if be == "jnp-chunked":
        from repro.core.hstu import hstu_attention_prefix_chunked
        return hstu_attention_prefix_chunked(
            q, k, v, rab, spec, scale_len,
            max_rel_pos=max_rel_pos, chunk=block_q)
    from repro.kernels.ref import hstu_attention_prefix_ref
    return hstu_attention_prefix_ref(q, k, v, rab, spec.n_hist, spec.n_new,
                                     spec.prefix_lengths, spec.new_counts,
                                     spec.target_counts, scale_len,
                                     max_rel_pos)
