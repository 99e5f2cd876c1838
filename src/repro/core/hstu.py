"""HSTU — Hierarchical Sequential Transduction Unit (Zhai et al. 2024,
arXiv:2402.17152), the ROO-friendly sequence encoder the paper scales up.

One HSTU layer (pointwise attention variant, as deployed):

    [U, V, Q, K] = SiLU( X @ W_uvqk )                        (f1)
    A            = SiLU( Q K^T / sqrt(d) + rab ) * mask / n  (pointwise attn)
    Y            = ( LayerNorm( A @ V ) * U ) @ W_o          (f2)
    out          = X + Y                                     (residual)

No softmax: SiLU-activated scores scaled by 1/n, which is what makes the
kernel a single fused pass (no running-max bookkeeping) — see
``repro/kernels/hstu_attention.py`` for the Pallas TPU version; this module
is the pure-jnp implementation used as its oracle and for CPU execution.

``rab`` is a learned relative-position bias over clipped position deltas
(optionally time-bucketed — the contextual `c` features of §3.3).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Union

import jax
import jax.numpy as jnp

from repro.core.masks import MaskSpec, PrefixMaskSpec


@dataclasses.dataclass(frozen=True)
class HSTUConfig:
    d_model: int
    n_heads: int
    d_qk: int
    d_v: int
    n_layers: int
    max_rel_pos: int = 128         # rab table covers deltas in [-max, max]
    use_rab: bool = True
    eps: float = 1e-6
    # attention backend (kernels/dispatch.py): None = auto (pallas on TPU,
    # jnp-chunked elsewhere) | "pallas" | "pallas-interpret" | "jnp-chunked"
    # | "jnp-dense"
    attn_backend: Optional[str] = None


def _ln(x, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def hstu_layer_init(rng: jax.Array, cfg: HSTUConfig, dtype=jnp.float32) -> Dict:
    h, dqk, dv, d = cfg.n_heads, cfg.d_qk, cfg.d_v, cfg.d_model
    k1, k2, k3 = jax.random.split(rng, 3)
    fan = (2.0 / (d + h * (2 * dqk + 2 * dv))) ** 0.5
    params = {
        "w_uvqk": (jax.random.normal(k1, (d, h * (2 * dv + 2 * dqk))) * fan).astype(dtype),
        "b_uvqk": jnp.zeros((h * (2 * dv + 2 * dqk),), dtype),
        "w_o": (jax.random.normal(k2, (h * dv, d)) * (2.0 / (h * dv + d)) ** 0.5).astype(dtype),
        "ln_scale": jnp.ones((h * dv,), dtype),
        "ln_bias": jnp.zeros((h * dv,), dtype),
    }
    if cfg.use_rab:
        params["rab"] = (jax.random.normal(k3, (cfg.n_heads, 2 * cfg.max_rel_pos + 1))
                         * 0.02).astype(dtype)
    return params


def hstu_init(rng: jax.Array, cfg: HSTUConfig, dtype=jnp.float32) -> Dict:
    keys = jax.random.split(rng, cfg.n_layers)
    return {"layers": [hstu_layer_init(k, cfg, dtype) for k in keys],
            "in_ln_scale": jnp.ones((cfg.d_model,), dtype),
            "in_ln_bias": jnp.zeros((cfg.d_model,), dtype)}


def _rel_bias(rab: jnp.ndarray, s: int, max_rel: int) -> jnp.ndarray:
    """(H, S, S) bias from the (H, 2*max+1) delta table."""
    pos = jnp.arange(s)
    delta = jnp.clip(pos[:, None] - pos[None, :], -max_rel, max_rel) + max_rel
    return rab[:, delta]          # (H, S, S)


def hstu_attention_chunked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           rab: Optional[jnp.ndarray], spec: MaskSpec,
                           max_rel_pos: int = 128,
                           chunk: int = 128) -> jnp.ndarray:
    """Blockwise jnp reference path: scores, rab bias, and the ROO mask are
    produced one q-chunk at a time (sequential ``lax.map``), so the (S, S)
    tensors never exist in HBM — the off-TPU analogue of the Pallas kernel,
    and what `jnp-chunked` dispatches to. Matches kernels/ref.py numerics.

    q, k: (B, H, S, Dqk); v: (B, H, S, Dv); rab: (H, 2*max_rel_pos+1) | None.
    """
    b, h, s, dqk = q.shape
    dv = v.shape[-1]
    cq = min(chunk, s)
    s_pad = -(-s // cq) * cq
    qp = (jnp.pad(q, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
          if s_pad != s else q)
    inv_d = 1.0 / math.sqrt(dqk)
    inv_n = 1.0 / s
    n_hist = spec.n_hist
    hl, tc = spec.hist_lengths, spec.target_counts
    kf = k.astype(jnp.float32)
    cols = jnp.arange(s)
    is_hk = cols < n_hist
    valid_c = jnp.where(is_hk[None, :], cols[None, :] < hl[:, None],
                        (cols[None, :] - n_hist) < tc[:, None])      # (B, S)

    def one_chunk(ci):
        q_c = jax.lax.dynamic_slice(
            qp, (0, 0, ci * cq, 0), (b, h, cq, dqk)).astype(jnp.float32)
        rows = ci * cq + jnp.arange(cq)
        scores = jnp.einsum("bhid,bhjd->bhij", q_c, kf,
                            preferred_element_type=jnp.float32) * inv_d
        if rab is not None:
            delta = jnp.clip(rows[:, None] - cols[None, :],
                             -max_rel_pos, max_rel_pos) + max_rel_pos
            scores = scores + rab[:, delta][None].astype(scores.dtype)
        is_hq = rows < n_hist
        struct = ((is_hq[:, None] & is_hk[None, :]
                   & (cols[None, :] <= rows[:, None]))
                  | (~is_hq[:, None] & is_hk[None, :])
                  | (~is_hq[:, None] & ~is_hk[None, :]
                     & (rows[:, None] == cols[None, :])))            # (cq, S)
        valid_r = jnp.where(is_hq[None, :], rows[None, :] < hl[:, None],
                            (rows[None, :] - n_hist) < tc[:, None])  # (B, cq)
        m = struct[None] & valid_r[:, :, None] & valid_c[:, None, :]
        a = jax.nn.silu(scores) * inv_n
        a = a * m[:, None].astype(a.dtype)
        return jnp.einsum("bhij,bhjd->bhid", a.astype(v.dtype), v)

    out = jax.lax.map(one_chunk, jnp.arange(s_pad // cq))
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, s_pad, dv)
    return out[:, :, :s, :] if s_pad != s else out


def hstu_attention_prefix_chunked(q: jnp.ndarray, k: jnp.ndarray,
                                  v: jnp.ndarray,
                                  rab: Optional[jnp.ndarray],
                                  spec: PrefixMaskSpec,
                                  scale_len: int,
                                  max_rel_pos: int = 128,
                                  chunk: int = 128) -> jnp.ndarray:
    """Blockwise cached-prefix attention — the `jnp-chunked` backend of
    ``dispatch.hstu_attention_prefix``. Rows are [new events | targets]
    (q: (B, H, R, Dqk)), columns the full K/V buffer [history cache |
    targets] (k/v: (B, H, C, ·)). Numerics deliberately mirror
    :func:`hstu_attention_chunked` op for op, so extend-from-empty
    (prefix 0, n_new == n_hist) is bit-identical to full recompute.
    """
    b, h, n_rows, dqk = q.shape
    dv = v.shape[-1]
    n_cols = k.shape[2]
    cq = min(chunk, n_rows)
    r_pad = -(-n_rows // cq) * cq
    qp = (jnp.pad(q, ((0, 0), (0, 0), (0, r_pad - n_rows), (0, 0)))
          if r_pad != n_rows else q)
    inv_d = 1.0 / math.sqrt(dqk)
    inv_n = 1.0 / scale_len
    n_hist, n_new = spec.n_hist, spec.n_new
    pfx, nc, tc = spec.prefix_lengths, spec.new_counts, spec.target_counts
    kf = k.astype(jnp.float32)
    cols = jnp.arange(n_cols)
    is_hk = cols < n_hist
    valid_c = jnp.where(is_hk[None, :],
                        cols[None, :] < (pfx + nc)[:, None],
                        (cols[None, :] - n_hist) < tc[:, None])      # (B, C)

    def one_chunk(ci):
        q_c = jax.lax.dynamic_slice(
            qp, (0, 0, ci * cq, 0), (b, h, cq, dqk)).astype(jnp.float32)
        rows = ci * cq + jnp.arange(cq)
        is_new = rows < n_new
        row_pos = jnp.where(is_new[None, :], pfx[:, None] + rows[None, :],
                            rows[None, :] + (n_hist - n_new))        # (B, cq)
        scores = jnp.einsum("bhid,bhjd->bhij", q_c, kf,
                            preferred_element_type=jnp.float32) * inv_d
        if rab is not None:
            delta = jnp.clip(row_pos[:, :, None] - cols[None, None, :],
                             -max_rel_pos, max_rel_pos) + max_rel_pos
            bias = jnp.moveaxis(jnp.take(rab, delta, axis=1), 0, 1)
            scores = scores + bias.astype(scores.dtype)              # (B,H,cq,C)
        struct = ((is_new[None, :, None] & is_hk[None, None, :]
                   & (cols[None, None, :] <= row_pos[:, :, None]))
                  | ((~is_new[:, None] & is_hk[None, :])
                     | (~is_new[:, None] & ~is_hk[None, :]
                        & ((rows - n_new)[:, None]
                           == (cols - n_hist)[None, :])))[None])     # (B, cq, C)
        valid_r = jnp.where(is_new[None, :], rows[None, :] < nc[:, None],
                            (rows[None, :] - n_new) < tc[:, None])   # (B, cq)
        m = struct & valid_r[:, :, None] & valid_c[:, None, :]
        a = jax.nn.silu(scores) * inv_n
        a = a * m[:, None].astype(a.dtype)
        return jnp.einsum("bhij,bhjd->bhid", a.astype(v.dtype), v)

    out = jax.lax.map(one_chunk, jnp.arange(r_pad // cq))
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, r_pad, dv)
    return out[:, :, :n_rows, :] if r_pad != n_rows else out


def hstu_layer_apply(params: Dict, cfg: HSTUConfig, x: jnp.ndarray,
                     mask: Union[jnp.ndarray, MaskSpec],
                     backend: Optional[str] = None,
                     plan=None) -> jnp.ndarray:
    """x: (B, S, d). Returns (B, S, d).

    ``mask``: a :class:`MaskSpec` (preferred — routed through
    kernels/dispatch.py so the mask is generated inside the selected
    backend) or a dense (B, S, S) / (S, S) bool array (legacy path, which
    materializes scores + bias in HBM).
    ``backend`` overrides ``cfg.attn_backend`` for this call; ``plan`` is
    the training run's sharding plan (the Pallas kernels run per device).
    """
    b, s, d = x.shape
    h, dqk, dv = cfg.n_heads, cfg.d_qk, cfg.d_v
    xn = _ln(x, cfg.eps)
    uvqk = jax.nn.silu(xn @ params["w_uvqk"] + params["b_uvqk"])
    u, v, q, k = jnp.split(uvqk, [h * dv, 2 * h * dv, 2 * h * dv + h * dqk], axis=-1)
    q = q.reshape(b, s, h, dqk).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, h, dqk).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, h, dv).transpose(0, 2, 1, 3)

    if isinstance(mask, MaskSpec):
        from repro.kernels import dispatch
        rab = params["rab"] if cfg.use_rab else None
        av = dispatch.hstu_attention(q, k, v, rab, mask,
                                     backend=backend or cfg.attn_backend,
                                     max_rel_pos=cfg.max_rel_pos, plan=plan)
    else:
        if mask.ndim == 2:
            mask = mask[None]
        bias = (_rel_bias(params["rab"], s, cfg.max_rel_pos)[None]
                if cfg.use_rab else None)
        scores = jnp.einsum("bhid,bhjd->bhij", q, k) / jnp.sqrt(
            jnp.asarray(dqk, x.dtype))
        if bias is not None:
            scores = scores + bias
        a = jax.nn.silu(scores) / jnp.asarray(s, x.dtype)
        a = a * mask[:, None].astype(a.dtype)
        av = jnp.einsum("bhij,bhjd->bhid", a, v)

    av = av.transpose(0, 2, 1, 3).reshape(b, s, h * dv)
    y = _ln(av, cfg.eps) * params["ln_scale"] + params["ln_bias"]
    y = (y * u) @ params["w_o"]
    return x + y


def hstu_apply(params: Dict, cfg: HSTUConfig, x: jnp.ndarray,
               mask: Union[jnp.ndarray, MaskSpec],
               backend: Optional[str] = None, plan=None) -> jnp.ndarray:
    x = _ln(x, cfg.eps) * params["in_ln_scale"] + params["in_ln_bias"]
    for layer in params["layers"]:
        x = hstu_layer_apply(layer, cfg, x, mask, backend=backend, plan=plan)
    return x


def hstu_prefix_layer_apply(params: Dict, cfg: HSTUConfig, x: jnp.ndarray,
                            k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                            spec: PrefixMaskSpec, scale_len: int,
                            backend: Optional[str] = None):
    """One HSTU layer over [new events | targets] rows against a per-user
    K/V cache (incremental serving).

    x: (B, n_new + m, d); k_cache: (B, n_hist, H, dqk); v_cache:
    (B, n_hist, H, dv). The layer projects the rows exactly as
    :func:`hstu_layer_apply` (row-wise ops are row-count invariant, which is
    what makes the split bit-exact), scatters the valid new rows' K/V into
    the cache at ``prefix + r``, and attends rows against
    [cache | target K/V]. Returns ``(x_out, k_cache', v_cache')`` — the
    updated caches are this layer's state for the *next* request.
    """
    b, r_len, d = x.shape
    h, dqk, dv = cfg.n_heads, cfg.d_qk, cfg.d_v
    n_hist, n_new = spec.n_hist, spec.n_new
    xn = _ln(x, cfg.eps)
    uvqk = jax.nn.silu(xn @ params["w_uvqk"] + params["b_uvqk"])
    u, v, q, k = jnp.split(uvqk, [h * dv, 2 * h * dv, 2 * h * dv + h * dqk],
                           axis=-1)
    q = q.reshape(b, r_len, h, dqk).transpose(0, 2, 1, 3)
    k = k.reshape(b, r_len, h, dqk)
    v = v.reshape(b, r_len, h, dv)

    # Scatter valid new rows into the cache; invalid rows park at the extra
    # slot n_hist, which is cropped — garbage never lands in user state.
    rr = jnp.arange(n_new)
    pos = jnp.where(rr[None, :] < spec.new_counts[:, None],
                    spec.prefix_lengths[:, None] + rr[None, :], n_hist)
    bidx = jnp.arange(b)[:, None]
    kc = jnp.concatenate([k_cache, jnp.zeros((b, 1, h, dqk), k_cache.dtype)],
                         axis=1)
    kc = kc.at[bidx, pos].set(k[:, :n_new], mode="drop")[:, :n_hist]
    vc = jnp.concatenate([v_cache, jnp.zeros((b, 1, h, dv), v_cache.dtype)],
                         axis=1)
    vc = vc.at[bidx, pos].set(v[:, :n_new], mode="drop")[:, :n_hist]

    k_cols = jnp.concatenate([kc, k[:, n_new:]], axis=1).transpose(0, 2, 1, 3)
    v_cols = jnp.concatenate([vc, v[:, n_new:]], axis=1).transpose(0, 2, 1, 3)

    from repro.kernels import dispatch
    rab = params["rab"] if cfg.use_rab else None
    av = dispatch.hstu_attention_prefix(
        q, k_cols, v_cols, rab, spec, backend=backend or cfg.attn_backend,
        scale_len=scale_len, max_rel_pos=cfg.max_rel_pos)

    av = av.transpose(0, 2, 1, 3).reshape(b, r_len, h * dv)
    y = _ln(av, cfg.eps) * params["ln_scale"] + params["ln_bias"]
    y = (y * u) @ params["w_o"]
    return x + y, kc, vc


def hstu_prefix_apply(params: Dict, cfg: HSTUConfig, x: jnp.ndarray,
                      state_k: jnp.ndarray, state_v: jnp.ndarray,
                      spec: PrefixMaskSpec, scale_len: int,
                      backend: Optional[str] = None):
    """Incremental counterpart of :func:`hstu_apply`.

    x: (B, n_new + m, d) rows [new events | targets]; state_k:
    (B, n_layers, n_hist, H, dqk); state_v: (B, n_layers, n_hist, H, dv).
    Returns ``(x_out, state_k', state_v')`` with the per-layer caches
    extended by this request's valid new events.
    """
    x = _ln(x, cfg.eps) * params["in_ln_scale"] + params["in_ln_bias"]
    ks, vs = [], []
    for li, layer in enumerate(params["layers"]):
        x, kc, vc = hstu_prefix_layer_apply(
            layer, cfg, x, state_k[:, li], state_v[:, li], spec, scale_len,
            backend=backend)
        ks.append(kc)
        vs.append(vc)
    return x, jnp.stack(ks, axis=1), jnp.stack(vs, axis=1)


def hstu_flops(cfg: HSTUConfig, batch: int, seq: int) -> int:
    """Forward FLOPs (2x MACs) of the encoder — used for the §3.3
    amortization benchmark and Table 6 accounting."""
    h, dqk, dv, d = cfg.n_heads, cfg.d_qk, cfg.d_v, cfg.d_model
    per_layer = (
        2 * seq * d * h * (2 * dv + 2 * dqk)        # f1 projections
        + 2 * h * seq * seq * dqk                   # Q K^T
        + 2 * h * seq * seq * dv                    # A V
        + 2 * seq * h * dv * d                      # f2 output proj
    )
    return batch * cfg.n_layers * per_layer
