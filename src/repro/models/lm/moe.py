"""Mixture-of-Experts block with explicit expert-parallel all_to_all.

GShard-style capacity-based routing, but dispatch is sort-based (argsort by
expert + scatter into capacity slots) instead of the O(T·E·C·d) one-hot
einsum — gather/scatter moves O(T·k·d) bytes only.

Parallelism: experts sharded over the `model` axis (expert parallelism);
expert weights additionally FSDP-sharded over the data axes and all-gathered
just-in-time inside the shard_map body (autodiff turns that into the grad
reduce-scatter). Token exchange is one pair of `lax.all_to_all` over
`model` per layer — the collective the roofline table accounts per step.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import ShardingPlan


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    pad_to: int = 16                 # pad expert count to EP-degree multiple
    router_dtype: str = "float32"

    @property
    def n_experts_padded(self) -> int:
        return math.ceil(self.n_experts / self.pad_to) * self.pad_to


def moe_init(rng: jax.Array, cfg: MoEConfig, n_layers: int, d_model: int,
             dtype) -> Dict:
    ep = cfg.n_experts_padded
    fe = cfg.d_ff_expert
    ks = jax.random.split(rng, 4)

    def nrm(k, shape, fan_in):
        return (jax.random.normal(k, shape) / jnp.sqrt(fan_in)).astype(dtype)

    return {
        "router": nrm(ks[0], (n_layers, d_model, ep), d_model),
        "w1e": nrm(ks[1], (n_layers, ep, d_model, fe), d_model),
        "w3e": nrm(ks[2], (n_layers, ep, d_model, fe), d_model),
        "w2e": nrm(ks[3], (n_layers, ep, fe, d_model), fe),
    }


def moe_param_specs(plan: ShardingPlan) -> Dict:
    m, fs = plan.model_axis, plan.fsdp_axis
    return {
        "router": P(None, None, None),
        "w1e": P(None, m, fs, None),
        "w3e": P(None, m, fs, None),
        "w2e": P(None, m, None, fs),
    }


def _capacity(t_local: int, cfg: MoEConfig) -> int:
    return max(1, math.ceil(t_local * cfg.top_k / cfg.n_experts_padded
                            * cfg.capacity_factor))


def _route_local(xt: jnp.ndarray, router: jnp.ndarray, cfg: MoEConfig):
    """xt: (T, d). Returns (topk_idx (T,k), topk_prob (T,k))."""
    rl = (xt.astype(jnp.float32) @ router.astype(jnp.float32))
    pad = jnp.arange(cfg.n_experts_padded) >= cfg.n_experts
    rl = jnp.where(pad[None, :], -1e30, rl)
    probs = jax.nn.softmax(rl, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    return top_i.astype(jnp.int32), top_p


def _dispatch_compute_combine(xt, router, w1, w3, w2, cfg: MoEConfig,
                              model_axis: Optional[str], n_model: int,
                              fsdp_axes, tokens_replicated: bool = False) -> jnp.ndarray:
    """Per-device MoE: route -> sort-dispatch -> a2a -> FFN -> a2a -> combine.

    xt: (T, d) local tokens. w1/w3: (E_loc, d_loc, fe); w2: (E_loc, fe, d_loc).

    ``tokens_replicated``: inference path where every device in a model row
    holds the SAME tokens (decode with tiny batch). Instead of all_to_all,
    each shard computes only its local experts and the partial outputs are
    psum'd over `model` — the standard inference expert-parallel pattern.
    """
    t, d = xt.shape
    ep = cfg.n_experts_padded
    c = _capacity(t, cfg)
    top_i, top_p = _route_local(xt, router, cfg)

    # ---- sort-based dispatch into (E, C, d) capacity buffer ------------------
    flat_e = top_i.reshape(-1)                              # (T*k,)
    flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), cfg.top_k)
    flat_p = top_p.reshape(-1)
    order = jnp.argsort(flat_e)
    se, st, sp = flat_e[order], flat_t[order], flat_p[order]
    counts = jnp.bincount(se, length=ep)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    pos = jnp.arange(t * cfg.top_k, dtype=jnp.int32) - starts[se]
    in_cap = pos < c
    slot = jnp.where(in_cap, se * c + pos, ep * c)          # park overflow
    buf = jnp.zeros((ep * c + 1, d), xt.dtype).at[slot].set(xt[st], mode="drop")
    buf = buf[:-1].reshape(ep, c, d)

    ep_loc = ep // max(n_model, 1)
    use_a2a = (model_axis is not None and n_model > 1 and not tokens_replicated)
    use_slice = (model_axis is not None and n_model > 1 and tokens_replicated)

    # ---- expert exchange ------------------------------------------------------
    if use_a2a:
        buf = jax.lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=1,
                                 tiled=True)                # (E_loc, n*C, d)
    elif use_slice:
        shard = jax.lax.axis_index(model_axis)
        buf = jax.lax.dynamic_slice_in_dim(buf, shard * ep_loc, ep_loc, axis=0)
    # ---- expert FFN (weights all-gathered over fsdp axes JIT) -----------------
    if fsdp_axes:
        w1 = jax.lax.all_gather(w1, fsdp_axes, axis=1, tiled=True)
        w3 = jax.lax.all_gather(w3, fsdp_axes, axis=1, tiled=True)
        w2 = jax.lax.all_gather(w2, fsdp_axes, axis=2, tiled=True)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w1)) \
        * jnp.einsum("ecd,edf->ecf", buf, w3)
    out = jnp.einsum("ecf,efd->ecd", h, w2)                 # (E_loc, n*C, d)

    if use_a2a:
        out = jax.lax.all_to_all(out, model_axis, split_axis=1, concat_axis=0,
                                 tiled=True)                # (E, C, d)
    elif use_slice:
        shard = jax.lax.axis_index(model_axis)
        full = jnp.zeros((ep, c, d), out.dtype)
        out = jax.lax.dynamic_update_slice_in_dim(full, out, shard * ep_loc,
                                                  axis=0)
    # ---- combine --------------------------------------------------------------
    flat_out = out.reshape(ep * c, d)
    gathered = jnp.where(in_cap[:, None],
                         jnp.take(flat_out, jnp.minimum(slot, ep * c - 1),
                                  axis=0), 0.0)
    y = jnp.zeros((t, d), xt.dtype).at[st].add(
        (gathered * sp[:, None]).astype(xt.dtype))
    if use_slice:
        y = jax.lax.psum(y, model_axis)
    return y


def moe_layer(x: jnp.ndarray, lyr: Dict, cfg: MoEConfig,
              plan: ShardingPlan, seq_sharded: bool = True) -> jnp.ndarray:
    """x: (B, S, d) residual -> (B, S, d).

    Under a mesh, runs in shard_map over all axes with explicit collectives;
    without one (CPU tests), runs the same math single-device.
    ``seq_sharded``: training keeps the residual seq-sharded over `model`;
    decode (S == 1) cannot shard seq, so only the batch axes shard.
    """
    b, s, d = x.shape
    router = lyr["router"]
    w1, w3, w2 = lyr["w1e"], lyr["w3e"], lyr["w2e"]

    if not plan.enabled:
        xt = x.reshape(b * s, d)
        y = _dispatch_compute_combine(xt, router, w1, w3, w2, cfg,
                                      model_axis=None, n_model=1,
                                      fsdp_axes=None)
        return y.reshape(b, s, d)

    m, ba, fs = plan.model_axis, plan.batch_axes, plan.fsdp_axis
    n_model = plan.mesh.shape[m]
    fsdp_axes = fs if isinstance(fs, tuple) else (fs,)
    n_batch = 1
    for a in ba:
        n_batch *= plan.mesh.shape[a]
    batch_sharded = (b % n_batch == 0) and b >= n_batch
    x_spec = P(ba if batch_sharded else None,
               m if seq_sharded else None, None)
    tokens_replicated = not seq_sharded

    def fn(xl, r, w1l, w3l, w2l):
        bl, sl, _ = xl.shape
        xt = xl.reshape(bl * sl, d)
        y = _dispatch_compute_combine(xt, r, w1l, w3l, w2l, cfg,
                                      model_axis=m, n_model=n_model,
                                      fsdp_axes=fsdp_axes,
                                      tokens_replicated=tokens_replicated)
        return y.reshape(bl, sl, d)

    # check_vma: the training path is fully checkable; the replicated-token
    # inference path is provably invariant (tokens replicated + psum over
    # model) but the static checker can't see through the FSDP all_gather.
    return jax.shard_map(
        fn, mesh=plan.mesh,
        in_specs=(x_spec, P(None, None),
                  P(m, fs, None), P(m, fs, None), P(m, None, fs)),
        out_specs=x_spec,
        check_vma=not tokens_replicated)(x, router, w1, w3, w2)
