"""Dense/MoE GQA transformer LM — the assigned LM-family architectures.

Production-style JAX implementation:
  * stacked per-layer params + ``lax.scan`` over layers (compact HLO, fast
    SPMD compile) with ``jax.checkpoint`` remat inside the scan body;
  * megatron TP over the `model` axis (q-heads / d_ff / vocab) + FSDP over
    the `data` axis for the non-TP dim of every matrix; sequence-parallel
    residual stream (seq sharded over `model` between blocks);
  * GQA with few KV heads: KV projections replicated over `model` (KV head
    count < TP degree), Q/O sharded;
  * RoPE, SwiGLU/GELU, RMSNorm;
  * q-chunked attention for long sequences (no S×S materialization);
  * optional MoE block (models/lm/moe.py) with explicit all_to_all under
    shard_map.

ROO note (DESIGN.md §4): the paper's technique is a recsys data dedup and
does not apply to LM pretraining batches; these archs run WITHOUT it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import ShardingPlan, replicated_plan
from repro.models.lm.moe import MoEConfig, moe_init, moe_layer, moe_param_specs


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    rope_theta: float = 10000.0
    activation: str = "swiglu"          # swiglu | gelu
    moe: Optional[MoEConfig] = None
    param_dtype: str = "float32"        # float32 | bfloat16
    compute_dtype: str = "bfloat16"
    tie_embeddings: bool = True
    q_chunk: int = 1024                 # q-block size for chunked attention
    full_attn_max_seq: int = 4096       # above this, use chunked attention
    use_spmd_layer: bool = False        # explicit megatron-SP shard_map layer

    @property
    def pdtype(self):
        return jnp.bfloat16 if self.param_dtype == "bfloat16" else jnp.float32

    @property
    def cdtype(self):
        return jnp.bfloat16 if self.compute_dtype == "bfloat16" else jnp.float32

    def n_params(self) -> int:
        d, h, kv, dh, f, L = (self.d_model, self.n_heads, self.n_kv_heads,
                              self.d_head, self.d_ff, self.n_layers)
        attn = d * h * dh + d * 2 * kv * dh + h * dh * d
        if self.moe:
            mlp = (d * self.moe.n_experts_padded
                   + self.moe.n_experts * 3 * d * self.moe.d_ff_expert)
        else:
            n_in = 2 if self.activation == "swiglu" else 1
            mlp = n_in * d * f + f * d
        emb = self.vocab * d * (1 if self.tie_embeddings else 2)
        return L * (attn + mlp + 2 * d) + emb + d

    def n_active_params(self) -> int:
        """Params touched per token (MoE: top_k experts only)."""
        if not self.moe:
            return self.n_params()
        d, L = self.d_model, self.n_layers
        h, kv, dh = self.n_heads, self.n_kv_heads, self.d_head
        attn = d * h * dh + d * 2 * kv * dh + h * dh * d
        mlp = (d * self.moe.n_experts_padded
               + self.moe.top_k * 3 * d * self.moe.d_ff_expert)
        emb = self.vocab * d * (1 if self.tie_embeddings else 2)
        return L * (attn + mlp + 2 * d) + emb + d


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def lm_init(rng: jax.Array, cfg: LMConfig) -> Dict:
    dt = cfg.pdtype
    d, h, kv, dh, f, L = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                          cfg.d_head, cfg.d_ff, cfg.n_layers)
    ks = jax.random.split(rng, 10)

    def nrm(k, shape, fan_in):
        return (jax.random.normal(k, shape) / jnp.sqrt(fan_in)).astype(dt)

    layers = {
        "attn_norm": jnp.ones((L, d), dt),
        "wq": nrm(ks[0], (L, d, h * dh), d),
        "wkv": nrm(ks[1], (L, d, 2 * kv * dh), d),
        "wo": nrm(ks[2], (L, h * dh, d), h * dh),
        "mlp_norm": jnp.ones((L, d), dt),
    }
    if cfg.moe is not None:
        layers.update(moe_init(ks[3], cfg.moe, L, d, dt))
    else:
        layers["w1"] = nrm(ks[4], (L, d, f), d)
        if cfg.activation == "swiglu":
            layers["w3"] = nrm(ks[5], (L, d, f), d)
        layers["w2"] = nrm(ks[6], (L, f, d), f)
    params = {
        "embed": (jax.random.normal(ks[7], (cfg.vocab, d)) * 0.02).astype(dt),
        "layers": layers,
        "final_norm": jnp.ones((d,), dt),
    }
    if not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(ks[8], (cfg.vocab, d)) * 0.02).astype(dt)
    return params


def lm_param_specs(cfg: LMConfig, plan: ShardingPlan) -> Dict:
    """PartitionSpec pytree matching lm_init's structure."""
    m, fs = plan.model_axis, plan.fsdp_axis
    layers = {
        "attn_norm": P(None, None),
        "wq": P(None, fs, m),
        "wkv": P(None, fs, None),
        "wo": P(None, m, fs),
        "mlp_norm": P(None, None),
    }
    if cfg.moe is not None:
        layers.update(moe_param_specs(plan))
    else:
        layers["w1"] = P(None, fs, m)
        if cfg.activation == "swiglu":
            layers["w3"] = P(None, fs, m)
        layers["w2"] = P(None, m, fs)
    specs = {
        "embed": P(m, fs),
        "layers": layers,
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["head"] = P(m, fs)
    return specs


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

def _rmsnorm(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    n = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (n * scale.astype(jnp.float32)).astype(x.dtype)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, n_heads, d_head); positions: (..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs          # (..,S,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def _attention(q, k, v, q_pos, kv_pos, cfg: LMConfig, kv_valid=None):
    """GQA attention, causal by positions. q: (B,Sq,H,dh); k,v: (B,Skv,KV,dh).

    For long Skv the q axis is processed in chunks so the (Sq,Skv) score
    matrix never fully materializes (flash-style streaming is unnecessary
    because full rows fit; blocks bound the working set).
    """
    b, sq, h, dh = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, dh)
    scale = dh ** -0.5

    def block(q_blk, qpos_blk):
        # q_blk: (B, T, KV, G, dh)
        scores = jnp.einsum("btkgd,bskd->btkgs", q_blk, k,
                            preferred_element_type=jnp.float32) * scale
        mask = (kv_pos[:, None, :] <= qpos_blk[:, :, None])          # (B,T,Skv)
        if kv_valid is not None:
            mask = mask & kv_valid[:, None, :]
        scores = jnp.where(mask[:, :, None, None, :], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("btkgs,bskd->btkgd", p, v)

    if sq <= cfg.full_attn_max_seq:
        out = block(qg, q_pos)
    else:
        nblk = sq // cfg.q_chunk
        qb = qg.reshape(b, nblk, cfg.q_chunk, kvh, g, dh).transpose(1, 0, 2, 3, 4, 5)
        pb = q_pos.reshape(b, nblk, cfg.q_chunk).transpose(1, 0, 2)
        out = jax.lax.map(lambda args: block(*args), (qb, pb))
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, kvh, g, dh)
    return out.reshape(b, sq, h, dh)


def _mlp(x, lyr, cfg: LMConfig, plan: ShardingPlan):
    if cfg.activation == "swiglu":
        h = jax.nn.silu(x @ lyr["w1"]) * (x @ lyr["w3"])
    else:
        h = jax.nn.gelu(x @ lyr["w1"])
    h = plan.constrain(h, plan.batch_axes, None, plan.model_axis)
    return h @ lyr["w2"]


def _layer(x, lyr, cfg: LMConfig, plan: ShardingPlan, positions):
    """One transformer block. x: (B, S, d) seq-sharded over model axis."""
    b, s, d = x.shape
    h, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ba, m = plan.batch_axes, plan.model_axis

    xn = _rmsnorm(x, lyr["attn_norm"])
    q = (xn @ lyr["wq"]).reshape(b, s, h, dh)
    q = plan.constrain(q, ba, None, m, None)          # heads TP, seq gathered
    kvp = (xn @ lyr["wkv"]).reshape(b, s, 2, kvh, dh)
    kvp = plan.constrain(kvp, ba, None, None, None, None)
    k, v = kvp[:, :, 0], kvp[:, :, 1]
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    attn = _attention(q, k, v, positions, positions, cfg)
    attn = plan.constrain(attn, ba, None, m, None)
    y = attn.reshape(b, s, h * dh) @ lyr["wo"]
    x = x + plan.constrain(y, ba, m, None)            # back to seq-parallel

    xn = _rmsnorm(x, lyr["mlp_norm"])
    if cfg.moe is not None:
        y = moe_layer(xn, lyr, cfg.moe, plan)
    else:
        y = _mlp(xn, lyr, cfg, plan)
    x = x + plan.constrain(y, ba, m, None)
    return x


# ---------------------------------------------------------------------------
# explicit Megatron-SP layer (beyond-paper optimized path, §Perf)
#
# GSPMD's auto-partitioning of the constrained layer reshards the SP->TP
# boundary as all-gather(seq of ALL heads)+slice and places collectives on
# f32 convert outputs — ~6x the necessary bytes. This shard_map version
# does the textbook schedule: ONE bf16 all-gather of the normed residual
# per block input, local-head attention / local-shard FFN, ONE psum_scatter
# back to sequence parallelism. Requires n_heads % tp == 0 (configs pad).
# ---------------------------------------------------------------------------

def _layer_spmd(x, lyr, cfg: LMConfig, plan: ShardingPlan, positions):
    """One transformer block under shard_map. x: (B, S, d) seq-sharded."""
    m, ba, fs = plan.model_axis, plan.batch_axes, plan.fsdp_axis
    fsdp_axes = fs if isinstance(fs, tuple) else (fs,)
    n_model = plan.mesh.shape[m]
    h, kvh, dh, d = cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_model
    h_loc = h // n_model

    def fn(xl, pos, attn_norm, wq, wkv, wo, mlp_norm, *mlp_w):
        # weights arrive (d/fsdp, cols/m)-sharded; gather the fsdp dim JIT
        wq = jax.lax.all_gather(wq, fsdp_axes, axis=0, tiled=True)
        wkv = jax.lax.all_gather(wkv, fsdp_axes, axis=0, tiled=True)
        wo = jax.lax.all_gather(wo, fsdp_axes, axis=1, tiled=True)
        b, s_loc, _ = xl.shape
        xn = _rmsnorm(xl, attn_norm)
        xg = jax.lax.all_gather(xn, m, axis=1, tiled=True)   # ONE bf16 gather
        s = xg.shape[1]
        q = (xg @ wq).reshape(b, s, h_loc, dh)               # local heads only
        kvp = (xg @ wkv).reshape(b, s, 2, kvh, dh)
        k, v = kvp[:, :, 0], kvp[:, :, 1]
        # GQA with sharded q-heads: pick each local q head's KV head (all KV
        # heads are computed locally — they're cheap and replicated over TP)
        g_global = max(h // kvh, 1)
        shard = jax.lax.axis_index(m)
        kv_idx = (shard * h_loc + jnp.arange(h_loc)) // g_global
        k = jnp.take(k, kv_idx, axis=2)                      # (b,s,h_loc,dh)
        v = jnp.take(v, kv_idx, axis=2)
        posf = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        q = rope(q, posf, cfg.rope_theta)
        k = rope(k, posf, cfg.rope_theta)
        attn = _attention(q, k, v, posf, posf, cfg)          # MHA (g == 1)
        part = attn.reshape(b, s, h_loc * dh) @ wo           # partial over heads
        y = jax.lax.psum_scatter(part, m, scatter_dimension=1, tiled=True)
        xl = xl + y

        xn = _rmsnorm(xl, mlp_norm)
        xg = jax.lax.all_gather(xn, m, axis=1, tiled=True)
        if cfg.activation == "swiglu":
            w1, w3, w2 = mlp_w
            w1 = jax.lax.all_gather(w1, fsdp_axes, axis=0, tiled=True)
            w3 = jax.lax.all_gather(w3, fsdp_axes, axis=0, tiled=True)
            w2 = jax.lax.all_gather(w2, fsdp_axes, axis=1, tiled=True)
            hh = jax.nn.silu(xg @ w1) * (xg @ w3)
        else:
            w1, w2 = mlp_w
            w1 = jax.lax.all_gather(w1, fsdp_axes, axis=0, tiled=True)
            w2 = jax.lax.all_gather(w2, fsdp_axes, axis=1, tiled=True)
            hh = jax.nn.gelu(xg @ w1)
        part = hh @ w2
        y = jax.lax.psum_scatter(part, m, scatter_dimension=1, tiled=True)
        return xl + y

    mlp_names = ("w1", "w3", "w2") if cfg.activation == "swiglu" \
        else ("w1", "w2")
    mlp_specs = tuple(P(fs, m) if n != "w2" else P(m, fs) for n in mlp_names)
    return jax.shard_map(
        fn, mesh=plan.mesh,
        in_specs=(P(ba, m, None), P(ba, None),
                  P(None,), P(fs, m), P(fs, None), P(m, fs), P(None,))
        + mlp_specs,
        out_specs=P(ba, m, None),
        check_vma=False)(
        x, positions, lyr["attn_norm"], lyr["wq"], lyr["wkv"], lyr["wo"],
        lyr["mlp_norm"], *[lyr[n] for n in mlp_names])


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------

def lm_forward(params: Dict, cfg: LMConfig, tokens: jnp.ndarray,
               plan: Optional[ShardingPlan] = None,
               collect_kv: bool = False):
    """tokens: (B, S) int32 -> hidden (B, S, d) [+ per-layer (k, v) stack]."""
    plan = plan or replicated_plan()
    b, s = tokens.shape
    cdt = cfg.cdtype
    x = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
    x = plan.constrain(x, plan.batch_axes, plan.model_axis, None)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    layers = jax.tree.map(lambda p: p.astype(cdt) if p.dtype != jnp.int32 else p,
                          params["layers"])

    def body(carry, lyr):
        x = carry
        if collect_kv:
            # recompute K/V for the cache (prefill): cheap vs attention
            xn = _rmsnorm(x, lyr["attn_norm"])
            kvp = (xn @ lyr["wkv"]).reshape(b, s, 2, cfg.n_kv_heads, cfg.d_head)
            k = rope(kvp[:, :, 0], positions, cfg.rope_theta)
            ys = (k, kvp[:, :, 1])
        else:
            ys = None
        if cfg.use_spmd_layer and plan.enabled:
            x = _layer_spmd(x, lyr, cfg, plan, positions)
        else:
            x = _layer(x, lyr, cfg, plan, positions)
        return x, ys

    body_r = jax.checkpoint(body,
                            policy=jax.checkpoint_policies.nothing_saveable)
    x, kv = jax.lax.scan(body_r, x, layers)
    x = _rmsnorm(x, params["final_norm"])
    if collect_kv:
        return x, kv
    return x


def lm_logits(params: Dict, cfg: LMConfig, hidden: jnp.ndarray,
              plan: Optional[ShardingPlan] = None) -> jnp.ndarray:
    plan = plan or replicated_plan()
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,vd->bsv", hidden, head.astype(hidden.dtype),
                        preferred_element_type=jnp.float32)
    return plan.constrain(logits, plan.batch_axes, None, plan.model_axis)


def lm_loss(params: Dict, cfg: LMConfig, tokens: jnp.ndarray,
            labels: jnp.ndarray,
            plan: Optional[ShardingPlan] = None) -> jnp.ndarray:
    """Causal LM cross-entropy, vocab-sharded logits."""
    plan = plan or replicated_plan()
    hidden = lm_forward(params, cfg, tokens, plan)
    logits = lm_logits(params, cfg, hidden, plan)                 # (B,S,V) f32
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    lab = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                              axis=-1)[..., 0]
    return jnp.mean(lse - lab)
