"""Attention variants (GQA / sliding-window / MLA), MLPs, and MoE.

All functions are (params, x, ...) -> y with plain dict param pytrees, and
come in two modes:
  * train/prefill: full sequence, causal (optionally windowed) mask
  * decode: one new token against a KV cache at position ``pos``

Spec builders (``*_specs``) are the single source of truth for shapes and
logical sharding axes (models/common.ParamSpec).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from ..configs.base import ArchConfig
from ..sharding import MeshContext, constrain
from .common import ParamSpec, apply_rope, dense, rms_norm

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# GQA attention (covers MHA and MQA; optional sliding window)
# ---------------------------------------------------------------------------

def gqa_specs(cfg: ArchConfig) -> dict:
    d, H, Hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    specs = {
        "wq": ParamSpec((d, H, hd), ("fsdp", "heads", "head_dim")),
        "wk": ParamSpec((d, Hkv, hd), ("fsdp", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, Hkv, hd), ("fsdp", "kv_heads", "head_dim")),
        "wo": ParamSpec((H, hd, d), ("heads", "head_dim", "fsdp")),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((H, hd), ("heads", "head_dim"), init="zeros")
        specs["bk"] = ParamSpec((Hkv, hd), ("kv_heads", "head_dim"), init="zeros")
        specs["bv"] = ParamSpec((Hkv, hd), ("kv_heads", "head_dim"), init="zeros")
    return specs


def _attend(q, k, v, mask):
    """q (B,S,H,hd), k/v (B,T,Hkv,hd), mask (B,1,S,T) or (1,1,S,T) bool.
    Materialises the full (S, T) logits — decode/small-S path and the
    oracle for the chunked version below."""
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    q = q.reshape(B, S, Hkv, group, hd)
    logits = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(hd).astype(jnp.float32)
    logits = jnp.where(mask[:, :, None] if mask.ndim == 4 else mask,
                       logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, H, hd)


def _causal_mask(S, T, offset: int = 0, window: int = 0):
    """(1, 1, S, T) bool; q position i (global offset+i) sees keys j <= i,
    and j > i - window when window > 0."""
    qpos = offset + jnp.arange(S)[:, None]
    kpos = jnp.arange(T)[None, :]
    m = kpos <= qpos
    if window > 0:
        m &= kpos > qpos - window
    return m[None, None]


ATTN_CHUNK = 1024  # KV-chunk length for the online-softmax path


def _attend_chunked(q, k, v, *, window: int = 0, chunk: int = ATTN_CHUNK):
    """Flash-style causal attention: lax.scan over KV chunks with an online
    softmax, so logits never exceed (B, Hkv, g, S, chunk).  This is what
    makes 32k-token prefill (and unsharded-head archs) fit HBM — the full
    (S, T) score matrix is never materialised.

    Self-attention layout: q (B,S,H,hd), k/v (B,S,Hkv,hd), same positions.
    """
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    nc = S // chunk
    qr = q.reshape(B, S, Hkv, group, hd)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    kc = k.reshape(B, nc, chunk, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nc, chunk, Hkv, hd).transpose(1, 0, 2, 3, 4)
    qpos = jnp.arange(S, dtype=jnp.int32)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, c0 = xs
        kpos = c0 * chunk + jnp.arange(chunk, dtype=jnp.int32)
        logits = jnp.einsum("bskgd,btkd->bkgst", qr, kb).astype(jnp.float32)
        logits = logits * scale
        mask = kpos[None, :] <= qpos[:, None]
        if window > 0:
            mask &= kpos[None, :] > qpos[:, None] - window
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        # guard fully-masked rows (m_new == NEG_INF): keep weights at 0
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", p, vb.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, group, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, group, S), jnp.float32)
    a0 = jnp.zeros((B, Hkv, group, S, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kc, vc, jnp.arange(nc, dtype=jnp.int32))
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd).astype(q.dtype)


def gqa_attention(p, x, cfg: ArchConfig, ctx: MeshContext, *, window: int = 0,
                  positions=None):
    """Full-sequence causal attention.  x (B, S, d)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"]).astype(x.dtype)
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"]).astype(x.dtype)
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"]).astype(x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, ctx, ("batch", None, "act_model", None))
    if S % ATTN_CHUNK == 0 and S > ATTN_CHUNK:
        out = _attend_chunked(q, k, v, window=window)
    else:
        out = _attend(q, k, v, _causal_mask(S, S, window=window))
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"]).astype(x.dtype)
    return constrain(y, ctx, ("batch", None, None))


def gqa_init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype):
    Hkv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, max_len, Hkv, hd), dtype),
        "v": jnp.zeros((batch, max_len, Hkv, hd), dtype),
    }


def gqa_decode(p, x, cache, pos, cfg: ArchConfig, ctx: MeshContext, *,
               window: int = 0):
    """One-token decode.  x (B, 1, d); cache k/v (B, T, Hkv, hd); pos scalar
    int32 — the index of the new token.  Returns (y, cache)."""
    B = x.shape[0]
    T = cache["k"].shape[1]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"]).astype(x.dtype)
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"]).astype(x.dtype)
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"]).astype(x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    # windowed caches store key at pos % T (ring buffer); full caches at pos
    # (caches may be low-precision, e.g. fp8 — cast on write, upcast on read)
    cdt = cache["k"].dtype
    slot = jnp.mod(pos, T) if window > 0 else pos
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cdt), (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cdt), (0, slot, 0, 0))
    kpos = jnp.arange(T)
    if window > 0:
        # ring: entry j holds absolute position j + T*floor stuff; valid if
        # within the last ``window`` positions <= pos
        abs_pos = jnp.where(kpos <= slot, pos - slot + kpos,
                            pos - slot - T + kpos)
        mask = (abs_pos >= 0) & (abs_pos <= pos) & (abs_pos > pos - window)
    else:
        mask = kpos <= pos
    out = _attend(q, ck.astype(x.dtype), cv.astype(x.dtype),
                  mask[None, None, None, :])
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"]).astype(x.dtype)
    return y, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek-V2 / MiniCPM3)
# ---------------------------------------------------------------------------

def mla_specs(cfg: ArchConfig) -> dict:
    d, H = cfg.d_model, cfg.num_heads
    qn, qr, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ql, kl = cfg.q_lora_rank, cfg.kv_lora_rank
    specs = {
        "kv_down": ParamSpec((d, kl + qr), ("fsdp", "kv_lora")),
        "kv_norm": ParamSpec((kl,), ("kv_lora",), init="zeros"),
        "k_up": ParamSpec((kl, H, qn), ("kv_lora", "heads", "head_dim")),
        "v_up": ParamSpec((kl, H, vd), ("kv_lora", "heads", "head_dim")),
        "wo": ParamSpec((H, vd, d), ("heads", "head_dim", "fsdp")),
    }
    if ql > 0:
        specs["q_down"] = ParamSpec((d, ql), ("fsdp", "q_lora"))
        specs["q_norm"] = ParamSpec((ql,), ("q_lora",), init="zeros")
        specs["q_up"] = ParamSpec((ql, H, qn + qr), ("q_lora", "heads", "head_dim"))
    else:
        specs["q_proj"] = ParamSpec((d, H, qn + qr), ("fsdp", "heads", "head_dim"))
    return specs


def _mla_q(p, x, cfg: ArchConfig):
    if cfg.q_lora_rank > 0:
        cq = rms_norm(dense(x, p["q_down"]), p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsq,qhk->bshk", cq, p["q_up"]).astype(x.dtype)
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["q_proj"]).astype(x.dtype)
    return jnp.split(q, [cfg.qk_nope_dim], axis=-1)  # nope, rope


def _mla_kv_latent(p, x, cfg: ArchConfig):
    ckv_full = dense(x, p["kv_down"])                     # (B,S,kl+qr)
    ckv, k_rope = jnp.split(ckv_full, [cfg.kv_lora_rank], axis=-1)
    ckv = rms_norm(ckv, p["kv_norm"], cfg.norm_eps)
    return ckv, k_rope


def _mla_attend(p, q_nope, q_rope, ckv, k_rope, cfg: ArchConfig, mask):
    """q_* (B,S,H,*); ckv (B,T,kl); k_rope (B,T,qr) already roped."""
    k_nope = jnp.einsum("btc,chk->bthk", ckv, p["k_up"]).astype(q_nope.dtype)
    v = jnp.einsum("btc,chk->bthk", ckv, p["v_up"]).astype(q_nope.dtype)
    scale = 1.0 / jnp.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    logits = (
        jnp.einsum("bshk,bthk->bhst", q_nope, k_nope)
        + jnp.einsum("bshk,btk->bhst", q_rope, k_rope)
    ).astype(jnp.float32) * scale
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthk->bshk", probs, v)


def _mla_attend_chunked(p, q_nope, q_rope, ckv, k_rope, cfg: ArchConfig,
                        *, chunk: int = ATTN_CHUNK):
    """Flash-style MLA: expands each KV chunk from the latent on the fly —
    neither the (S, T) scores nor the full expanded K/V ever materialise."""
    B, S, H, _ = q_nope.shape
    nc = S // chunk
    scale = 1.0 / jnp.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    ckv_c = ckv.reshape(B, nc, chunk, -1).transpose(1, 0, 2, 3)
    kr_c = k_rope.reshape(B, nc, chunk, -1).transpose(1, 0, 2, 3)
    qpos = jnp.arange(S, dtype=jnp.int32)
    hd_v = cfg.v_head_dim

    def body(carry, xs):
        m, l, acc = carry
        ckv_b, kr_b, c0 = xs
        k_nope_b = jnp.einsum("btc,chk->bthk", ckv_b, p["k_up"]).astype(q_nope.dtype)
        v_b = jnp.einsum("btc,chk->bthk", ckv_b, p["v_up"]).astype(q_nope.dtype)
        logits = (
            jnp.einsum("bshk,bthk->bhst", q_nope, k_nope_b)
            + jnp.einsum("bshk,btk->bhst", q_rope, kr_b)
        ).astype(jnp.float32) * scale
        kpos = c0 * chunk + jnp.arange(chunk, dtype=jnp.int32)
        mask = kpos[None, :] <= qpos[:, None]
        logits = jnp.where(mask[None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        pw = jnp.exp(logits - m_new[..., None])
        pw = jnp.where(mask[None, None], pw, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + pw.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhst,bthk->bhsk", pw, v_b.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    a0 = jnp.zeros((B, H, S, hd_v), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (ckv_c, kr_c, jnp.arange(nc, dtype=jnp.int32))
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q_nope.dtype)  # (B,S,H,hd_v)


def mla_attention(p, x, cfg: ArchConfig, ctx: MeshContext, *, positions=None):
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    q_nope, q_rope = _mla_q(p, x, cfg)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    ckv, k_rope = _mla_kv_latent(p, x, cfg)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
    if S % ATTN_CHUNK == 0 and S > ATTN_CHUNK:
        out = _mla_attend_chunked(p, q_nope, q_rope, ckv, k_rope, cfg)
    else:
        mask = _causal_mask(S, S)
        out = _mla_attend(p, q_nope, q_rope, ckv, k_rope, cfg, mask)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"]).astype(x.dtype)
    return constrain(y, ctx, ("batch", None, None))


def mla_init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype):
    return {
        "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
    }


def mla_decode(p, x, cache, pos, cfg: ArchConfig, ctx: MeshContext):
    B = x.shape[0]
    cdt = cache["ckv"].dtype
    positions = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(p, x, cfg)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    ckv_new, k_rope_new = _mla_kv_latent(p, x, cfg)
    k_rope_new = apply_rope(k_rope_new, positions, cfg.rope_theta)
    ckv = jax.lax.dynamic_update_slice(
        cache["ckv"], ckv_new.astype(cdt), (0, pos, 0))
    k_rope = jax.lax.dynamic_update_slice(
        cache["k_rope"], k_rope_new.astype(cdt), (0, pos, 0))
    T = ckv.shape[1]
    mask = (jnp.arange(T) <= pos)[None, None, None, :]
    out = _mla_attend(p, q_nope, q_rope, ckv.astype(x.dtype),
                      k_rope.astype(x.dtype), cfg, mask)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"]).astype(x.dtype)
    return y, {"ckv": ckv, "k_rope": k_rope}


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_specs(cfg: ArchConfig, d_ff: int | None = None) -> dict:
    d = cfg.d_model
    f = cfg.d_ff if d_ff is None else d_ff
    if cfg.mlp == "swiglu":
        return {
            "w_gate": ParamSpec((d, f), ("fsdp", "mlp")),
            "w_up": ParamSpec((d, f), ("fsdp", "mlp")),
            "w_down": ParamSpec((f, d), ("mlp", "fsdp")),
        }
    return {  # relu2 / gelu: single up-proj
        "w_up": ParamSpec((d, f), ("fsdp", "mlp")),
        "w_down": ParamSpec((f, d), ("mlp", "fsdp")),
    }


def mlp(p, x, cfg: ArchConfig, ctx: MeshContext):
    if cfg.mlp == "swiglu":
        h = jax.nn.silu(dense(x, p["w_gate"])) * dense(x, p["w_up"])
    elif cfg.mlp == "relu2":
        h = jnp.square(jax.nn.relu(dense(x, p["w_up"])))
    else:
        h = jax.nn.gelu(dense(x, p["w_up"]))
    h = constrain(h, ctx, ("batch", None, "act_model"))
    return constrain(dense(h, p["w_down"]), ctx, ("batch", None, None))


# ---------------------------------------------------------------------------
# MoE: top-k routing, capacity drop, explicit EP/FSDP via shard_map
# ---------------------------------------------------------------------------
#
# Routing must stay LOCAL to each data shard (a pjit-level argsort over the
# sharded token dim would lower to a global sort).  So the routed part is a
# shard_map: tokens sharded over (pod, data) and replicated over 'model';
# expert weights sharded over 'model' (EP) and over 'data' on their d_model
# dim (FSDP, gathered per layer like ZeRO-3); each model rank serves its own
# experts and a single psum('model') combines — the same reduce a TP dense
# FFN pays, with zero all_to_all (DESIGN.md §6).

def moe_specs(cfg: ArchConfig) -> dict:
    d, E, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff or cfg.d_ff
    specs = {
        "router": ParamSpec((d, E), ("fsdp", None)),
        "w_gate": ParamSpec((E, d, f), ("experts", "fsdp", "expert_ff")),
        "w_up": ParamSpec((E, d, f), ("experts", "fsdp", "expert_ff")),
        "w_down": ParamSpec((E, f, d), ("experts", "expert_ff", "fsdp")),
    }
    if cfg.num_shared_experts > 0:
        shared_f = f * cfg.num_shared_experts
        specs["shared"] = mlp_specs(cfg.replace(mlp="swiglu"), shared_f)
    return specs


def _moe_local(xt, router, wg, wu, wd, *, cfg: ArchConfig, ctx: MeshContext,
               model_axis: str, ep_sharded: bool, fsdp_axes: tuple[str, ...],
               ff_axes: tuple[str, ...]):
    """shard_map body.  xt (Tl, d) local tokens; wg/wu (El, d_shard, f_shard);
    wd (El, f_shard, d_shard)."""
    E, k = cfg.num_experts, cfg.top_k
    Tl, d = xt.shape

    # ZeRO-3 gather of this layer's expert weights over the FSDP axes
    for ax in fsdp_axes:
        router = jax.lax.all_gather(router, ax, axis=0, tiled=True)
        wg = jax.lax.all_gather(wg, ax, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, ax, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, ax, axis=2, tiled=True)
    for ax in ff_axes:  # expert hidden dim sharded over pods
        wg = jax.lax.all_gather(wg, ax, axis=2, tiled=True)
        wu = jax.lax.all_gather(wu, ax, axis=2, tiled=True)
        wd = jax.lax.all_gather(wd, ax, axis=1, tiled=True)

    logits = jnp.einsum("td,de->te", xt, router).astype(jnp.float32)
    weights, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    weights = (weights / jnp.clip(weights.sum(-1, keepdims=True), 1e-9, None))

    flat_expert = experts.reshape(Tl * k)
    flat_token = (
        jnp.repeat(jnp.arange(Tl, dtype=jnp.int32)[:, None], k, axis=1)
        .reshape(Tl * k)
    )
    flat_weight = weights.reshape(Tl * k)
    order = jnp.argsort(flat_expert)
    e_sorted = flat_expert[order]
    t_sorted = flat_token[order]
    w_sorted = flat_weight[order]

    # my expert range ([0, E) when experts are replicated over the mesh)
    El = wg.shape[0]
    me = jax.lax.axis_index(model_axis) if (ep_sharded and model_axis) else 0
    my_experts = me * El + jnp.arange(El, dtype=jnp.int32)

    C = max(1, int(cfg.capacity_factor * Tl * k / E))
    starts = jnp.searchsorted(e_sorted, my_experts, side="left").astype(jnp.int32)
    ends = jnp.searchsorted(e_sorted, my_experts, side="right").astype(jnp.int32)
    counts = ends - starts
    take = starts[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]  # (El, C)
    valid = jnp.arange(C)[None, :] < jnp.minimum(counts, C)[:, None]
    take = jnp.clip(take, 0, Tl * k - 1)
    tok_idx = jnp.where(valid, t_sorted[take], 0)
    gate_w = jnp.where(valid, w_sorted[take], 0.0)

    xe = xt[tok_idx]                                           # (El, C, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) * jnp.einsum(
        "ecd,edf->ecf", xe, wu
    )
    ye = jnp.einsum("ecf,efd->ecd", h.astype(xt.dtype), wd)
    ye = ye * gate_w[..., None].astype(ye.dtype)
    ye = jnp.where(valid[..., None], ye, 0)

    y = jnp.zeros((Tl, d), xt.dtype).at[tok_idx.reshape(-1)].add(
        ye.reshape(El * C, d)
    )
    if ep_sharded and model_axis:
        y = jax.lax.psum(y, model_axis)
    return y


def moe_block(p, x, cfg: ArchConfig, ctx: MeshContext):
    B, S, d = x.shape
    bdp = ctx.batch_axes or None
    model_axis = ctx.model_axis or ""
    # shardings the spec system assigns to the expert weights
    wg_spec = ctx.spec_for(("experts", "fsdp", "expert_ff"), p["w_gate"].shape)
    espec, dspec, fspec = wg_spec[0], wg_spec[1], wg_spec[2]
    as_tuple = lambda s: (  # noqa: E731
        s if isinstance(s, tuple) else ((s,) if s else ())
    )
    fsdp_axes = as_tuple(dspec)
    ff_axes = as_tuple(fspec)

    body = functools.partial(
        _moe_local, cfg=cfg, ctx=ctx, model_axis=model_axis,
        ep_sharded=espec is not None, fsdp_axes=fsdp_axes, ff_axes=ff_axes,
    )
    xt = x.reshape(B * S, d)
    y = shard_map(
        body,
        mesh=ctx.mesh,
        in_specs=(
            P(bdp, None),                    # tokens
            P(dspec, None),                  # router (FSDP over data)
            P(espec, dspec, fspec),          # w_gate
            P(espec, dspec, fspec),          # w_up
            P(espec, fspec, dspec),          # w_down
        ),
        out_specs=P(bdp, None),
        check_vma=False,
    )(xt, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    y = constrain(y.reshape(B, S, d), ctx, ("batch", None, None))
    if cfg.num_shared_experts > 0:
        y = y + mlp(p["shared"], x, cfg.replace(mlp="swiglu"), ctx)
    return y
