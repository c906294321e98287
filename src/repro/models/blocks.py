"""Transformer block variants for the assigned architecture families.

Block signature (uniform so layer stacks can be lax.scan'ed):

    block_apply(cfg, ctx, lp, x, cache, mode, layer_flags)
        -> (x, new_cache, aux)

``mode``: "train" (no cache), "prefill" (build cache), "decode" (one step
against the cache).  ``layer_flags`` carries per-layer scalars that vary
inside a scanned stack (e.g. hymba's per-layer attention window).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .attention import blockwise_attention, decode_attention, rope
from .config import ModelConfig
from .moe import init_moe_params, moe_expert_parallel, moe_local
from .ssm import (
    init_mamba_params,
    init_mlstm_params,
    init_slstm_params,
    mamba_mix,
    mlstm_mix,
    slstm_mix,
)


@dataclasses.dataclass(frozen=True)
class MeshCtx:
    """Distribution context threaded through the model."""

    mesh: Any = None
    batch_axes: Tuple[str, ...] = ()
    model_axis: str = "model"
    shard_batch: bool = True  # False when global batch isn't divisible

    @property
    def model_parallel(self) -> bool:
        return (
            self.mesh is not None
            and self.model_axis in self.mesh.axis_names
            and self.mesh.shape[self.model_axis] > 1
        )

    def batch_spec(self):
        return tuple(self.batch_axes) if (self.batch_axes and self.shard_batch) else None


# ------------------------------------------------------------------ norms
def init_norm(d: int, kind: str, dtype) -> Dict[str, jnp.ndarray]:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layer":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def norm_apply(p, x, kind: str, eps: float):
    # statistics in f32 (fused reductions — no materialized f32 copy of x;
    # a full upcast of x was observed to make XLA hoist an f32 convert of
    # the entire saved layer-carry stack out of the backward scan), then
    # normalize in the input dtype.
    if kind == "rms":
        ms = jnp.mean(
            x.astype(jnp.float32) * x.astype(jnp.float32), -1, keepdims=True
        )
        y = x * jax.lax.rsqrt(ms + eps).astype(x.dtype)
    else:
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean(xf * xf, -1, keepdims=True) - mu * mu
        y = (x - mu.astype(x.dtype)) * jax.lax.rsqrt(var + eps).astype(x.dtype)
    y = y * p["scale"].astype(x.dtype)
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


# -------------------------------------------------------------------- ffn
def init_ffn(rng, d: int, f: int, cfg: ModelConfig, dtype):
    k = jax.random.split(rng, 3)
    p = {
        "w1": jax.random.normal(k[0], (d, f), dtype) * d ** -0.5,
        "w2": jax.random.normal(k[1], (f, d), dtype) * f ** -0.5,
    }
    if cfg.glu:
        p["w3"] = jax.random.normal(k[2], (d, f), dtype) * d ** -0.5
    if cfg.use_bias:
        p["b1"] = jnp.zeros((f,), dtype)
        p["b2"] = jnp.zeros((d,), dtype)
    return p


def ffn_apply(p, x, cfg: ModelConfig):
    h = x @ p["w1"]
    if "b1" in p:
        h = h + p["b1"]
    a = jax.nn.silu(h) if cfg.act == "silu" else jax.nn.gelu(h)
    if cfg.glu:
        a = a * (x @ p["w3"])
    y = a @ p["w2"]
    if "b2" in p:
        y = y + p["b2"]
    return y


# -------------------------------------------------------------- attention
def init_attention(rng, cfg: ModelConfig, dtype):
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    k = jax.random.split(rng, 4)
    sc = d ** -0.5
    p = {
        "wq": jax.random.normal(k[0], (d, h, dh), dtype) * sc,
        "wk": jax.random.normal(k[1], (d, kv, dh), dtype) * sc,
        "wv": jax.random.normal(k[2], (d, kv, dh), dtype) * sc,
        "wo": jax.random.normal(k[3], (h, dh, d), dtype) * (h * dh) ** -0.5,
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros((h, dh), dtype)
        p["bk"] = jnp.zeros((kv, dh), dtype)
        p["bv"] = jnp.zeros((kv, dh), dtype)
        p["bo"] = jnp.zeros((d,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype)
        p["k_norm"] = jnp.ones((dh,), dtype)
    return p


def _rmsn(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _quantize_kv(x):
    """[B, S, Hkv, dh] -> (int8 values, [B, S, Hkv] f32 scales)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _ring_write(cache, k, v, positions):
    """Write S new (k, v) at slots positions %% W; update slot->position map.

    cache: {"k","v","pos"[,"k_scale","v_scale"]}; k/v: [B, S, Hkv, dh];
    positions: [S] int32.  int8 caches quantize per token-per-head
    (beyond-paper: halves cache bytes/bandwidth for decode).

    Decode (S == 1) uses dynamic_update_slice — the SPMD partitioner
    handles dus on the sharded seq dim in place, whereas the scatter path
    triggered full-cache f32 copies (EXPERIMENTS §Perf H3).
    """
    w = cache["k"].shape[1]
    slots = positions % w
    new = dict(cache)
    quant = "k_scale" in cache
    if quant:
        k, ks = _quantize_kv(k)
        v, vs = _quantize_kv(v)
    if k.shape[1] == 1:
        slot = slots[0]
        dus = jax.lax.dynamic_update_slice
        new["k"] = dus(cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
        new["v"] = dus(cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
        new["pos"] = dus(cache["pos"], positions, (slot,))
        if quant:
            new["k_scale"] = dus(cache["k_scale"], ks, (0, slot, 0))
            new["v_scale"] = dus(cache["v_scale"], vs, (0, slot, 0))
        return new
    new["k"] = cache["k"].at[:, slots].set(k.astype(cache["k"].dtype))
    new["v"] = cache["v"].at[:, slots].set(v.astype(cache["v"].dtype))
    new["pos"] = cache["pos"].at[slots].set(positions)
    if quant:
        new["k_scale"] = cache["k_scale"].at[:, slots].set(ks)
        new["v_scale"] = cache["v_scale"].at[:, slots].set(vs)
    return new


def _seqsharded_decode(ctx: MeshCtx, q, ck, cv, cpos, length, window,
                       k_scale=None, v_scale=None):
    """Flash-decoding combine over a sequence-sharded cache (shard_map over
    the model axis): each shard attends over its local cache slice, then
    (m, l, acc) partials are combined with pmax/psum.  int8 caches are
    dequantized per-shard-slice (transient, never the full stack)."""
    dp = ctx.batch_spec()
    ax = ctx.model_axis
    quant = k_scale is not None

    def local(q, ck, cv, cpos, ks, vs):
        b, s, hkv, dh = ck.shape
        h = q.shape[1]
        g = h // hkv
        if quant:
            ck = ck.astype(q.dtype) * ks[..., None].astype(q.dtype)
            cv = cv.astype(q.dtype) * vs[..., None].astype(q.dtype)
        qg = q.reshape(b, hkv, g, dh)
        # operands stay in the cache dtype with f32 ACCUMULATION — an
        # .astype(f32) on ck/cv here gets hoisted out of the layer scan and
        # materializes the whole [L, ...] cache stack in f32 (measured 2x
        # 4.3 GB/chip on command-r decode; EXPERIMENTS §Perf H3)
        logits = jnp.einsum(
            "bkgd,bskd->bkgs", qg, ck, preferred_element_type=jnp.float32
        ) * (dh ** -0.5)
        valid = (cpos >= 0) & (cpos < length)
        valid &= jnp.where(window > 0, cpos >= (length - window), True)
        logits = jnp.where(valid[None, None, None, :], logits, -1e30)
        m_loc = logits.max(-1)
        m_g = jax.lax.pmax(m_loc, ax)
        p = jnp.exp(logits - m_g[..., None])
        l = jax.lax.psum(p.sum(-1), ax)
        acc = jax.lax.psum(
            jnp.einsum(
                "bkgs,bskd->bkgd", p.astype(cv.dtype), cv,
                preferred_element_type=jnp.float32,
            ),
            ax,
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(b, h, dh).astype(q.dtype)

    if not quant:
        zeros = jnp.zeros((), jnp.float32)
        k_scale = v_scale = jnp.zeros_like(cpos, jnp.float32)  # unused dummies
        scale_spec = P(ax)
    else:
        scale_spec = P(dp, ax, None)
    return jax.shard_map(
        local,
        mesh=ctx.mesh,
        in_specs=(
            P(dp, None, None), P(dp, ax, None, None), P(dp, ax, None, None),
            P(ax), scale_spec, scale_spec,
        ),
        out_specs=P(dp, None, None),
        check_vma=False,
    )(q, ck, cv, cpos, k_scale, v_scale)


def attention_sublayer(cfg: ModelConfig, ctx, p, x, cache, mode, positions, window, prefix):
    b, s, d = x.shape
    dh = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
    if cfg.use_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = _rmsn(q, p["q_norm"], cfg.norm_eps)
        k = _rmsn(k, p["k_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    new_cache = cache
    if mode == "train":
        y = blockwise_attention(
            q, k, v, positions, positions, window=window, prefix=prefix,
            chunk=cfg.attn_chunk,
        )
    elif mode == "prefill":
        y = blockwise_attention(
            q, k, v, positions, positions, window=window, prefix=prefix,
            chunk=cfg.attn_chunk,
        )
        new_cache = _ring_write(cache, k, v, positions)
    else:  # decode: s == 1
        length = positions[0] + 1  # positions[0] is the new token's position
        new_cache = _ring_write(cache, k, v, positions)
        ck, cv, cp = new_cache["k"], new_cache["v"], new_cache["pos"]
        quant = "k_scale" in new_cache
        q1 = q[:, 0]
        if ctx is not None and ctx.model_parallel:
            y = _seqsharded_decode(
                ctx, q1, ck, cv, cp, length, window,
                k_scale=new_cache.get("k_scale"),
                v_scale=new_cache.get("v_scale"),
            )
        else:
            if quant:
                ck = ck.astype(q1.dtype) * new_cache["k_scale"][..., None].astype(q1.dtype)
                cv = cv.astype(q1.dtype) * new_cache["v_scale"][..., None].astype(q1.dtype)
            y = decode_attention(
                q1, ck, cv, length, window=window,
                positions=jnp.broadcast_to(cp[None], (b, ck.shape[1])),
            )
        y = y[:, None]
    out = jnp.einsum("bshe,hed->bsd", y, p["wo"])
    if cfg.use_bias:
        out = out + p["bo"]
    return out, new_cache


# ------------------------------------------------------------- moe wrapper
def moe_apply(cfg: ModelConfig, ctx, p, x):
    if ctx is not None and ctx.model_parallel:
        dp = ctx.batch_spec()
        ax = ctx.model_axis

        def f(pp, xx):
            return moe_expert_parallel(
                pp, xx, axis_name=ax, top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor, act=cfg.act, glu=cfg.glu,
                renorm=cfg.renorm_topk,
            )

        especs = jax.tree.map(lambda _: P(ax), p)
        especs["router"] = P()
        return jax.shard_map(
            f, mesh=ctx.mesh,
            in_specs=(especs, P(dp, None, None)),
            out_specs=(P(dp, None, None), P()),
            check_vma=False,
        )(p, x)
    return moe_local(
        p, x, top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
        act=cfg.act, glu=cfg.glu, renorm=cfg.renorm_topk,
    )


# ------------------------------------------------------------ block bodies
def init_dense_block(rng, cfg: ModelConfig, dtype, moe: bool):
    k = jax.random.split(rng, 5)
    dt = dtype
    p = {
        "ln1": init_norm(cfg.d_model, cfg.norm, dt),
        "attn": init_attention(k[0], cfg, dt),
    }
    if not cfg.parallel_residual:
        p["ln2"] = init_norm(cfg.d_model, cfg.norm, dt)
    if moe:
        p["moe"] = init_moe_params(k[1], cfg.d_model, cfg.d_ff, cfg.n_experts, dt, cfg.glu)
        if cfg.n_shared_experts:
            p["shared"] = init_ffn(k[2], cfg.d_model, cfg.d_ff * cfg.n_shared_experts, cfg, dt)
    else:
        p["ffn"] = init_ffn(k[3], cfg.d_model, cfg.d_ff, cfg, dt)
    return p


def dense_block_apply(cfg, ctx, p, x, cache, mode, positions, flags):
    window = flags.get("window", cfg.sliding_window)
    prefix = flags.get("prefix", 0)
    aux = jnp.float32(0.0)
    h = norm_apply(p["ln1"], x, cfg.norm, cfg.norm_eps)
    attn_out, cache = attention_sublayer(
        cfg, ctx, p["attn"], h, cache, mode, positions, window, prefix
    )
    if cfg.parallel_residual:
        if "moe" in p:
            m_out, aux = moe_apply(cfg, ctx, p["moe"], h)
            if "shared" in p:
                m_out = m_out + ffn_apply(p["shared"], h, cfg)
        else:
            m_out = ffn_apply(p["ffn"], h, cfg)
        x = x + attn_out + m_out
    else:
        x = x + attn_out
        h2 = norm_apply(p["ln2"], x, cfg.norm, cfg.norm_eps)
        if "moe" in p:
            m_out, aux = moe_apply(cfg, ctx, p["moe"], h2)
            if "shared" in p:
                m_out = m_out + ffn_apply(p["shared"], h2, cfg)
        else:
            m_out = ffn_apply(p["ffn"], h2, cfg)
        x = x + m_out
    return x, cache, aux


def init_hymba_block(rng, cfg: ModelConfig, dtype):
    k = jax.random.split(rng, 4)
    return {
        "ln1": init_norm(cfg.d_model, cfg.norm, dtype),
        "attn": init_attention(k[0], cfg, dtype),
        "mamba": init_mamba_params(
            k[1], cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.conv_kernel, dtype
        ),
        "ln2": init_norm(cfg.d_model, cfg.norm, dtype),
        "ffn": init_ffn(k[2], cfg.d_model, cfg.d_ff, cfg, dtype),
        # per-path output norms (hymba fuses the two heads' outputs)
        "attn_out_norm": init_norm(cfg.d_model, "rms", dtype),
        "mamba_out_norm": init_norm(cfg.d_model, "rms", dtype),
    }


def hymba_block_apply(cfg, ctx, p, x, cache, mode, positions, flags):
    """Hymba: attention heads and mamba heads run in PARALLEL on the same
    normed input; their normed outputs are averaged [arXiv:2411.13676]."""
    window = flags.get("window", cfg.sliding_window)
    h = norm_apply(p["ln1"], x, cfg.norm, cfg.norm_eps)
    attn_cache = cache["attn"] if cache is not None else None
    ssm_state = cache["ssm"] if cache is not None else None
    attn_out, attn_cache = attention_sublayer(
        cfg, ctx, p["attn"], h, attn_cache, mode, positions, window, 0
    )
    m_out, ssm_state_new = mamba_mix(
        p["mamba"], h, cfg, state=ssm_state, decode=(mode == "decode")
    )
    fused = 0.5 * (
        norm_apply(p["attn_out_norm"], attn_out, "rms", cfg.norm_eps)
        + norm_apply(p["mamba_out_norm"], m_out, "rms", cfg.norm_eps)
    )
    x = x + fused
    h2 = norm_apply(p["ln2"], x, cfg.norm, cfg.norm_eps)
    x = x + ffn_apply(p["ffn"], h2, cfg)
    new_cache = None
    if cache is not None:
        new_cache = {"attn": attn_cache, "ssm": ssm_state_new}
    elif mode != "train":
        new_cache = {"attn": attn_cache, "ssm": ssm_state_new}
    return x, new_cache, jnp.float32(0.0)


def init_xlstm_block(rng, cfg: ModelConfig, dtype, kind: str):
    k = jax.random.split(rng, 2)
    p = {"ln1": init_norm(cfg.d_model, cfg.norm, dtype)}
    if kind == "mlstm":
        p["mix"] = init_mlstm_params(k[0], cfg.d_model, cfg.n_heads, dtype)
    else:
        p["mix"] = init_slstm_params(k[0], cfg.d_model, cfg.n_heads, dtype)
    if cfg.d_ff:
        p["ln2"] = init_norm(cfg.d_model, cfg.norm, dtype)
        p["ffn"] = init_ffn(k[1], cfg.d_model, cfg.d_ff, cfg, dtype)
    return p


def xlstm_block_apply(cfg, ctx, p, x, cache, mode, positions, flags, kind: str):
    h = norm_apply(p["ln1"], x, cfg.norm, cfg.norm_eps)
    mix = mlstm_mix if kind == "mlstm" else slstm_mix
    y, new_state = mix(p["mix"], h, cfg, state=cache, decode=(mode == "decode"))
    x = x + y
    if "ffn" in p:
        h2 = norm_apply(p["ln2"], x, cfg.norm, cfg.norm_eps)
        x = x + ffn_apply(p["ffn"], h2, cfg)
    return x, new_state, jnp.float32(0.0)
