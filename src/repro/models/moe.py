"""Mixture-of-Experts with capacity-based routing and expert parallelism.

Two execution paths with identical routing semantics:

* ``moe_local``  — single-shard: tokens are bucketed per expert and the
  expert FFNs run as one batched einsum [E, C, D] x [E, D, F].  Used by
  CPU smoke tests and as the oracle for the distributed path.

* ``moe_expert_parallel`` — experts sharded over the mesh "model" axis
  (E_loc = E / M per shard).  Per shard: route -> bucket by destination
  shard (capacity C) -> all_to_all -> bucket by local expert (capacity C2)
  -> batched expert einsum -> all_to_all back -> weighted combine into the
  original token slots.  Token order never leaves the source shard, so the
  return trip needs no metadata beyond the local expert id.

Capacity overflow drops tokens (standard capacity-factor routing); dropped
pairs simply contribute nothing to the combine.  Everything is static-
shaped and differentiable (scatter/gather + all_to_all transpose rules).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp


# ----------------------------------------------------------------- router
def router(x_flat: jnp.ndarray, w_router: jnp.ndarray, top_k: int, renorm: bool = True):
    """x_flat [T, D] -> (weights [T,k], expert_idx [T,k], aux_loss scalar)."""
    logits = (x_flat.astype(jnp.float32)) @ (w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # [T, E]
    w, idx = jax.lax.top_k(probs, top_k)
    if renorm:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * P_e
    e = probs.shape[-1]
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), axis=0
    )  # fraction routed (top-1 proxy)
    aux = e * jnp.sum(me * ce)
    return w.astype(x_flat.dtype), idx, aux


def _bucket_positions(dest: jnp.ndarray, n_buckets: int, capacity: int):
    """Rank of each element within its destination bucket.

    dest [P] int32 -> (pos [P], valid [P]).  Order-preserving (stable).
    """
    onehot = jax.nn.one_hot(dest, n_buckets, dtype=jnp.int32)  # [P, Nb]
    pos = (jnp.cumsum(onehot, axis=0) - 1)  # rank among same-dest
    pos = jnp.take_along_axis(pos, dest[:, None], axis=1)[:, 0]
    valid = pos < capacity
    return pos, valid


def _expert_ffn(buf: jnp.ndarray, wp: Dict[str, jnp.ndarray], act: str, glu: bool):
    """buf [E, C, D] -> [E, C, D] through per-expert (Sw)iGLU MLPs."""
    h = jnp.einsum("ecd,edf->ecf", buf, wp["w1"])
    a = jax.nn.silu(h) if act == "silu" else jax.nn.gelu(h)
    if glu:
        gate = jnp.einsum("ecd,edf->ecf", buf, wp["w3"])
        a = a * gate
    return jnp.einsum("ecf,efd->ecd", a, wp["w2"])


# ------------------------------------------------------------- local path
def moe_local(
    params: Dict[str, jnp.ndarray],
    x: jnp.ndarray,  # [B, S, D]
    *,
    top_k: int,
    capacity_factor: float = 1.25,
    act: str = "silu",
    glu: bool = True,
    renorm: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b, s, d = x.shape
    e = params["w1"].shape[0]
    xf = x.reshape(-1, d)
    t = xf.shape[0]
    w, idx, aux = router(xf, params["router"], top_k, renorm=renorm)

    pairs = t * top_k
    # capacity floor keeps tiny (decode-time) batches drop-free
    cap = min(pairs, max(8, -(-pairs * capacity_factor // e).__int__()))
    dest = idx.reshape(-1)  # [P]
    src = jnp.repeat(jnp.arange(t), top_k)
    pos, valid = _bucket_positions(dest, e, cap)

    buf = jnp.zeros((e, cap, d), x.dtype)
    buf = buf.at[dest, pos].set(
        jnp.where(valid[:, None], xf[src], 0.0), mode="drop"
    )
    out_buf = _expert_ffn(buf, params, act, glu)
    out_pairs = out_buf[dest, pos] * valid[:, None]  # [P, D]
    y = jnp.zeros_like(xf)
    y = y.at[src].add(out_pairs * w.reshape(-1)[:, None])
    return y.reshape(b, s, d), aux


# --------------------------------------------------- expert-parallel path
def moe_expert_parallel(
    params: Dict[str, jnp.ndarray],
    x: jnp.ndarray,  # LOCAL shard [B_loc, S, D]
    *,
    axis_name: str,
    top_k: int,
    capacity_factor: float = 1.25,
    act: str = "silu",
    glu: bool = True,
    renorm: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Runs INSIDE shard_map.  ``params['w1']`` etc. hold the LOCAL expert
    slice [E_loc, D, F]; the router weights are replicated [D, E].

    The input x arrives replicated over the model axis; each replica takes
    its 1/M contiguous token slice before routing (EXPERIMENTS.md §Perf H1:
    dispatching the full replicated set from every replica made each expert
    process M identical copies of every token — 16x buffer and compute
    waste at M=16), and the disjoint outputs are all-gathered at the end.
    """
    b, s, d = x.shape
    m = jax.lax.axis_size(axis_name)
    m_idx = jax.lax.axis_index(axis_name)
    e_loc = params["w1"].shape[0]
    e = e_loc * m
    x_all = x.reshape(-1, d)
    t_all = x_all.shape[0]
    t = -(-t_all // m)  # tokens per model replica (padded)
    pad = t * m - t_all
    if pad:
        x_all = jnp.pad(x_all, ((0, pad), (0, 0)))
    xf = jax.lax.dynamic_slice(x_all, (m_idx * t, 0), (t, d))
    w, idx, aux = router(xf, params["router"], top_k, renorm=renorm)
    aux = jax.lax.pmean(aux, axis_name)

    pairs = t * top_k
    # per-destination-shard capacity, floored for tiny decode batches
    cap = min(pairs, max(8, -(-pairs * capacity_factor // m).__int__()))
    dest_shard = idx.reshape(-1) // e_loc  # [P]
    eid_local = idx.reshape(-1) % e_loc
    src = jnp.repeat(jnp.arange(t), top_k)
    pos, valid = _bucket_positions(dest_shard, m, cap)

    send_x = jnp.zeros((m, cap, d), x.dtype)
    send_x = send_x.at[dest_shard, pos].set(
        jnp.where(valid[:, None], xf[src], 0.0), mode="drop"
    )
    send_eid = jnp.full((m, cap), -1, jnp.int32)
    send_eid = send_eid.at[dest_shard, pos].set(
        jnp.where(valid, eid_local, -1), mode="drop"
    )

    recv_x = jax.lax.all_to_all(send_x, axis_name, 0, 0, tiled=False)
    recv_eid = jax.lax.all_to_all(send_eid, axis_name, 0, 0, tiled=False)

    # local dispatch into expert buckets
    rx = recv_x.reshape(-1, d)  # [M*cap, D]
    re = recv_eid.reshape(-1)
    cap2 = min(m * cap, max(8, -(-m * cap * capacity_factor // e_loc).__int__()))
    re_safe = jnp.where(re >= 0, re, 0)
    pos2, valid2 = _bucket_positions(re_safe, e_loc, cap2)
    valid2 &= re >= 0

    buf = jnp.zeros((e_loc, cap2, d), x.dtype)
    buf = buf.at[re_safe, pos2].set(jnp.where(valid2[:, None], rx, 0.0), mode="drop")
    out_buf = _expert_ffn(buf, params, act, glu)
    out_rx = out_buf[re_safe, pos2] * valid2[:, None]  # [M*cap, D]

    back = jax.lax.all_to_all(
        out_rx.reshape(m, cap, d), axis_name, 0, 0, tiled=False
    )  # [M, cap, D] — returns along the send path
    out_pairs = back[dest_shard, pos] * valid[:, None]
    y_local = jnp.zeros_like(xf)
    y_local = y_local.at[src].add(out_pairs * w.reshape(-1)[:, None])
    # disjoint slices -> gather the full token set back on every replica
    y = jax.lax.all_gather(y_local, axis_name, axis=0, tiled=True)
    if pad:
        y = y[:t_all]
    return y.reshape(b, s, d), aux


def init_moe_params(rng, d_model, d_ff, n_experts, dtype, glu=True):
    k = jax.random.split(rng, 4)
    sc_in = d_model ** -0.5
    sc_out = d_ff ** -0.5
    p = {
        "router": jax.random.normal(k[0], (d_model, n_experts), jnp.float32) * sc_in,
        "w1": jax.random.normal(k[1], (n_experts, d_model, d_ff), dtype) * sc_in,
        "w2": jax.random.normal(k[2], (n_experts, d_ff, d_model), dtype) * sc_out,
    }
    if glu:
        p["w3"] = jax.random.normal(k[3], (n_experts, d_model, d_ff), dtype) * sc_in
    return p
