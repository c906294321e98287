"""Substrate tests: attention, SSM, MoE, optimizer, data, checkpointing."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.data import TokenStream, make_batch_iterator
from repro.checkpoint import load_checkpoint, restore_sharded, save_checkpoint
from repro.models.attention import blockwise_attention, decode_attention, rope
from repro.models.moe import init_moe_params, moe_expert_parallel, moe_local
from repro.models.ssm import ssd_decode_step, ssd_scan
from repro.optim import adamw_init, adamw_update, clip_by_global_norm, cosine_schedule

RNG = np.random.default_rng(7)


def _arr(shape, scale=1.0, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


# -------------------------------------------------------------- attention
def _dense_ref(q, k, v, pos, window=0, prefix=0):
    b, s, h, dh = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, s, kv, g, dh)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) * dh ** -0.5
    qq, kk = pos[:, None], pos[None, :]
    mask = kk <= qq
    if window:
        mask &= (qq - kk) < window
    if prefix:
        mask |= (qq < prefix) & (kk < prefix)
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, -1)
    return jnp.einsum("bkgqs,bskd->bqkgd", w, v).reshape(b, s, h, dh)


@given(
    st.integers(min_value=8, max_value=48),
    st.sampled_from([(4, 4), (6, 2), (8, 1)]),
    st.sampled_from([0, 8]),
    st.sampled_from([8, 16]),
)
@settings(max_examples=12, deadline=None)
def test_flash_attention_property(s, heads, window, chunk):
    h, kv = heads
    q, k, v = _arr((2, s, h, 16), 0.5), _arr((2, s, kv, 16), 0.5), _arr((2, s, kv, 16))
    pos = jnp.arange(s, dtype=jnp.int32)
    got = blockwise_attention(q, k, v, pos, pos, window=window, chunk=chunk)
    want = _dense_ref(q, k, v, pos, window=window)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_attention_grads_match_dense():
    s = 24
    q, k, v = _arr((1, s, 4, 8), 0.5), _arr((1, s, 2, 8), 0.5), _arr((1, s, 2, 8))
    pos = jnp.arange(s, dtype=jnp.int32)
    g1 = jax.grad(
        lambda q, k, v: (blockwise_attention(q, k, v, pos, pos, chunk=8) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    g2 = jax.grad(
        lambda q, k, v: (_dense_ref(q, k, v, pos) ** 2).sum(), argnums=(0, 1, 2)
    )(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)


def test_rope_preserves_norm_and_relative_phase():
    x = _arr((1, 16, 2, 8))
    pos = jnp.arange(16, dtype=jnp.int32)
    y = rope(x, pos, 10_000.0)
    np.testing.assert_allclose(
        jnp.linalg.norm(x, axis=-1), jnp.linalg.norm(y, axis=-1), rtol=1e-5
    )
    # relative property: <rope(q,i), rope(k,j)> depends only on i-j
    q = _arr((1, 1, 1, 8))
    k = _arr((1, 1, 1, 8))
    def dot_at(i, j):
        qi = rope(q, jnp.array([i], jnp.int32), 1e4)
        kj = rope(k, jnp.array([j], jnp.int32), 1e4)
        return float(jnp.sum(qi * kj))
    assert abs(dot_at(5, 3) - dot_at(7, 5)) < 1e-4


def test_decode_attention_ring_positions():
    """Ring-buffer (out-of-order) cache slots must give the same result as
    an in-order cache when per-slot positions are supplied."""
    b, s, kv, dh = 1, 8, 1, 8
    q = _arr((b, 4, dh), 0.5)
    k, v = _arr((b, s, kv, dh), 0.5), _arr((b, s, kv, dh))
    perm = np.asarray([3, 1, 0, 2, 7, 5, 4, 6])
    pos = jnp.asarray(np.argsort(perm), jnp.int32)[None]  # position of each slot
    out_inorder = decode_attention(q, k, v, length=8)
    out_ring = decode_attention(q, k[:, perm], v[:, perm], length=8,
                                positions=pos[:, perm][..., perm])
    # permute cache slots and supply positions; easier direct check:
    k2 = k[:, perm]
    v2 = v[:, perm]
    pos2 = jnp.asarray(perm, jnp.int32)[None]  # slot i holds position perm[i]
    out2 = decode_attention(q, k2, v2, length=8, positions=pos2)
    np.testing.assert_allclose(out_inorder, out2, rtol=1e-5, atol=1e-6)


# -------------------------------------------------------------------- ssm
def test_ssd_scan_matches_naive_and_decode():
    B, S, H, P, N = 2, 29, 2, 4, 3
    x = _arr((B, S, H, P))
    log_a = -jnp.abs(_arr((B, S, H))) * 0.3
    Bm, Cm = _arr((B, S, H, N), 0.4), _arr((B, S, H, N), 0.4)
    h = np.zeros((B, H, N, P))
    ys = []
    for t in range(S):
        a = np.exp(np.asarray(log_a[:, t]))[..., None, None]
        h = a * h + np.asarray(Bm[:, t])[..., None] * np.asarray(x[:, t])[:, :, None, :]
        ys.append(np.einsum("bhn,bhnp->bhp", np.asarray(Cm[:, t]), h))
    y_ref = np.stack(ys, 1)
    y, hf = ssd_scan(x, log_a, Bm, Cm, chunk=8)
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(hf, h, rtol=1e-4, atol=1e-5)
    # one more decode step continues the recurrence
    y1, h1 = ssd_decode_step(x[:, -1], log_a[:, -1], Bm[:, -1], Cm[:, -1], jnp.asarray(h))
    a = jnp.exp(log_a[:, -1])[..., None, None]
    h_want = a * h + Bm[:, -1][..., None] * x[:, -1][:, :, None, :]
    np.testing.assert_allclose(h1, h_want, rtol=1e-4, atol=1e-5)


@given(st.integers(min_value=4, max_value=64), st.sampled_from([4, 8, 16]))
@settings(max_examples=10, deadline=None)
def test_ssd_chunk_invariance(s, chunk):
    B, H, P, N = 1, 2, 4, 3
    x = _arr((B, s, H, P))
    log_a = -jnp.abs(_arr((B, s, H))) * 0.2
    Bm, Cm = _arr((B, s, H, N), 0.4), _arr((B, s, H, N), 0.4)
    y1, h1 = ssd_scan(x, log_a, Bm, Cm, chunk=chunk)
    y2, h2 = ssd_scan(x, log_a, Bm, Cm, chunk=s)
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(h1, h2, rtol=1e-4, atol=1e-5)


def test_ssd_grads_flow():
    B, S, H, P, N = 1, 16, 2, 4, 3
    x = _arr((B, S, H, P))
    log_a = -jnp.abs(_arr((B, S, H))) * 0.3
    Bm, Cm = _arr((B, S, H, N), 0.4), _arr((B, S, H, N), 0.4)
    g = jax.grad(lambda x: (ssd_scan(x, log_a, Bm, Cm, chunk=8)[0] ** 2).sum())(x)
    assert float(jnp.abs(g).sum()) > 0
    assert not bool(jnp.isnan(g).any())


# -------------------------------------------------------------------- moe
def test_moe_local_vs_expert_parallel_exact():
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh

    D, F, E, K = 16, 32, 4, 2
    params = init_moe_params(jax.random.PRNGKey(0), D, F, E, jnp.float32)
    x = _arr((2, 8, D))
    y1, aux1 = moe_local(params, x, top_k=K, capacity_factor=8.0)
    mesh = make_mesh((1,), ("model",))
    ep = jax.shard_map(
        lambda p, xx: moe_expert_parallel(
            p, xx, axis_name="model", top_k=K, capacity_factor=8.0
        ),
        mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False,
    )
    y2, aux2 = ep(params, x)
    np.testing.assert_allclose(y1, y2, rtol=1e-6)
    np.testing.assert_allclose(aux1, aux2, rtol=1e-6)


def test_moe_capacity_drops_tokens():
    """With tiny capacity, output norm shrinks (tokens dropped) but stays
    finite — the documented lossy semantics of capacity routing."""
    D, F, E, K = 8, 16, 4, 2
    params = init_moe_params(jax.random.PRNGKey(0), D, F, E, jnp.float32)
    x = _arr((4, 16, D))
    y_full, _ = moe_local(params, x, top_k=K, capacity_factor=16.0)
    y_tight, _ = moe_local(params, x, top_k=K, capacity_factor=0.25)
    assert float(jnp.linalg.norm(y_tight)) < float(jnp.linalg.norm(y_full))
    assert not bool(jnp.isnan(y_tight).any())


def test_moe_aux_loss_balanced_router_lower():
    """A uniform router yields a lower load-balance loss than a collapsed
    one (Switch aux-loss sanity)."""
    from repro.models.moe import router

    D, E = 8, 4
    x = _arr((64, D))
    w_uniform = jnp.zeros((D, E))
    _, _, aux_u = router(x, w_uniform, top_k=2)
    w_collapsed = jnp.zeros((D, E)).at[:, 0].set(10.0)
    _, _, aux_c = router(x, w_collapsed, top_k=2)
    assert float(aux_u) < float(aux_c)


# -------------------------------------------------------------- optimizer
def test_adamw_converges_on_quadratic():
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    opt = adamw_init(params)
    for i in range(300):
        grads = jax.grad(lambda p: ((p["w"] - target) ** 2).sum())(params)
        params, opt, _ = adamw_update(params, grads, opt, lr=5e-2, weight_decay=0.0)
    np.testing.assert_allclose(params["w"], target, atol=1e-2)


def test_clip_by_global_norm():
    g = {"a": jnp.full((10,), 100.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(np.sqrt(10) * 100)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0, rel=1e-5)


def test_cosine_schedule_shape():
    lrs = [float(cosine_schedule(jnp.int32(s), 1e-3, 10, 100)) for s in range(0, 101, 10)]
    assert lrs[0] == 0.0
    assert max(lrs) == pytest.approx(1e-3, rel=1e-5)
    assert lrs[-1] == pytest.approx(1e-4, rel=1e-2)  # min_frac floor


# ------------------------------------------------------------------- data
def test_token_stream_deterministic_and_learnable():
    s1 = next(iter(TokenStream(vocab_size=64, seq_len=32, batch_size=4, seed=3)))
    s2 = next(iter(TokenStream(vocab_size=64, seq_len=32, batch_size=4, seed=3)))
    np.testing.assert_array_equal(s1["tokens"], s2["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(s1["tokens"][:, 1:], s1["labels"][:, :-1])
    # follow-rule signal exists: majority of consecutive deltas constant
    toks = s1["tokens"]
    deltas = (toks[:, 1:] - toks[:, :-1]) % 64
    # per-sequence dominant step exists (the learnable signal)
    dominant = max(
        np.bincount(row).max() / row.size for row in deltas
    )
    assert dominant > 0.5


def test_batch_iterator_shapes():
    from repro.configs import get_config

    cfg = get_config("paligemma-3b").reduced()
    it = make_batch_iterator(cfg, batch_size=2, seq_len=16, prefetch=0)
    b = next(iter(it))
    assert b["tokens"].shape == (2, 16)
    assert b["patches"].shape == (2, cfg.n_patches, 1152)


# ------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip():
    tree = {
        "a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "nested": [{"b": jnp.ones((4,), jnp.bfloat16)}],
    }
    with tempfile.TemporaryDirectory() as d:
        path = save_checkpoint(d, 7, tree, metadata={"note": "x"})
        assert os.path.exists(os.path.join(d, "latest"))
        arrays, manifest = load_checkpoint(d)
        assert manifest["step"] == 7
        restored = restore_sharded(d, jax.eval_shape(lambda: tree))
        np.testing.assert_array_equal(restored["a"], tree["a"])
        assert restored["nested"][0]["b"].dtype == jnp.bfloat16


def test_checkpoint_shape_mismatch_raises():
    tree = {"a": jnp.ones((2, 3))}
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 0, tree)
        bad = {"a": jax.ShapeDtypeStruct((3, 2), jnp.float32)}
        with pytest.raises(ValueError):
            restore_sharded(d, bad)
