"""Shared transformer layers: norms, RoPE, GQA attention (flash-style
chunked for long sequences), gated MLPs.

Everything is plain functional JAX over param dicts, designed to be
scanned over stacked layer params and partitioned by GSPMD from the rules
in ``repro.parallel.sharding``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


__all__ = [
    "rms_norm",
    "rope",
    "attention_train",
    "attention_decode",
    "mlp_gated",
    "init_attn",
    "init_mlp",
]

# flash-attention block sizes (pure-JAX chunked attention; on a real TPU a
# splash/pallas kernel would slot in here — the math is identical)
Q_BLOCK = 2048
KV_BLOCK = 1024


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dtype)


def _rope_angles(positions: jax.Array, head_dim: int, theta: float) -> Tuple:
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
    return jnp.cos(ang), jnp.sin(ang)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Apply rotary embeddings. x: (..., S, H, D); positions: (..., S)."""
    half = x.shape[-1] // 2
    cos, sin = _rope_angles(positions, x.shape[-1], theta)
    cos = cos[..., None, :]  # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def init_attn(key, cfg) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    scale = d ** -0.5
    p = {
        "wq": jax.random.normal(ks[0], (d, h, hd), jnp.float32) * scale,
        "wk": jax.random.normal(ks[1], (d, kv, hd), jnp.float32) * scale,
        "wv": jax.random.normal(ks[2], (d, kv, hd), jnp.float32) * scale,
        "wo": jax.random.normal(ks[3], (h, hd, d), jnp.float32) * (h * hd) ** -0.5,
        "norm": jnp.zeros((d,), jnp.float32),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), jnp.float32)
        p["bk"] = jnp.zeros((kv, hd), jnp.float32)
        p["bv"] = jnp.zeros((kv, hd), jnp.float32)
    return p


def _project_qkv(p, x, cfg, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _flash_body(q_blk, k, v, q_pos, kv_pos, window, scale, groups, unroll=False):
    """Attend one query block against all KV blocks with running softmax.

    q_blk: (B, Qb, H, D); k/v: (B, S, KV, D). Returns (B, Qb, H, D).
    Chunked over KV with f32 running (max, denom, acc) — the flash
    recurrence — so the (S × S) score matrix is never materialized.
    """
    b, qb, h, hd = q_blk.shape
    s = k.shape[1]
    n_kv = -(-s // KV_BLOCK)
    s_pad = n_kv * KV_BLOCK
    if s_pad > s:
        k = jnp.pad(k, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
        # padded slots get position +inf so the causal test (q_pos >= kv_pos)
        # masks them for every real query
        kv_pos = jnp.pad(kv_pos, (0, s_pad - s), constant_values=10**9)
    k = k.reshape(b, n_kv, KV_BLOCK, k.shape[2], hd)
    v = v.reshape(b, n_kv, KV_BLOCK, v.shape[2], hd)
    kv_pos = kv_pos.reshape(n_kv, KV_BLOCK)

    def step(carry, inp):
        m_i, l_i, acc = carry
        k_c, v_c, pos_c = inp  # (B, C, KV, D), (C,)
        k_c = jnp.repeat(k_c, groups, axis=2)  # GQA: expand kv heads
        v_c = jnp.repeat(v_c, groups, axis=2)
        scores = jnp.einsum("bqhd,bchd->bhqc", q_blk, k_c).astype(jnp.float32)
        scores = scores * scale
        causal = q_pos[:, None] >= pos_c[None, :]          # (Qb, C)
        if window is not None:
            causal &= (q_pos[:, None] - pos_c[None, :]) < window
        scores = jnp.where(causal[None, None], scores, -1e30)
        m_new = jnp.maximum(m_i, scores.max(axis=-1))       # (B,H,Qb)
        p = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(m_i - m_new)
        l_new = l_i * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqc,bchd->bhqd", p.astype(v_c.dtype), v_c
        ).astype(jnp.float32)
        return (m_new, l_new, acc), None

    # remat the KV step: flash backward recomputes the (B,H,Qb,C) score/
    # probability blocks rather than saving S²-worth of them — this IS the
    # flash-attention memory property on the backward pass.
    step = jax.checkpoint(step)

    m0 = jnp.full((b, h, qb), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, qb), jnp.float32)
    acc0 = jnp.zeros((b, h, qb, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step,
        (m0, l0, acc0),
        (k.swapaxes(0, 1), v.swapaxes(0, 1), kv_pos),
        unroll=unroll,
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.swapaxes(1, 2).astype(q_blk.dtype)  # (B, Qb, H, D)


def attention_train(
    p: dict,
    x: jax.Array,
    cfg,
    *,
    window: Optional[int] = None,
    return_kv: bool = False,
    unroll: bool = False,
):
    """Causal (optionally sliding-window) self-attention, flash-chunked.

    x: (B, S, D) → (B, S, D). Never materializes S×S scores; used for both
    train and prefill. With ``return_kv`` also returns the roped (k, v)
    (B, S, KV, D) for prefill cache construction.
    """
    b, s, d = x.shape
    positions = jnp.arange(s)
    q, k, v = _project_qkv(p, x, cfg, positions[None, :])
    groups = cfg.num_heads // cfg.num_kv_heads
    scale = cfg.head_dim ** -0.5

    n_q = -(-s // Q_BLOCK)
    s_pad = n_q * Q_BLOCK
    if s_pad > s:
        q = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
    qb = q.reshape(b, n_q, s_pad // n_q, cfg.num_heads, cfg.head_dim)
    q_pos = jnp.arange(s_pad).reshape(n_q, -1)

    def q_step(_, inp):
        q_c, pos_c = inp
        out = _flash_body(q_c, k, v, pos_c, positions, window, scale, groups,
                          unroll=unroll)
        return None, out

    _, outs = jax.lax.scan(q_step, None, (qb.swapaxes(0, 1), q_pos),
                           unroll=unroll)
    out = outs.swapaxes(0, 1).reshape(b, s_pad, cfg.num_heads, cfg.head_dim)
    out = out[:, :s]
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    if return_kv:
        return out, (k, v)
    return out


def attention_decode(
    p: dict,
    x: jax.Array,
    cfg,
    cache_k: jax.Array,
    cache_v: jax.Array,
    pos: jax.Array,
    *,
    window: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step with a (ring-buffered when windowed) KV cache.

    x: (B, 1, D); cache_k/v: (B, S_cache, KV, D) — stores *roped* keys at
    absolute slot ``pos % S_cache``; pos: (B,) absolute positions.
    Returns (out (B,1,D), new_cache_k, new_cache_v).
    """
    b, _, d = x.shape
    s_cache = cache_k.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = rope(q, pos[:, None], cfg.rope_theta)
    k = rope(k, pos[:, None], cfg.rope_theta)

    slot = (pos % s_cache).astype(jnp.int32)
    cache_k = cache_k.at[jnp.arange(b), slot].set(k[:, 0])
    cache_v = cache_v.at[jnp.arange(b), slot].set(v[:, 0])

    groups = cfg.num_heads // cfg.num_kv_heads
    # grouped-query einsum — no materialized repeat of the KV cache
    b_, s_, _, hd_ = q.shape
    qg = q.reshape(b_, s_, cfg.num_kv_heads, groups, hd_)
    scores = jnp.einsum("bskgd,bckd->bkgsc", qg, cache_k).astype(jnp.float32)
    scores = scores.reshape(b_, cfg.num_heads, s_, -1)
    scores = scores * (cfg.head_dim ** -0.5)

    # validity: slot c holds absolute position; with a ring buffer the
    # absolute position of slot c is recoverable from (pos, window).
    slots = jnp.arange(s_cache)[None, :]                    # (1, S_cache)
    if window is None:
        # absolute-indexed full cache: slot index == position
        valid = slots <= pos[:, None]
    elif isinstance(window, int) and window == s_cache:
        # ring buffer (cache size == window): every slot written within the
        # last s_cache steps is valid once wrapped; before that, slots ≤ pos.
        valid = slots <= pos[:, None]
        wrapped = pos[:, None] >= s_cache
        valid = jnp.where(wrapped, jnp.ones_like(valid, dtype=bool), valid)
    else:
        # absolute-indexed full cache with a (possibly traced) window:
        # slot == position, mask by causal validity AND distance < window.
        valid = (slots <= pos[:, None]) & ((pos[:, None] - slots) < window)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(cache_v.dtype)
    wg = w.reshape(b_, cfg.num_kv_heads, groups, s_, -1)
    out = jnp.einsum("bkgsc,bckd->bskgd", wg, cache_v)
    out = out.reshape(b_, s_, cfg.num_heads, hd_)
    return (
        jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype)),
        cache_k,
        cache_v,
    )


# ---------------------------------------------------------------------------
# gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int) -> dict:
    ks = jax.random.split(key, 3)
    return {
        "wg": jax.random.normal(ks[0], (d_model, d_ff), jnp.float32) * d_model**-0.5,
        "wu": jax.random.normal(ks[1], (d_model, d_ff), jnp.float32) * d_model**-0.5,
        "wd": jax.random.normal(ks[2], (d_ff, d_model), jnp.float32) * d_ff**-0.5,
        "norm": jnp.zeros((d_model,), jnp.float32),
    }


def mlp_gated(p: dict, x: jax.Array, activation: str = "swiglu") -> jax.Array:
    g = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, p["wu"].astype(x.dtype))
    act = jax.nn.silu if activation == "swiglu" else functools.partial(
        jax.nn.gelu, approximate=True
    )
    h = act(g) * u
    return jnp.einsum("bsf,fd->bsd", h, p["wd"].astype(x.dtype))


# ---------------------------------------------------------------------------
# sequence-parallel decode attention (shard_map flash-decode) — §Perf lever
# ---------------------------------------------------------------------------


def attention_decode_sp(
    p: dict,
    x: jax.Array,
    cfg,
    cache_k: jax.Array,
    cache_v: jax.Array,
    pos: jax.Array,
    mesh,
    *,
    window=None,
    seq_axis: str = "model",
    batch_axes=("data",),
):
    """Decode attention with the KV cache **sequence-sharded over the model
    axis**, computed under shard_map.

    Replaces the GSPMD-auto path for decode, which (a) triggers
    "involuntary full rematerialization" on the cache scatter (the written
    slot lives on one seq shard) and (b) all-gathers cache slices for the
    attention einsum. Here:

      * the new (roped) K/V are written **locally** by the one shard that
        owns slot ``pos % S`` (predicated set — no collective);
      * each shard attends over its local slice and the partial softmax
        stats are combined with tiny ``pmax``/``psum`` collectives
        ((B,H,1)+(B,H,D) floats instead of MB-scale gathers) — the
        flash-decode combine.

    Returns (out (B,1,D), new_cache_k, new_cache_v) like attention_decode.
    """
    b = x.shape[0]
    s_cache = cache_k.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = rope(q, pos[:, None], cfg.rope_theta)
    k = rope(k, pos[:, None], cfg.rope_theta)

    n_seq = mesh.shape[seq_axis]
    chunk = s_cache // n_seq
    groups = cfg.num_heads // cfg.num_kv_heads
    scale = cfg.head_dim ** -0.5
    b_ax = tuple(a for a in batch_axes if a in mesh.shape and b % mesh.shape[a] == 0) or None

    from jax.sharding import PartitionSpec as P

    def local(q_l, k_new, v_new, ck_l, cv_l, pos_l, window_l):
        j = jax.lax.axis_index(seq_axis)
        bl = q_l.shape[0]
        slot = (pos_l % s_cache).astype(jnp.int32)
        slot_loc = slot - j * chunk
        mine = (slot_loc >= 0) & (slot_loc < chunk)
        idx = jnp.clip(slot_loc, 0, chunk - 1)
        rows = jnp.arange(bl)
        old_k = ck_l[rows, idx]
        old_v = cv_l[rows, idx]
        ck_l = ck_l.at[rows, idx].set(
            jnp.where(mine[:, None, None], k_new[:, 0], old_k))
        cv_l = cv_l.at[rows, idx].set(
            jnp.where(mine[:, None, None], v_new[:, 0], old_v))

        # local attention over this shard's slice (absolute slot indices);
        # grouped-query einsum — no materialized repeat of the KV slice
        slots_abs = j * chunk + jnp.arange(chunk)                # (chunk,)
        b2, s2, _, hd2 = q_l.shape
        qg = q_l.reshape(b2, s2, cfg.num_kv_heads, groups, hd2)
        scores = jnp.einsum("bskgd,bckd->bkgsc", qg, ck_l).astype(jnp.float32)
        scores = scores.reshape(b2, cfg.num_heads, s2, -1)
        scores = scores * scale
        valid = slots_abs[None, :] <= pos_l[:, None]
        if window_l is not None:
            valid &= (pos_l[:, None] - slots_abs[None, :]) < window_l
        scores = jnp.where(valid[:, None, None, :], scores, -1e30)

        m_l = scores.max(-1)                                      # (B,H,1)
        m_g = jax.lax.pmax(m_l, seq_axis)
        p_l = jnp.exp(scores - m_g[..., None])
        l_g = jax.lax.psum(p_l.sum(-1), seq_axis)                 # (B,H,1)
        pg = p_l.astype(cv_l.dtype).reshape(b2, cfg.num_kv_heads, groups, s2, -1)
        acc = jnp.einsum("bkgsc,bckd->bskgd", pg, cv_l)
        acc = acc.reshape(b2, s2, cfg.num_heads, hd2)
        acc = jax.lax.psum(acc.astype(jnp.float32), seq_axis)
        out = (acc / jnp.maximum(l_g, 1e-30).swapaxes(1, 2)[..., None]).astype(q_l.dtype)
        return out, ck_l, cv_l

    win_arg = None if window is None else jnp.asarray(window, jnp.int32)
    in_specs = (
        P(b_ax, None, None, None),   # q
        P(b_ax, None, None, None),   # k_new
        P(b_ax, None, None, None),   # v_new
        P(b_ax, seq_axis, None, None),
        P(b_ax, seq_axis, None, None),
        P(b_ax),
    ) + ((P(),) if win_arg is not None else ())
    out_specs = (
        P(b_ax, None, None, None),
        P(b_ax, seq_axis, None, None),
        P(b_ax, seq_axis, None, None),
    )
    args = (q, k, v, cache_k, cache_v, pos)
    if win_arg is not None:
        fn = lambda q_l, kn, vn, ck, cv, pl, wl: local(q_l, kn, vn, ck, cv, pl, wl)
        args = args + (win_arg,)
    else:
        fn = lambda q_l, kn, vn, ck, cv, pl: local(q_l, kn, vn, ck, cv, pl, None)
    out, ck, cv = jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )(*args)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, ck, cv


# ---------------------------------------------------------------------------
# context-parallel attention (shard_map, q-sequence over 'model') — for archs
# whose head counts do not divide the model axis (hymba: 25 q / 5 kv heads):
# without this, GSPMD replicates the whole S²·H attention compute on every
# model shard. Here each shard computes its own query-sequence slice
# (compute ÷ mesh), K/V are computed locally from the replicated input
# (cheap: kv_heads is small), and the output is all-gathered once.
# ---------------------------------------------------------------------------


def attention_train_cp(
    p: dict,
    x: jax.Array,
    cfg,
    mesh,
    *,
    window=None,
    return_kv: bool = False,
    unroll: bool = False,
    seq_axis: str = "model",
):
    b, s, d = x.shape
    n_seq = mesh.shape[seq_axis]
    if s % n_seq:
        return attention_train(p, x, cfg, window=window, return_kv=return_kv,
                               unroll=unroll)
    s_loc = s // n_seq
    groups = cfg.num_heads // cfg.num_kv_heads
    scale = cfg.head_dim ** -0.5

    from jax.sharding import PartitionSpec as P

    def local(x_full, wq, wk, wv, wo, bq, bk, bv):
        j = jax.lax.axis_index(seq_axis)
        x_l = jax.lax.dynamic_slice_in_dim(x_full, j * s_loc, s_loc, axis=1)
        q = jnp.einsum("bsd,dhk->bshk", x_l, wq.astype(x_l.dtype))
        k = jnp.einsum("bsd,dhk->bshk", x_full, wk.astype(x_l.dtype))
        v = jnp.einsum("bsd,dhk->bshk", x_full, wv.astype(x_l.dtype))
        if bq is not None:
            q = q + bq.astype(x_l.dtype)
            k = k + bk.astype(x_l.dtype)
            v = v + bv.astype(x_l.dtype)
        q_pos = j * s_loc + jnp.arange(s_loc)
        kv_pos = jnp.arange(s)
        q = rope(q, q_pos[None, :], cfg.rope_theta)
        k = rope(k, kv_pos[None, :], cfg.rope_theta)
        out_l = _flash_body(q, k, v, q_pos, kv_pos, window, scale, groups,
                            unroll=unroll)              # (B, S_loc, H, hd)
        out_l = jnp.einsum("bshk,hkd->bsd", out_l, wo.astype(x_l.dtype))
        out = jax.lax.all_gather(out_l, seq_axis, axis=1, tiled=True)
        if return_kv:
            return out, k, v
        return out

    bq = p.get("bq")
    bk = p.get("bk")
    bv = p.get("bv")
    # bias args may be None → pass zeros-shaped placeholders instead of
    # branching specs (keeps a single shard_map signature)
    if bq is None:
        bq = jnp.zeros((cfg.num_heads, cfg.head_dim), x.dtype)
        bk = jnp.zeros((cfg.num_kv_heads, cfg.head_dim), x.dtype)
        bv = jnp.zeros((cfg.num_kv_heads, cfg.head_dim), x.dtype)

    # batch stays sharded over the DP axes; everything else is replicated
    # over 'model' going in, and the q-slice varies by model shard inside.
    b_ax = tuple(a for a in ("pod", "data")
                 if a in mesh.shape and b % mesh.shape[a] == 0) or None
    rep4 = P(b_ax, None, None, None)
    out = jax.shard_map(
        lambda xf, wq, wk, wv, wo, bq_, bk_, bv_: local(xf, wq, wk, wv, wo,
                                                        bq_, bk_, bv_),
        mesh=mesh,
        in_specs=(P(b_ax, None, None), P(None, None, None), P(None, None, None),
                  P(None, None, None), P(None, None, None), P(None, None),
                  P(None, None), P(None, None)),
        out_specs=(P(b_ax, None, None), rep4, rep4) if return_kv
        else P(b_ax, None, None),
        check_vma=False,
    )(x, p["wq"], p["wk"], p["wv"], p["wo"], bq, bk, bv)
    if return_kv:
        out, k, v = out
        return out, (k, v)
    return out
