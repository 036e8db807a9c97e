"""Mixture-of-Experts layer with expert parallelism (EP).

Design (see DESIGN.md §6): token-choice top-k routing with capacity, computed
under ``shard_map`` with **experts sharded over the `model` axis and tokens
replicated across it** (tokens are naturally replicated over `model` in our
layouts — batch lives on the DP axes). Each model shard:

  1. computes the (replicated) router probabilities for all local tokens;
  2. for each of its *local* experts, capacity-selects the top-C tokens by
     routing weight (an expert-choice-among-routed capacity rule — tokens
     beyond capacity are dropped, as in GShard/Switch);
  3. runs the expert FFNs as one batched einsum over (E_local, C, d);
  4. scatter-adds the weighted expert outputs back to the token buffer.

The only collective is one ``psum`` over `model` of the (B, S, d) output —
the same volume as a row-parallel MLP all-reduce; no all-to-all is needed
because tokens are model-replicated. Dummy padded experts (qwen2-moe:
60 → 64) are masked in the router so they attract no tokens.

Shared experts (deepseek/qwen2-moe) are a fused dense gated MLP handled
outside this module (TP via GSPMD).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["init_moe", "moe_layer", "moe_capacity"]


def init_moe(key, cfg, mesh: Optional[Mesh] = None) -> dict:
    from repro.parallel.sharding import pad_experts

    d = cfg.d_model
    f = cfg.moe.d_ff_expert
    e_pad = pad_experts(cfg.moe.num_experts, mesh) if mesh is not None else cfg.moe.num_experts
    ks = jax.random.split(key, 4)
    scale = d ** -0.5
    return {
        "router": jax.random.normal(ks[0], (d, e_pad), jnp.float32) * scale,
        "wg": jax.random.normal(ks[1], (e_pad, d, f), jnp.float32) * scale,
        "wu": jax.random.normal(ks[2], (e_pad, d, f), jnp.float32) * scale,
        "wd": jax.random.normal(ks[3], (e_pad, f, d), jnp.float32) * f**-0.5,
        "norm": jnp.zeros((d,), jnp.float32),
    }


def moe_capacity(tokens: int, num_experts: int, top_k: int, cf: float) -> int:
    """Per-expert capacity C, padded to a multiple of 8 (sublane)."""
    c = int(tokens * top_k / num_experts * cf) + 1
    return -(-c // 8) * 8


def _moe_local(x, router, wg, wu, wd, *, cfg, e_pad: int, model_axis: Optional[str]):
    """Per-shard MoE compute. x: (B_loc, S, d) (model-replicated)."""
    moe = cfg.moe
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)

    n_shards = 1
    shard_idx = 0
    if model_axis is not None:
        n_shards = jax.lax.axis_size(model_axis)
        shard_idx = jax.lax.axis_index(model_axis)
    e_local = e_pad // n_shards

    # --- routing (replicated over model) ---
    logits = (xf.astype(jnp.float32) @ router.astype(jnp.float32))  # (T, E_pad)
    # mask padded dummy experts
    if e_pad > moe.num_experts:
        pad_mask = jnp.arange(e_pad) >= moe.num_experts
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, moe.top_k)                   # (T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)  # renorm

    # dense routing-weight matrix restricted to top-k: (T, E_pad)
    w_full = jnp.zeros((t, e_pad), jnp.float32)
    w_full = w_full.at[jnp.arange(t)[:, None], top_i].set(top_p)

    # aux load-balance loss (computed on true experts only)
    frac_tokens = (w_full[:, : moe.num_experts] > 0).mean(0)
    frac_probs = probs[:, : moe.num_experts].mean(0)
    aux = moe.num_experts * jnp.sum(frac_tokens * frac_probs)

    # --- local expert slice ---
    # wg/wu/wd arrive pre-sliced by shard_map: (E_local, d, f) etc.
    w_local = jax.lax.dynamic_slice(
        w_full, (0, shard_idx * e_local), (t, e_local)
    )  # (T, E_local)

    cap = moe_capacity(t, e_pad, moe.top_k, moe.capacity_factor)
    cap = min(cap, t)
    # capacity-select: per local expert, top-C tokens by routing weight
    sel_w, sel_t = jax.lax.top_k(w_local.T, cap)                     # (E_local, C)
    xg = xf[sel_t]                                                   # (E_local, C, d)
    active = (sel_w > 0.0).astype(xf.dtype)[..., None]

    g = jnp.einsum("ecd,edf->ecf", xg, wg.astype(xf.dtype))
    u = jnp.einsum("ecd,edf->ecf", xg, wu.astype(xf.dtype))
    h = jax.nn.silu(g) * u
    out_e = jnp.einsum("ecf,efd->ecd", h, wd.astype(xf.dtype))
    out_e = out_e * active * sel_w[..., None].astype(xf.dtype)

    # scatter-add back to tokens
    yf = jnp.zeros((t, d), xf.dtype)
    yf = yf.at[sel_t.reshape(-1)].add(out_e.reshape(-1, d))
    if model_axis is not None:
        yf = jax.lax.psum(yf, model_axis)
        aux = aux  # identical on all shards (routing is replicated)
    return yf.reshape(b, s, d), aux


def moe_layer(
    p: dict,
    x: jax.Array,
    cfg,
    mesh: Optional[Mesh] = None,
) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN. x: (B, S, d) → (y, aux_loss).

    With a mesh: shard_map over the full mesh — tokens split over DP axes,
    experts over 'model'. Without a mesh (single-device smoke): direct call.
    """
    e_pad = p["router"].shape[-1]
    if mesh is None or "model" not in mesh.shape or mesh.shape["model"] == 1:
        y, aux = _moe_local(
            x, p["router"], p["wg"], p["wu"], p["wd"],
            cfg=cfg, e_pad=e_pad, model_axis=None,
        )
        return y, aux

    from repro.parallel.sharding import data_axes

    dp = data_axes(mesh)
    if cfg.moe.sharding == "ep" and e_pad % mesh.shape["model"] == 0:
        expert_spec = P("model", None, None)
        model_axis = "model"
    else:
        # TP fallback inside experts (ff dim) — experts replicated
        expert_spec = P(None, None, "model")
        model_axis = None

    def fn(x_l, router, wg, wu, wd):
        y, aux = _moe_local(
            x_l, router, wg, wu, wd, cfg=cfg, e_pad=e_pad,
            model_axis=model_axis,
        )
        if model_axis is None:
            # TP mode: partial outputs over the ff shards
            y = jax.lax.psum(y, "model")
        # aux: average over every mesh axis (replicated axes unaffected)
        aux = jax.lax.pmean(aux, tuple(mesh.axis_names))
        return y, aux

    b_axis = dp if x.shape[0] % _size(mesh, dp) == 0 else None
    s_axis = dp if b_axis is None and x.shape[1] % _size(mesh, dp) == 0 else None
    in_specs = (
        P(b_axis, s_axis, None),
        P(None, None),
        expert_spec,
        expert_spec,
        P("model", None, None) if model_axis else P(None, "model", None),
    )
    out_specs = (P(b_axis, s_axis, None), P())
    # check_vma=False: routing is replicated over 'model' while expert
    # weights vary over it; the psum-of-contributions pattern mixes
    # model-invariant and model-varying values, which the strict VMA
    # checker rejects even though the collective semantics are exactly
    # what we want (classic shard_map behavior).
    y, aux = jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )(x, p["router"], p["wg"], p["wu"], p["wd"])
    return y, aux


def _size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
