"""Mixture-of-Experts FFN.

Two execution paths:

``moe_block``  (single-device / smoke tests)
    Dense capacity-based dispatch: top-k routing, position-in-expert via a
    stable argsort, one scatter into an ``[E, C, D]`` buffer, batched expert
    einsums, weighted combine.  The oracle for the distributed path.

``moe_block_manual``  (inside a fully-manual shard_map over (dp..., model))
    The distributed layer.  Token dispatch is where the paper's
    Adaptive-Group exchange applies verbatim (DESIGN.md §4/§5):

    * ``moe_sharding='ep'`` (phi3.5: E % axis == 0) — tokens are split over
      the model axis; each member routes its token slice into per-expert
      chunks and exchanges them with the expert owners.  With
      ``pipeline=True`` the exchange runs as the paper's grouped
      ``ppermute`` schedule with the *expert FFN computed per arriving
      chunk* (compute overlaps the remaining transfers — Algorithm 3's
      interleave); otherwise one fused ``all_to_all``.  Results return on
      the reverse schedule and token outputs are re-gathered.
    * ``moe_sharding='tp'`` (mixtral: 8 experts on a 16 axis) — expert FFN
      hidden dim is sharded over the model axis; tokens stay replicated,
      partial outputs ``psum`` over the axis (dense-TP semantics).
    * token counts not divisible by the axis (decode) fall back to
      replicated-token EP: every member computes its expert slice on all
      tokens, partial combines ``psum``.

    FSDP'd expert weights are explicitly all-gathered over the data axis at
    entry (the ZeRO-3 unshard).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import Initializer

__all__ = ["moe_init", "moe_block", "moe_block_manual"]


def moe_init(init: Initializer, cfg):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    return {
        "router": init.normal((d, e), scale=d ** -0.5),
        "w_gate": init.normal((e, d, f), scale=d ** -0.5),
        "w_up": init.normal((e, d, f), scale=d ** -0.5),
        "w_down": init.normal((e, f, d), scale=f ** -0.5),
    }


# ---------------------------------------------------------------------------
# Routing / dispatch primitives (shared)
# ---------------------------------------------------------------------------


def _route(xt, router, k):
    """Returns (top_w [T,k] f32 renormalized, top_e [T,k] i32, aux loss)."""
    t, _ = xt.shape
    e = router.shape[1]
    logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    assign = jnp.zeros((t, e), jnp.float32).at[jnp.arange(t)[:, None], top_e].set(1.0)
    aux = e * jnp.sum(assign.mean(0) * probs.mean(0))
    return top_w, top_e.astype(jnp.int32), aux


def _dispatch(xt, top_e, capacity, num_experts, dtype):
    """Scatter tokens into [E, C, D]; returns (buf, e_flat, pos, keep, tok)."""
    t, d = xt.shape
    k = top_e.shape[1]
    e_flat = top_e.reshape(-1)
    order = jnp.argsort(e_flat, stable=True)
    sorted_e = e_flat[order]
    counts = jnp.bincount(e_flat, length=num_experts)
    starts = jnp.cumsum(counts) - counts
    pos_sorted = jnp.arange(t * k) - starts[sorted_e]
    pos = jnp.zeros((t * k,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))
    keep = pos < capacity
    pos_c = jnp.minimum(pos, capacity - 1)
    tok = jnp.repeat(jnp.arange(t), k)
    payload = jnp.where(keep[:, None], xt[tok].astype(dtype), 0)
    buf = jnp.zeros((num_experts, capacity, d), dtype)
    buf = buf.at[e_flat, pos_c].add(payload)
    return buf, e_flat, pos_c, keep, tok


def _combine(out_buf, e_flat, pos_c, keep, tok, top_w, t, dtype):
    slot_out = out_buf[e_flat, pos_c]
    slot_out = jnp.where(keep[:, None], slot_out, 0)
    w_flat = top_w.reshape(-1).astype(dtype)
    return jnp.zeros((t, out_buf.shape[-1]), dtype).at[tok].add(slot_out * w_flat[:, None])


def _expert_ffn(buf, wg, wu, wd):
    """buf [E, C, D] x per-expert weights -> [E, C, D_out]."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * jnp.einsum("ecd,edf->ecf", buf, wu)
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _capacity(cfg, tokens: int) -> int:
    c = int(cfg.capacity_factor * tokens * cfg.experts_per_token / cfg.num_experts)
    return max(8, ((c + 7) // 8) * 8)


# ---------------------------------------------------------------------------
# Dense path (oracle / single device)
# ---------------------------------------------------------------------------


def moe_block(
    p,
    x: jax.Array,  # [B, L, D]
    cfg,
    *,
    shard_fn=lambda a, kind: a,  # unused on this path (kept for API compat)
    dtype=jnp.bfloat16,
) -> Tuple[jax.Array, jax.Array]:
    b, l, d = x.shape
    t = b * l
    xt = x.reshape(t, d)
    top_w, top_e, aux = _route(xt, p["router"], cfg.experts_per_token)
    buf, e_flat, pos_c, keep, tok = _dispatch(xt, top_e, _capacity(cfg, t), cfg.num_experts, dtype)
    out_buf = _expert_ffn(
        buf,
        p["w_gate"].astype(dtype),
        p["w_up"].astype(dtype),
        p["w_down"].astype(dtype),
    )
    combined = _combine(out_buf, e_flat, pos_c, keep, tok, top_w, t, dtype)
    return combined.reshape(b, l, d), aux


# ---------------------------------------------------------------------------
# Manual (distributed) path
# ---------------------------------------------------------------------------


def moe_block_manual(
    p,
    x: jax.Array,  # [B_loc, L, D] (replicated over the model axis)
    cfg,
    *,
    dp_axes: Tuple[str, ...],
    model_axis: str,
    fsdp_axis: Optional[str],
    pipeline: bool = False,
    group_factor: int = 1,
    dtype=jnp.bfloat16,
) -> Tuple[jax.Array, jax.Array]:
    ep = cfg.moe_sharding == "ep"
    pm = jax.lax.axis_size(model_axis)
    m = jax.lax.axis_index(model_axis)
    b, l, d = x.shape
    t = b * l
    xt = x.reshape(t, d)

    def unshard(w, dim):  # ZeRO-3 gather over the data axis
        if fsdp_axis is None:
            return w.astype(dtype)
        return jax.lax.all_gather(w, fsdp_axis, axis=dim, tiled=True).astype(dtype)

    router = unshard(p["router"], 0)
    wg = unshard(p["w_gate"], 1)
    wu = unshard(p["w_up"], 1)
    wd = unshard(p["w_down"], 2)

    if not ep:
        # TP experts: F sharded over model; tokens replicated; psum partials
        top_w, top_e, aux = _route(xt, router, cfg.experts_per_token)
        buf, e_flat, pos_c, keep, tok = _dispatch(
            xt, top_e, _capacity(cfg, t), cfg.num_experts, dtype
        )
        out_buf = _expert_ffn(buf, wg, wu, wd)  # [E, C, D] partial over F
        combined = _combine(out_buf, e_flat, pos_c, keep, tok, top_w, t, dtype)
        # f32 psum: XLA:CPU's AllReducePromotion crashes on bf16 all-reduce
        # clones in multi-pod replica groups (compiler bug workaround)
        combined = jax.lax.psum(combined.astype(jnp.float32), model_axis).astype(dtype)
        # aux is computed from replicated tokens: invarying over model (and
        # over data when the batch is unsharded) — pmean only over dp axes
        aux = jax.lax.pmean(aux, dp_axes) if dp_axes else aux
        return combined.reshape(b, l, d), aux

    e_loc = cfg.num_experts // pm  # local experts on this member

    if t % pm != 0:
        # replicated-token EP fallback (decode-sized batches)
        top_w, top_e, aux = _route(xt, router, cfg.experts_per_token)
        buf, e_flat, pos_c, keep, tok = _dispatch(
            xt, top_e, _capacity(cfg, t), cfg.num_experts, dtype
        )
        my = jax.lax.dynamic_slice_in_dim(buf, m * e_loc, e_loc, 0)
        out_my = _expert_ffn(my, wg, wu, wd)  # [E_loc, C, D]
        # scatter back only this member's experts; psum completes the sum
        out_buf = jnp.zeros_like(buf)
        out_buf = jax.lax.dynamic_update_slice_in_dim(out_buf, out_my, m * e_loc, 0)
        combined = _combine(out_buf, e_flat, pos_c, keep, tok, top_w, t, dtype)
        combined = jax.lax.psum(combined.astype(jnp.float32), model_axis).astype(dtype)
        aux = jax.lax.pmean(aux, dp_axes) if dp_axes else aux
        return combined.reshape(b, l, d), aux

    # --- token-sharded EP: the paper's exchange, chunk per model member ---
    tm = t // pm
    xt_m = jax.lax.dynamic_slice_in_dim(xt, m * tm, tm, 0)  # my token slice
    top_w, top_e, aux = _route(xt_m, router, cfg.experts_per_token)
    cap = _capacity(cfg, tm)
    buf, e_flat, pos_c, keep, tok = _dispatch(xt_m, top_e, cap, cfg.num_experts, dtype)
    chunks = buf.reshape(pm, e_loc, cap, d)  # chunk q -> member q's experts

    if pipeline:
        # Adaptive-Group pipelined all-to-all (Algorithm 3): each arriving
        # chunk's expert FFN runs while later chunks are still in flight.
        from repro.comm import grouped_exchange

        def consume(acc, chunk, src):
            out = _expert_ffn(chunk, wg, wu, wd)  # [E_loc, C, D]
            return jax.lax.dynamic_update_index_in_dim(acc, out, src, 0)

        acc0 = jnp.zeros((pm, e_loc, cap, d), dtype)
        out_chunks = grouped_exchange(chunks, model_axis, consume, acc0, group_factor=group_factor)
    else:
        recv = jax.lax.all_to_all(
            chunks, model_axis, split_axis=0, concat_axis=0
        )  # [pm, e_loc, cap, d]: member q's tokens for my experts
        # batch all received chunks through the local experts at once
        recv_flat = recv.transpose(1, 0, 2, 3).reshape(e_loc, pm * cap, d)
        out_flat = _expert_ffn(recv_flat, wg, wu, wd)
        out_chunks = out_flat.reshape(e_loc, pm, cap, d).transpose(1, 0, 2, 3)

    # reverse exchange: results of chunk q go back to member q
    back = jax.lax.all_to_all(out_chunks, model_axis, split_axis=0, concat_axis=0)
    out_buf = back.reshape(cfg.num_experts, cap, d)
    combined = _combine(out_buf, e_flat, pos_c, keep, tok, top_w, tm, dtype)
    # restore full token replication across the model axis
    full = jax.lax.all_gather(combined, model_axis, axis=0, tiled=True)  # [T, D]
    return full.reshape(b, l, d), jax.lax.pmean(aux, dp_axes + (model_axis,))
