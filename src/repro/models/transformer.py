"""Generic decoder LM assembled from an ArchConfig.

One code path covers every assigned family via the block pattern:

  attn        global causal self-attention (+ SWA window) + FFN (dense/MoE)
  local       windowed self-attention + FFN
  cross       cross-attention over stub context (llama-vision image layers)
  attn_cross  self-attn + cross-attn + FFN (whisper decoder layer)
  rwkv        RWKV6 time-mix + channel-mix
  rglru       RG-LRU temporal mix + FFN

Depth is organized as ``n_full`` repeats of the pattern (stacked params,
``lax.scan`` + optional remat — O(1) HLO in depth, which is what keeps the
100-layer dry-run compilable) plus an explicit ragged tail.  Whisper adds a
separate bidirectional encoder stack over stub frame embeddings.

Caches/states mirror the layer structure ({'groups': {pos_j: stacked},
'tail': [...]}) and thread through the same scan in decode.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map

from .attention import attn_init, attention_block, init_kv_cache
from .layers import Initializer, mlp_apply, mlp_init, rmsnorm
from .moe import moe_block, moe_init
from .rglru import init_rglru_state, rglru_block, rglru_init
from .rwkv6 import init_rwkv_state, rwkv_block, rwkv_channel_mix, rwkv_init

__all__ = ["init_params", "forward", "encode", "init_caches", "layer_plan"]

_NOOP = lambda x, kind: x


# ---------------------------------------------------------------------------
# Layer plan
# ---------------------------------------------------------------------------


def layer_plan(cfg) -> Tuple[int, Tuple[str, ...], Tuple[str, ...]]:
    """(n_full_groups, pattern, tail_kinds)."""
    pat = cfg.block_pattern
    n_full = cfg.num_layers // len(pat)
    tail = pat[: cfg.num_layers % len(pat)]
    return n_full, pat, tail


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def _ffn_init(init: Initializer, cfg):
    if cfg.num_experts:
        return moe_init(init, cfg)
    return mlp_init(init, cfg.d_model, cfg.d_ff, cfg.act)


def _block_init(init: Initializer, cfg, kind: str):
    d = cfg.d_model
    p: Dict[str, Any] = {"ln1": init.ones((d,))}
    if kind in ("attn", "local"):
        p["attn"] = attn_init(init, cfg)
        p["ln2"] = init.ones((d,))
        p["ffn"] = _ffn_init(init, cfg)
    elif kind == "cross":
        p["xattn"] = attn_init(init, cfg, cross=True)
        p["ln2"] = init.ones((d,))
        p["ffn"] = _ffn_init(init, cfg)
        p["xgate"] = init.zeros(())  # llama-vision style gated cross-attn
    elif kind == "attn_cross":
        p["attn"] = attn_init(init, cfg)
        p["ln_c"] = init.ones((d,))
        p["xattn"] = attn_init(init, cfg, cross=True)
        p["ln2"] = init.ones((d,))
        p["ffn"] = _ffn_init(init, cfg)
    elif kind == "rwkv":
        p.update(rwkv_init(init, cfg))
        p["ln2"] = init.ones((d,))
    elif kind == "rglru":
        p["rec"] = rglru_init(init, cfg)
        p["ln2"] = init.ones((d,))
        p["ffn"] = mlp_init(init, cfg.d_model, cfg.d_ff, cfg.act)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    return p


def init_params(cfg, key: jax.Array):
    """Pure init function — run under ``jax.eval_shape`` for the dry-run."""
    init = Initializer(key)
    d = cfg.d_model
    n_full, pat, tail = layer_plan(cfg)
    params: Dict[str, Any] = {
        "embed": init.normal((cfg.padded_vocab, d)),
        "final_norm": init.ones((d,)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init.normal((d, cfg.padded_vocab))
    groups = {}
    for j, kind in enumerate(pat):
        stacked = [ _block_init(init, cfg, kind) for _ in range(n_full) ]
        groups[f"pos{j}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *stacked) if n_full else None
    params["groups"] = {k: v for k, v in groups.items() if v is not None}
    params["tail"] = [_block_init(init, cfg, kind) for kind in tail]
    if cfg.encoder_layers:
        params["encoder"] = {
            "blocks": [
                {
                    "ln1": init.ones((d,)),
                    "attn": attn_init(init, cfg),
                    "ln2": init.ones((d,)),
                    "ffn": mlp_init(init, d, cfg.d_ff, cfg.act),
                }
                for _ in range(cfg.encoder_layers)
            ],
            "final_norm": init.ones((d,)),
        }
    return params


# ---------------------------------------------------------------------------
# Caches / recurrent state
# ---------------------------------------------------------------------------


def _block_cache(cfg, kind: str, batch: int, s_buf: int):
    hd = cfg.resolved_head_dim
    if kind in ("attn", "attn_cross"):
        c = init_kv_cache(batch, cfg.num_kv_heads, s_buf, hd)
        return c
    if kind == "local":
        win_buf = min(s_buf, cfg.local_window + 128)
        return init_kv_cache(batch, cfg.num_kv_heads, win_buf, hd)
    if kind == "cross":
        return {"ctx": None}  # filled with projected context at prefill
    if kind == "rwkv":
        h = cfg.d_model // hd
        return init_rwkv_state(batch, h, hd, cfg.d_model)
    if kind == "rglru":
        return init_rglru_state(batch, cfg.d_model)
    raise ValueError(kind)


def cache_buffer_len(cfg, seq_len: int) -> int:
    """Self-attn KV buffer length for decode at context ``seq_len``."""
    if cfg.window > 0:
        return min(seq_len + 128, cfg.window + 128)
    return seq_len + 128


def init_caches(cfg, batch: int, seq_len: int, *, context_len: int = 0):
    """Zero caches for decoding with ``seq_len`` tokens of context.

    Cross-attention caches hold the projected stub context (filled by
    ``forward`` at prefill); here they are zero tensors of the right shape.
    """
    s_buf = cache_buffer_len(cfg, seq_len)
    n_full, pat, tail = layer_plan(cfg)
    hd = cfg.resolved_head_dim

    def one(kind):
        c = _block_cache(cfg, kind, batch, s_buf)
        if kind == "cross":
            lc = context_len or cfg.num_image_tokens or cfg.encoder_context
            c = {
                "xk": jnp.zeros((batch, cfg.num_kv_heads, lc, hd), jnp.bfloat16),
                "xv": jnp.zeros((batch, cfg.num_kv_heads, lc, hd), jnp.bfloat16),
            }
        if kind == "attn_cross":
            lc = context_len or cfg.encoder_context
            c["xk"] = jnp.zeros((batch, cfg.num_kv_heads, lc, hd), jnp.bfloat16)
            c["xv"] = jnp.zeros((batch, cfg.num_kv_heads, lc, hd), jnp.bfloat16)
        return c

    groups = {}
    for j, kind in enumerate(pat):
        if n_full:
            stacked = [one(kind) for _ in range(n_full)]
            groups[f"pos{j}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *stacked)
    return {"groups": groups, "tail": [one(kind) for kind in tail]}


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _ffn_apply(p, x, cfg, shard, dtype):
    if not cfg.num_experts:
        return mlp_apply(p, x, cfg.act, dtype=dtype), jnp.zeros((), jnp.float32)

    mesh = getattr(shard, "mesh", None)
    dp = tuple(getattr(shard, "dp_axes", ()) or ())
    mdl = getattr(shard, "model_axis", "model")
    if mesh is None or mdl not in mesh.axis_names:
        out, aux = moe_block(p, x, cfg, dtype=dtype)
        return out, aux

    # Token dispatch (argsort/scatter) is sharding-hostile under plain SPMD
    # (XLA replicates the global sort), so the whole MoE FFN runs in a
    # fully-manual shard_map: routing/scatter local per data shard, token
    # chunks exchanged over the model axis with the paper's grouped
    # pipeline or a fused all_to_all (models.moe.moe_block_manual).
    from jax.sharding import PartitionSpec as P

    from .moe import moe_block_manual

    fsdp = getattr(shard, "fsdp_axis", None)
    ep = cfg.moe_sharding == "ep"
    pspecs = {
        "router": P(fsdp, None),
        "w_gate": P(mdl, fsdp, None) if ep else P(None, fsdp, mdl),
        "w_up": P(mdl, fsdp, None) if ep else P(None, fsdp, mdl),
        "w_down": P(mdl, None, fsdp) if ep else P(None, mdl, fsdp),
    }

    def body(p_, x_):
        return moe_block_manual(
            p_,
            x_,
            cfg,
            dp_axes=dp,
            model_axis=mdl,
            fsdp_axis=fsdp,
            pipeline=getattr(shard, "moe_pipeline", False),
            group_factor=getattr(shard, "moe_group_factor", 1),
            dtype=dtype,
        )

    manual = set(dp) | {mdl}
    if fsdp:
        manual.add(fsdp)  # weight specs mention the FSDP axis even when the
        # batch is unsharded (long_500k b=1): it must be manual here too
    mapped = shard_map(
        body,
        mesh=mesh,
        in_specs=(pspecs, P(dp, None, None)),
        out_specs=(P(dp, None, None), P()),
        axis_names=manual,
        # outputs ARE replicated over the model axis (psum / final
        # all_gather above), but vma can't infer it through all_gather
        check_vma=False,
    )
    return mapped(p, x)


def _apply_block(
    p,
    h,
    cfg,
    kind: str,
    *,
    context=None,
    cache=None,
    pos=None,
    mode="train",
    shard=_NOOP,
    impl="xla",
    dtype=jnp.bfloat16,
    s_buf: Optional[int] = None,
):
    """Pre-norm residual block.  Returns (h, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    eps = cfg.norm_eps
    build_len = s_buf if mode == "prefill" else None
    decode_cache = cache if mode == "decode" else None

    if kind in ("attn", "local"):
        window = cfg.window if kind == "attn" else cfg.local_window
        mix, new_cache = attention_block(
            p["attn"],
            rmsnorm(p["ln1"], h, eps),
            cfg,
            causal=True,
            window=window,
            cache=decode_cache,
            pos=pos,
            impl=impl,
            dtype=dtype,
            build_cache_len=build_len if kind == "attn" else (
                min(s_buf, cfg.local_window + 128) if build_len else None
            ),
            shard=shard,
        )
        h = shard(h + mix, "act")
        ff, aux = _ffn_apply(p["ffn"], rmsnorm(p["ln2"], h, eps), cfg, shard, dtype)
        h = shard(h + ff, "act")
    elif kind == "cross":
        if mode == "decode":
            xk, xv = cache["xk"], cache["xv"]
            mix = _cross_from_cache(p["xattn"], rmsnorm(p["ln1"], h, eps), cfg, xk, xv, dtype)
            new_cache = cache
        else:
            mix, _ = attention_block(
                p["xattn"], rmsnorm(p["ln1"], h, eps), cfg, context=context, dtype=dtype
            )
            new_cache = _project_context(
                p["xattn"],
                cfg,
                context,
                dtype,
            ) if mode == "prefill" else None
        h = shard(h + jnp.tanh(p["xgate"]).astype(h.dtype) * mix, "act")
        ff, aux = _ffn_apply(p["ffn"], rmsnorm(p["ln2"], h, eps), cfg, shard, dtype)
        h = shard(h + ff, "act")
    elif kind == "attn_cross":
        sub_cache = ({k: cache[k] for k in ("k", "v", "slot_pos")} if mode == "decode" else None)
        mix, new_kv = attention_block(
            p["attn"],
            rmsnorm(p["ln1"], h, eps),
            cfg,
            causal=True,
            cache=sub_cache,
            pos=pos,
            impl=impl,
            dtype=dtype,
            build_cache_len=build_len,
            shard=shard,
        )
        h = shard(h + mix, "act")
        if mode == "decode":
            xmix = _cross_from_cache(
                p["xattn"], rmsnorm(p["ln_c"], h, eps), cfg, cache["xk"], cache["xv"], dtype
            )
        else:
            xmix, _ = attention_block(
                p["xattn"], rmsnorm(p["ln_c"], h, eps), cfg, context=context, dtype=dtype
            )
        h = shard(h + xmix, "act")
        ff, aux = _ffn_apply(p["ffn"], rmsnorm(p["ln2"], h, eps), cfg, shard, dtype)
        h = shard(h + ff, "act")
        new_cache = None
        if mode == "prefill":
            new_cache = dict(new_kv or {}, **_project_context(p["xattn"], cfg, context, dtype))
        elif mode == "decode":
            new_cache = dict(new_kv, xk=cache["xk"], xv=cache["xv"])
    elif kind == "rwkv":
        state = cache if mode in ("decode", "prefill") else None
        if state is None and mode in ("decode", "prefill"):
            raise ValueError("rwkv needs state in cache modes")
        mix, new_state = rwkv_block(p, rmsnorm(p["ln1"], h, eps), cfg, state=state, dtype=dtype)
        h = shard(h + mix, "act")
        cm, new_state2 = rwkv_channel_mix(
            p, rmsnorm(p["ln2"], h, eps), state=new_state, dtype=dtype
        )
        h = shard(h + cm, "act")
        new_cache = new_state2
    elif kind == "rglru":
        state = cache if mode in ("decode", "prefill") else None
        mix, new_state = rglru_block(
            p["rec"], rmsnorm(p["ln1"], h, eps), cfg, state=state, dtype=dtype
        )
        h = shard(h + mix, "act")
        ff = mlp_apply(p["ffn"], rmsnorm(p["ln2"], h, eps), cfg.act, dtype=dtype)
        h = shard(h + ff, "act")
        new_cache = new_state
    else:
        raise ValueError(kind)
    return h, new_cache, aux


def _project_context(p, cfg, context, dtype):
    """Precompute cross-attention K/V from the (stub) context for decode."""
    hd = cfg.resolved_head_dim
    kv = cfg.num_kv_heads
    b, lc, _ = context.shape

    def proj(w):
        y = context.astype(dtype) @ w["w"].astype(dtype)
        if "b" in w:
            y = y + w["b"].astype(dtype)
        return y.reshape(b, lc, kv, hd).transpose(0, 2, 1, 3)

    return {"xk": proj(p["wk"]), "xv": proj(p["wv"])}


def _cross_from_cache(p, x, cfg, xk, xv, dtype):
    from .attention import decode_attention

    hd = cfg.resolved_head_dim
    h, kvh = cfg.num_heads, cfg.num_kv_heads
    b, l, _ = x.shape
    q = (x.astype(dtype) @ p["wq"]["w"].astype(dtype))
    if "b" in p["wq"]:
        q = q + p["wq"]["b"].astype(dtype)
    q = q.reshape(b, l, h, hd).transpose(0, 2, 1, 3)
    lc = xk.shape[2]
    slot_pos = jnp.arange(lc)
    out = decode_attention(q, xk, xv, slot_pos, jnp.asarray(lc, jnp.int32), window=0)
    out = out.transpose(0, 2, 1, 3).reshape(b, l, h * hd)
    return out @ p["wo"]["w"].astype(dtype)


# ---------------------------------------------------------------------------
# Encoder (whisper)
# ---------------------------------------------------------------------------


def encode(params, cfg, frames: jax.Array, *, shard=_NOOP, dtype=jnp.bfloat16):
    """Bidirectional encoder over stub frame embeddings [B, T, D]."""
    h = frames.astype(dtype)
    enc = params["encoder"]
    for blk in enc["blocks"]:
        mix, _ = attention_block(
            blk["attn"], rmsnorm(blk["ln1"], h, cfg.norm_eps), cfg, causal=False, dtype=dtype
        )
        h = shard(h + mix, "act")
        ff = mlp_apply(blk["ffn"], rmsnorm(blk["ln2"], h, cfg.norm_eps), cfg.act, dtype=dtype)
        h = shard(h + ff, "act")
    return rmsnorm(enc["final_norm"], h, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def forward(
    params,
    cfg,
    tokens: jax.Array,  # [B, L] int32
    *,
    context: Optional[jax.Array] = None,  # [B, Lc, D] stub image/audio embeds
    mode: str = "train",  # train | prefill | decode
    caches=None,
    pos=None,  # decode position (scalar int32)
    shard=_NOOP,
    impl: str = "xla",
    remat: str = "full",
    dtype=jnp.bfloat16,
    s_buf: Optional[int] = None,  # prefill cache buffer length
    return_hidden: bool = False,  # skip the LM head (caller chunks the loss)
    unroll: bool = False,  # python-loop the groups (dry-run flop probes)
    cast_params: bool = False,  # cast >=2D weights to compute dtype up front
):
    """Returns (logits-or-hidden [B, L, V|D], new_caches, aux_loss)."""
    n_full, pat, tail = layer_plan(cfg)
    if cast_params:
        # cast-before-gather: FSDP all-gathers (and any hoisted copies of
        # the stacked layer weights) move bf16, not f32 — halves both the
        # gather bytes and the gathered-weight temps.  Masters stay f32 in
        # the optimizer; 1-D params (norms, mixes, decay bases) keep f32
        # for numerics.
        params = jax.tree.map(
            lambda x: x.astype(dtype)
            if (hasattr(x, "dtype") and x.dtype == jnp.float32 and x.ndim >= 2)
            else x,
            params,
        )
    h = params["embed"].astype(dtype)[tokens]
    h = shard(h, "act")
    use_cache = mode in ("prefill", "decode")
    if mode == "prefill" and caches is None:
        # zero recurrent states; attention caches are rebuilt by the blocks
        caches = init_caches(
            cfg,
            tokens.shape[0],
            tokens.shape[1],
            context_len=context.shape[1] if context is not None else 0,
        )

    pc = getattr(shard, "param_constraint", None)
    gspecs = getattr(shard, "group_specs", None)

    def group_step(h, group_params, group_cache):
        if pc is not None and gspecs is not None:
            # keep per-layer weights sharded at the loop boundary so the
            # FSDP gather stays INSIDE the scan body (one layer at a time)
            group_params = {k: pc(v, gspecs[k]) for k, v in group_params.items()}
        new_cache = {}
        aux = jnp.zeros((), jnp.float32)
        for j, kind in enumerate(pat):
            pj = f"pos{j}"
            c = group_cache.get(pj) if group_cache else None
            h, nc, a = _apply_block(
                group_params[pj],
                h,
                cfg,
                kind,
                context=context,
                cache=c,
                pos=pos,
                mode=mode,
                shard=shard,
                impl=impl,
                dtype=dtype,
                s_buf=s_buf,
            )
            aux = aux + a
            if use_cache:
                new_cache[pj] = nc
        return h, new_cache, aux

    if remat == "full":
        group_step = jax.checkpoint(group_step, static_argnums=())
    elif remat == "dots":
        group_step = jax.checkpoint(
            group_step, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )

    aux_total = jnp.zeros((), jnp.float32)
    new_caches = {"groups": {}, "tail": []}
    if n_full:
        if unroll:
            auxes = []
            group_cache_list = []
            for gi in range(n_full):
                gp = jax.tree.map(lambda x: x[gi], params["groups"])
                gc = (
                    jax.tree.map(lambda x: x[gi], caches["groups"])
                    if use_cache
                    else None
                )
                h, nc, a = group_step(h, gp, gc)
                auxes.append(a)
                if use_cache:
                    group_cache_list.append(nc)
            if use_cache:
                new_caches["groups"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *group_cache_list
                )
            auxes = jnp.stack(auxes)
        elif use_cache:
            def scan_body(h, xs):
                gp, gc = xs
                h, nc, a = group_step(h, gp, gc)
                return h, (nc, a)

            h, (stacked_caches, auxes) = jax.lax.scan(
                scan_body, h, (params["groups"], caches["groups"])
            )
            new_caches["groups"] = stacked_caches
        else:
            def scan_body_nc(h, gp):
                h, _, a = group_step(h, gp, None)
                return h, a

            h, auxes = jax.lax.scan(scan_body_nc, h, params["groups"])
        aux_total = aux_total + jnp.sum(auxes)

    for i, kind in enumerate(tail):
        c = caches["tail"][i] if use_cache and caches is not None else None
        h, nc, a = _apply_block(
            params["tail"][i],
            h,
            cfg,
            kind,
            context=context,
            cache=c,
            pos=pos,
            mode=mode,
            shard=shard,
            impl=impl,
            dtype=dtype,
            s_buf=s_buf,
        )
        aux_total = aux_total + a
        if use_cache:
            new_caches["tail"].append(nc)

    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    if return_hidden:
        return h, (new_caches if use_cache else None), aux_total
    head = params.get("lm_head", None)
    if head is None:
        logits = h.astype(jnp.float32) @ params["embed"].astype(jnp.float32).T
    else:
        logits = h.astype(jnp.float32) @ head.astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab_size:
        pad_mask = jnp.where(jnp.arange(cfg.padded_vocab) < cfg.vocab_size, 0.0, -1e30)
        logits = logits + pad_mask
    logits = shard(logits, "logits")
    return logits, (new_caches if use_cache else None), aux_total
