"""Unified decoder model covering all assigned families.

One scanned stack handles dense / MoE / SSM / hybrid / audio / VLM configs:
``cfg.hybrid_pattern`` gives the repeating cycle of block kinds
(e.g. ``("rec","rec","attn_local")`` for recurrentgemma,
``("attn","attn_moe")`` for llama4); layers are scanned over whole cycles
(stacked params — O(1) HLO size regardless of depth) with any remainder
layers unrolled as a tail.

Three entry points, matching the assigned shape kinds:
  * ``train_step_fn``   — fwd + bwd + optimizer update (train_4k)
  * ``prefill_fn``      — forward over the prompt, emits logits + cache
  * ``decode_step_fn``  — one token against the cache (decode_32k/long_500k)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.attention import decode_attention, train_attention
from repro.models.layers import (
    embedding_apply,
    linear_apply,
    make_embedding,
    make_linear,
    mlp_apply,
    make_mlp,
    rms_norm,
    rope,
)
from repro.core.meshctx import constrain as meshctx_constrain
from repro.core.tt import ttm_reconstruct
from repro.models.moe import moe_apply, moe_init
from repro.models.ssm import mamba2_apply, mamba2_init, rglru_apply, rglru_init
from repro.tracing import ATTN, EMBED, FFN, HEAD

__all__ = [
    "init_params", "forward", "loss_fn", "lm_head", "token_nll",
    "init_cache", "cache_struct",
    "map_cache", "cache_descriptors", "CacheLeaf",
    "block_init", "block_apply", "num_params", "param_bytes",
]


# ---------------------------------------------------------------------------
# Blocks.
# ---------------------------------------------------------------------------


def _attn_init(key: jax.Array, cfg: ModelConfig, *, local: bool) -> dict:
    q_dim, kv_dim, d = cfg.attn_dims
    d_head = cfg.d_head if not local else cfg.d_head
    ks = jax.random.split(key, 5)
    p = {
        "q": make_linear(ks[0], q_dim, d, cfg, "attn", use_bias=cfg.qkv_bias),
        "k": make_linear(ks[1], kv_dim, d, cfg, "attn", use_bias=cfg.qkv_bias),
        "v": make_linear(ks[2], kv_dim, d, cfg, "attn", use_bias=cfg.qkv_bias),
        "o": make_linear(ks[3], d, q_dim, cfg, "attn"),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((d_head,), jnp.dtype(cfg.dtype))
        p["k_norm"] = jnp.zeros((d_head,), jnp.dtype(cfg.dtype))
    return p


def _attn_apply(p: dict, x: jax.Array, cfg: ModelConfig, *, window: int | None,
                cache: dict | None, mode: str, pos, delta_cache: bool = False):
    """Returns (out, new_cache).  ``delta_cache``: decode returns only the
    newly written KV column {"k","v" (B,1,KV,dh)} instead of the full
    updated cache — the caller scatters it into its stacked buffer so one
    decode step writes O(B·KV·dh) bytes, not O(B·S·KV·dh)."""
    B, S, _ = x.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    flow, fb, prec = cfg.tt.flow, cfg.tt.fused_bwd, cfg.tt.precision
    # Head-dim TP cut point (see mlp_apply note re: replicated TT factors).
    q = meshctx_constrain(linear_apply(p["q"], x, flow=flow, fused_bwd=fb,
                                       precision=prec),
                          ("pod", "data"), None, "model").reshape(B, S, H, dh)
    k = meshctx_constrain(linear_apply(p["k"], x, flow=flow, fused_bwd=fb,
                                       precision=prec),
                          ("pod", "data"), None, "model").reshape(B, S, KV, dh)
    v = meshctx_constrain(linear_apply(p["v"], x, flow=flow, fused_bwd=fb,
                                       precision=prec),
                          ("pod", "data"), None, "model").reshape(B, S, KV, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.pos_embed == "rope":
        if mode == "decode":
            positions = jnp.broadcast_to(pos[None, None], (B, S))
        else:
            positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    new_cache = None
    if mode == "decode":
        kv_rep = cache["k"].shape[2] // KV
        if kv_rep > 1:
            k = jnp.repeat(k, kv_rep, axis=2)
            v = jnp.repeat(v, kv_rep, axis=2)
        slot = pos % cache["k"].shape[1] if window is not None else pos
        kc = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
        out = decode_attention(q, kc, vc, pos + 1, window=window)
        new_cache = {"k": k, "v": v} if delta_cache else {"k": kc, "v": vc}
    else:
        qc = cfg.attn_q_chunk or S
        kc = cfg.attn_kv_chunk or S
        out = train_attention(q, k, v, causal=cfg.causal, window=window,
                              q_chunk=qc, kv_chunk=kc,
                              fused=cfg.fused_attn, precision=prec)
        if mode == "prefill":
            new_cache = {"k": k, "v": v}
    out = out.reshape(B, S, H * dh)
    return linear_apply(p["o"], out, flow=flow, fused_bwd=fb,
                        precision=prec), new_cache


def block_init(key: jax.Array, kind: str, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 4)
    dtype = jnp.dtype(cfg.dtype)
    p: dict[str, Any] = {"norm1": jnp.zeros((cfg.d_model,), dtype)}
    if kind in ("attn", "attn_moe", "attn_local"):
        p["attn"] = _attn_init(ks[0], cfg, local=kind == "attn_local")
        p["norm2"] = jnp.zeros((cfg.d_model,), dtype)
        if kind == "attn_moe":
            p["moe"] = moe_init(ks[1], cfg)
        else:
            p["mlp"] = make_mlp(ks[1], cfg)
    elif kind == "ssm":
        p["mixer"] = mamba2_init(ks[0], cfg)
    elif kind == "rec":
        p["mixer"] = rglru_init(ks[0], cfg)
        p["norm2"] = jnp.zeros((cfg.d_model,), dtype)
        p["mlp"] = make_mlp(ks[1], cfg)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    return p


def block_apply(kind: str, p: dict, x: jax.Array, cfg: ModelConfig, *,
                cache: dict | None, mode: str, pos,
                delta_cache: bool = False) -> tuple[jax.Array, dict | None]:
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if kind in ("attn", "attn_moe", "attn_local"):
        window = cfg.window if kind == "attn_local" else None
        with jax.named_scope(ATTN):
            out, new_cache = _attn_apply(p["attn"], h, cfg, window=window,
                                         cache=cache, mode=mode, pos=pos,
                                         delta_cache=delta_cache)
        x = x + out
        h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
        with jax.named_scope(FFN):
            if kind == "attn_moe":
                out = moe_apply(p["moe"], h2, cfg)
            else:
                out = mlp_apply(p["mlp"], h2, cfg)
        x = x + out
    elif kind == "ssm":
        out, new_cache = mamba2_apply(p["mixer"], h, cfg, cache, mode=mode)
        x = x + out
    elif kind == "rec":
        out, new_cache = rglru_apply(p["mixer"], h, cfg, cache, mode=mode)
        x = x + out
        h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
        with jax.named_scope(FFN):
            out = mlp_apply(p["mlp"], h2, cfg)
        x = x + out
    else:
        raise ValueError(kind)
    return x, new_cache


# ---------------------------------------------------------------------------
# Stacking: full cycles scanned, remainder unrolled.
# ---------------------------------------------------------------------------


def _cycle_layout(cfg: ModelConfig) -> tuple[int, tuple[str, ...]]:
    pat = cfg.hybrid_pattern
    n_cycles = cfg.num_layers // len(pat)
    tail = cfg.hybrid_pattern[: cfg.num_layers - n_cycles * len(pat)]
    return n_cycles, tail


def init_params(key: jax.Array, cfg: ModelConfig) -> dict:
    n_cycles, tail = _cycle_layout(cfg)
    pat = cfg.hybrid_pattern
    k_embed, k_layers, k_tail, k_head, k_pos = jax.random.split(key, 5)
    dtype = jnp.dtype(cfg.dtype)

    cycle_keys = jax.random.split(k_layers, n_cycles)

    def one_cycle(ck):
        kks = jax.random.split(ck, len(pat))
        return tuple(block_init(kk, kind, cfg) for kk, kind in zip(kks, pat))

    stacked = jax.vmap(one_cycle)(cycle_keys) if n_cycles > 0 else None

    params: dict[str, Any] = {
        "embed": make_embedding(k_embed, cfg),
        "layers": stacked,
        "tail": tuple(
            block_init(kk, kind, cfg)
            for kk, kind in zip(jax.random.split(k_tail, max(len(tail), 1)), tail)
        ),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = make_linear(k_head, cfg.vocab_padded, cfg.d_model, cfg, "head")
    if cfg.pos_embed == "learned":
        params["pos_table"] = (
            jax.random.normal(k_pos, (cfg.max_seq_len, cfg.d_model), dtype) * 0.02)
    if cfg.frontend == "patch":
        # Stub frontend: a dense projection of precomputed patch embeddings.
        params["patch_proj"] = make_linear(k_pos, cfg.d_model, cfg.d_model, cfg, "none")
    return params


def _embed_inputs(params: dict, cfg: ModelConfig, tokens: jax.Array,
                  patches: jax.Array | None, pos_offset) -> jax.Array:
    with jax.named_scope(EMBED):
        h = embedding_apply(params["embed"], tokens)
        if cfg.frontend == "patch" and patches is not None:
            pe = linear_apply(params["patch_proj"], patches, flow=cfg.tt.flow,
                              fused_bwd=cfg.tt.fused_bwd)
            h = jnp.concatenate([pe, h[:, patches.shape[1]:, :]], axis=1)
        if cfg.pos_embed == "learned":
            S = tokens.shape[1]
            idx = pos_offset + jnp.arange(S)
            h = h + jnp.take(params["pos_table"], idx, axis=0)[None]
        elif cfg.pos_embed == "sinusoidal":
            S = tokens.shape[1]
            d = cfg.d_model
            pos = (pos_offset + jnp.arange(S))[:, None].astype(jnp.float32)
            div = jnp.exp(jnp.arange(0, d, 2, jnp.float32) * (-jnp.log(10000.0) / d))
            pe = jnp.zeros((S, d), jnp.float32)
            pe = pe.at[:, 0::2].set(jnp.sin(pos * div)).at[:, 1::2].set(jnp.cos(pos * div))
            h = h + pe.astype(h.dtype)[None]
        return h


def forward(params: dict, cfg: ModelConfig, tokens: jax.Array, *,
            patches: jax.Array | None = None, cache: Any = None,
            mode: str = "train", pos=0, remat: bool = True,
            features_only: bool = False):
    """Full model forward.

    mode="train":   tokens (B, S) -> logits (B, S, Vp); cache unused.
    mode="prefill": also returns per-layer cache for subsequent decode.
    mode="decode":  tokens (B, 1), cache required, ``pos`` scalar position.
    Returns (logits, new_cache).
    """
    n_cycles, tail = _cycle_layout(cfg)
    pat = cfg.hybrid_pattern
    pos = jnp.asarray(pos, jnp.int32)
    h = _embed_inputs(params, cfg, tokens,
                      patches, pos if mode == "decode" else 0)

    has_cache = cache is not None and cache.get("layers") is not None

    if mode == "decode" and has_cache and n_cycles > 0:
        # Decode: carry the WHOLE stacked cache and update each cycle's
        # slice in place (dynamic-slice / dynamic-update-slice on the
        # carry).  Emitting per-cycle caches as scan `ys` instead would
        # re-stack (copy) the full multi-GB cache every decode step; the
        # carried buffer aliases with the donated input cache so only the
        # touched slices move (EXPERIMENTS.md §Perf).
        def _write_block(kind, buf_blk, nc_blk, idx):
            """Scatter one block's cache delta into its stacked buffer."""
            if kind in ("attn", "attn_moe", "attn_local"):
                window = cfg.window if kind == "attn_local" else None
                out = {}
                for key in ("k", "v"):
                    buf = buf_blk[key]            # (L, B, Smax, KV, dh)
                    col = nc_blk[key].astype(buf.dtype)  # (B, 1, KV, dh)
                    slot = pos % buf.shape[2] if window is not None else pos
                    out[key] = jax.lax.dynamic_update_slice(
                        buf, col[None], (idx, 0, slot, 0, 0))
                return out
            return jax.tree.map(
                lambda buf, nc_: jax.lax.dynamic_update_index_in_dim(
                    buf, nc_.astype(buf.dtype), idx, axis=0),
                buf_blk, nc_blk)

        def decode_cycle(carry, layer_params):
            hh, cache_stack, idx = carry
            layer_cache = jax.tree.map(
                lambda buf: jax.lax.dynamic_index_in_dim(
                    buf, idx, axis=0, keepdims=False), cache_stack)
            new_stack = []
            for i, kind in enumerate(pat):
                hh, nc = block_apply(kind, layer_params[i], hh, cfg,
                                     cache=layer_cache[i], mode=mode, pos=pos,
                                     delta_cache=True)
                new_stack.append(_write_block(kind, cache_stack[i], nc, idx))
            return (hh, tuple(new_stack), idx + 1), None

        (h, new_stack_cache, _), _ = jax.lax.scan(
            decode_cycle, (h, cache["layers"], jnp.asarray(0, jnp.int32)),
            params["layers"])
    else:
        def cycle_fn(carry, xs):
            hh = carry
            layer_params, layer_cache = xs if has_cache else (xs, None)
            new_caches = []
            for i, kind in enumerate(pat):
                c_i = None if layer_cache is None else layer_cache[i]
                hh, nc = block_apply(kind, layer_params[i], hh, cfg,
                                     cache=c_i, mode=mode, pos=pos)
                new_caches.append(nc)
            out_cache = tuple(new_caches) if mode != "train" else None
            return hh, out_cache

        cycle = (jax.checkpoint(cycle_fn)
                 if (remat and mode == "train") else cycle_fn)

        if n_cycles > 0:
            xs = (params["layers"], cache["layers"]) if has_cache \
                else params["layers"]
            h, new_stack_cache = jax.lax.scan(cycle, h, xs)
        else:
            new_stack_cache = None

    new_tail_caches = []
    for i, kind in enumerate(tail):
        c_i = None if cache is None else cache["tail"][i]
        h, nc = block_apply(kind, params["tail"][i], h, cfg,
                            cache=c_i, mode=mode, pos=pos)
        new_tail_caches.append(nc)

    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    if features_only:
        return h, None
    logits = lm_head(params, cfg, h)
    new_cache = None
    if mode != "train":
        new_cache = {"layers": new_stack_cache, "tail": tuple(new_tail_caches)}
    return logits, new_cache


def lm_head(params: dict, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    """Final-norm'd features ``h (B, S, d)`` -> logits ``(B, S, Vp)``.

    Shared by ``forward`` and the pipeline's last stage
    (runtime.pipeline), so the tied-TTM reconstruct path and the sharding
    constraints cannot diverge between the two.
    """
    with jax.named_scope(HEAD):
        if cfg.tie_embeddings:
            if isinstance(params["embed"], dict):
                table = params["embed"]["table"]
            else:
                # Tied TTM head: materialize the table *transiently* (activation,
                # not a stored param) — the build is O(V·H·r) FLOPs, negligible
                # next to the logits GEMM, and shards on vocab under TP.
                from repro.core.meshctx import constrain
                emb = params["embed"]
                table = constrain(
                    ttm_reconstruct(emb.cores, emb.spec),
                    "model", None)[: cfg.vocab_padded, : cfg.d_model].astype(h.dtype)
            logits = jnp.einsum("bsd,vd->bsv", h, table,
                                preferred_element_type=jnp.float32).astype(h.dtype)
        else:
            logits = linear_apply(params["head"], h, flow=cfg.tt.flow,
                                  fused_bwd=cfg.tt.fused_bwd)
        # Vocab-shard the logits explicitly: with a TT head the weight factors
        # are replicated, so GSPMD has no lineage to shard the (B, S, V) output
        # — unconstrained it replicates ~40 GB/device of logits on 150k-vocab
        # archs (EXPERIMENTS.md §Perf, technique cell iteration).
        return meshctx_constrain(logits, ("pod", "data"), None, "model")


def token_nll(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-token ``-log p(label)`` in f32, TP-safe.

    The gold logit is extracted with a masked sum over the vocab axis (not
    ``take_along_axis``): under TP the vocab axis is sharded, and a gather
    along a sharded axis would make GSPMD all-gather the full (B, S, V)
    logits — the masked sum keeps everything local + one scalar-per-token
    all-reduce.
    """
    with jax.named_scope(HEAD):
        logits = logits.astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        gold = jnp.sum(jnp.where(vocab_iota == labels[..., None], logits, 0.0), axis=-1)
        return logz - gold


def loss_fn(params: dict, cfg: ModelConfig, batch: dict, *, remat: bool = True):
    """Next-token cross entropy.  batch: tokens (B,S), labels (B,S), mask."""
    logits, _ = forward(params, cfg, batch["tokens"],
                        patches=batch.get("patches"), mode="train", remat=remat)
    nll = token_nll(logits, batch["labels"])
    mask = batch.get("mask")
    if mask is not None:
        nll = nll * mask
        denom = jnp.maximum(mask.sum(), 1.0)
    else:
        denom = float(nll.size)
    return nll.sum() / denom


# ---------------------------------------------------------------------------
# Cache construction (decode shapes).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CacheLeaf:
    """Descriptor of one cache buffer: shape is WITHOUT the stacked-cycle
    leading dim; role drives the sharding rule (runtime.sharding)."""

    shape: tuple[int, ...]
    dtype: Any
    role: str  # "kv" (B,S,KV,dh) | "conv" (B,W,C) | "state" (B,...) | "vec" (B,D)


def _block_cache_desc(kind: str, cfg: ModelConfig, batch: int, seq_len: int,
                      kv_repeat: int, dtype) -> dict | None:
    if kind in ("attn", "attn_moe", "attn_local"):
        kvh = cfg.n_kv_heads * kv_repeat
        s = seq_len if kind != "attn_local" else min(cfg.window or seq_len, seq_len)
        shape = (batch, s, kvh, cfg.d_head)
        return {"k": CacheLeaf(shape, dtype, "kv"), "v": CacheLeaf(shape, dtype, "kv")}
    if kind == "ssm":
        s = cfg.ssm
        d_in = s.d_inner(cfg.d_model)
        h = s.n_heads(cfg.d_model)
        return {
            "conv": CacheLeaf((batch, s.d_conv - 1, d_in + 2 * s.d_state), dtype, "conv"),
            "ssd": CacheLeaf((batch, h, s.head_dim, s.d_state), jnp.float32, "state"),
        }
    if kind == "rec":
        return {
            "conv": CacheLeaf((batch, 3, cfg.d_model), dtype, "conv"),
            "h": CacheLeaf((batch, cfg.d_model), jnp.float32, "vec"),
        }
    raise ValueError(kind)


def cache_descriptors(cfg: ModelConfig, batch: int, seq_len: int, *,
                      kv_repeat: int = 1, dtype=None):
    """(stacked_desc, tail_desc, n_cycles) — leaves are CacheLeaf (no cycle dim)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    n_cycles, tail = _cycle_layout(cfg)
    pat = cfg.hybrid_pattern
    per_cycle = tuple(
        _block_cache_desc(kind, cfg, batch, seq_len, kv_repeat, dtype)
        for kind in pat) if n_cycles > 0 else None
    tail_desc = tuple(
        _block_cache_desc(kind, cfg, batch, seq_len, kv_repeat, dtype)
        for kind in tail)
    return per_cycle, tail_desc, n_cycles


def _is_cache_leaf(x):
    return isinstance(x, CacheLeaf)


def map_cache(fn, cfg: ModelConfig, batch: int, seq_len: int, *,
              kv_repeat: int = 1, dtype=None):
    """Build a cache-shaped pytree: ``fn(CacheLeaf, stacked_cycles|None)``."""
    per_cycle, tail_desc, n_cycles = cache_descriptors(
        cfg, batch, seq_len, kv_repeat=kv_repeat, dtype=dtype)
    stacked = None
    if per_cycle is not None:
        stacked = jax.tree.map(lambda leaf: fn(leaf, n_cycles), per_cycle,
                               is_leaf=_is_cache_leaf)
    tail = jax.tree.map(lambda leaf: fn(leaf, None), tail_desc,
                        is_leaf=_is_cache_leaf)
    return {"layers": stacked, "tail": tail}


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, *,
               kv_repeat: int = 1, dtype=None) -> dict:
    def make(leaf: CacheLeaf, cycles):
        shape = leaf.shape if cycles is None else (cycles,) + leaf.shape
        return jnp.zeros(shape, leaf.dtype)
    return map_cache(make, cfg, batch, seq_len, kv_repeat=kv_repeat, dtype=dtype)


def cache_struct(cfg: ModelConfig, batch: int, seq_len: int, *,
                 kv_repeat: int = 1, dtype=None) -> dict:
    """ShapeDtypeStruct tree (dry-run input stand-in: no allocation)."""
    def make(leaf: CacheLeaf, cycles):
        shape = leaf.shape if cycles is None else (cycles,) + leaf.shape
        return jax.ShapeDtypeStruct(shape, leaf.dtype)
    return map_cache(make, cfg, batch, seq_len, kv_repeat=kv_repeat, dtype=dtype)


# ---------------------------------------------------------------------------
# Introspection.
# ---------------------------------------------------------------------------


def num_params(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


def param_bytes(params) -> int:
    return sum(int(x.size * x.dtype.itemsize) for x in jax.tree.leaves(params))
