"""The dense transformer family (encoder or causal decoder): its plain
reference layers and the least FLOPs of its training step.

Layer equations:

    x = E[tokens] (+ P[:S] with learned positions)
    per layer:  x += Wo attn(RoPE? (Wq n1(x)), RoPE? (Wk n1(x)), Wv n1(x))
                x += FFN(n2(x))
    logits = n(x) E^T (tied table) or Whead n(x);   loss = mean token NLL

with RMSNorm ``n(x) = x / sqrt(mean(x^2) + eps) * (1 + g)``, GQA (query
head h reads KV head h // (H / KV)), softmax scale ``1 / sqrt(d_head)``,
FFN ``down(gelu_tanh(up h))`` or ``down(silu(gate h) * up h)``.  The
vocabulary is padded to a multiple of 256 rows and the padded rows take part
in the softmax, as in the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import (attention, dot, gelu_tanh, padded_vocab,
                             rms_norm, rope, silu, tt_apply, ttm_table)
from bench.work import attention as attn_work
from bench.work import lm_head, tt_ffn, tt_linear, ttm_embed, update
from bench.workcount import itemsize, mid_rank, vocab_padded


# --- the reference -------------------------------------------------------------

def _cores(w, owner):
    n = sum(1 for p in w if p.startswith(owner + ".cores["))
    return [w[f"{owner}.cores[{i}]"] for i in range(n)]


def _layer(m, rnd, lw, x):
    B, S, _ = x.shape
    H, KV, D = m["n_heads"], m["n_kv_heads"], m["d_head"]
    h = rms_norm(x, lw["norm1"], m["norm_eps"])

    def lin(name, y, out_dim, in_dim):
        return tt_apply(lw[name], y, rnd, lw.get(name + ".bias"),
                        out_dim, in_dim)

    d = m["d_model"]
    q = lin("q", h, H * D, d).reshape(B, S, H, D)
    k = lin("k", h, KV * D, d).reshape(B, S, KV, D)
    v = lin("v", h, KV * D, d).reshape(B, S, KV, D)
    if m["pos_embed"] == "rope":
        q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    x = x + lin("o", attention(q, k, v, m["causal"], rnd), d, H * D)
    h2 = rms_norm(x, lw["norm2"], m["norm_eps"])
    f = m["d_ff"]
    if m["mlp_gated"]:
        act = silu if m["act"] == "silu" else gelu_tanh
        hid = act(lin("gate", h2, f, d)) * lin("up", h2, f, d)
    else:
        act = gelu_tanh if m["act"] == "gelu" else silu
        hid = act(lin("up", h2, f, d))
    return x + lin("down", hid, d, f)


def _split_layers(w):
    pre = "['layers'][0]"
    lw = {}
    for p, a in w.items():
        if not p.startswith(pre):
            continue
        rest = p[len(pre):]
        if rest.startswith("['attn']") or rest.startswith("['mlp']"):
            rest = rest.split("]", 1)[1]            # drop the block key
        name = rest.replace("['", "").replace("']", "")
        if ".cores[" in name:
            owner, idx = name.split(".cores[")
            lw.setdefault(owner, {})[int(idx[:-1])] = a
        else:
            lw[name] = a
    return {k: ([v[i] for i in range(len(v))] if isinstance(v, dict) else v)
            for k, v in lw.items()}


def loss(m: dict, rnd, w: dict, batch: dict):
    """Mean token NLL of ``batch`` under weights ``w`` (by the program's
    parameter names); ``rnd`` rounds every matrix operand."""
    tokens, labels, mask = batch["tokens"], batch["labels"], batch["mask"]
    table = ttm_table(_cores(w, "['embed']"), rnd)
    x = table[tokens][..., : m["d_model"]]
    if m["pos_embed"] == "learned":
        x = x + w["['pos_table']"][: tokens.shape[1]]
    layer = jax.checkpoint(lambda lw, c: _layer(m, rnd, lw, c))
    x, _ = jax.lax.scan(lambda c, lw: (layer(lw, c), None), x,
                        _split_layers(w))
    x = rms_norm(x, w["['final_norm']"], m["norm_eps"])
    vp = padded_vocab(m["vocab_size"])
    if m["tie_embeddings"]:
        logits = dot("bsd,vd->bsv", x, table[:vp, : m["d_model"]], rnd)
    else:
        logits = tt_apply(_cores(w, "['head']"), x, rnd, None, vp,
                          m["d_model"])
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


# --- the step's least FLOPs ------------------------------------------------------

def step_flops(config: dict, traffic: dict, layout) -> int:
    """Every TT linear and FFN block forward and backward, attention over
    the pairs the mask keeps, the tied head or TT head, the TTM embedding,
    and the optimizer's update.  The K-independent half-factor builds of
    the TT linears are left out (under 0.1% of a step at these shapes), so
    the count never exceeds what the step needs."""
    m = config["model"]
    B, S = traffic["batch"], traffic["seq"]
    K, L, d, f = B * S, m["num_layers"], m["d_model"], m["d_ff"]
    H, KV, D = m["n_heads"], m["n_kv_heads"], m["d_head"]
    item = itemsize(config)
    total = 0

    def linear(M, N):
        r = mid_rank(config, M, N)
        return (tt_linear.forward(K, M, N, r, item)[0]
                + tt_linear.backward(K, M, N, r, item)[0])

    for _ in range(L):
        for M, N in ((H * D, d), (KV * D, d), (KV * D, d), (d, H * D)):
            total += linear(M, N)
        ranks = [mid_rank(config, f, d), mid_rank(config, d, f)]
        if m["mlp_gated"]:
            ranks.append(mid_rank(config, f, d))
        total += tt_ffn.forward(K, d, f, ranks, item)[0]
        total += tt_ffn.backward(K, d, f, ranks, item)[0]
        total += attn_work.forward(B, H, KV, S, D, m["causal"], item)[0]
        total += attn_work.backward(B, H, KV, S, D, m["causal"], item)[0]

    V = m["vocab_size"]
    if m["tie_embeddings"]:
        total += lm_head.flops(K, V, d)
    else:
        total += linear(vocab_padded(V), d)
    embed = [s[1:] if len(s) == 5 else s for p, s, _ in layout
             if p.startswith("['embed'].cores[")]
    total += ttm_embed.flops(embed, K)
    n = sum(_size(s) for _, s, _ in layout)
    return total + update.work(traffic["optimizer"], n, item)[0]


def _size(shape) -> int:
    out = 1
    for x in shape:
        out *= x
    return out
