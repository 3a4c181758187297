"""Plain reference of a training step: the model, its loss and gradient,
gradient clipping and the optimizer, in straightforward ``jax.numpy``.

It imports nothing of the program.  It reads the model from the
configuration's file under ``bench/configs``, its layers from the file of
the configuration's model family (``bench/models/<family>.py``, built of
the pieces below), and takes its weights from ``bench.weights`` (made from
the seed), keyed by the program's parameter names.  Every matrix product
runs at ``Precision.HIGHEST`` in float32, so on a TPU no product is rounded
to bfloat16.  Parameters are stored in the configuration's dtype between
steps, as the program stores them.

A TT matrix ``W (M, N)`` is ``A @ B`` with ``A`` the chain of its first d
cores and ``B`` the chain of its last d; ``y = (x B^T) A^T``.  A TTM table
is the chain of its cores over (vocab, hidden).

``lowp`` puts a control in the reference's place: every matrix operand
(and every cotangent that reaches one) is rounded to that dtype.  ``store``
stores the parameters in another dtype between steps.

``drop_half`` plants a fault: half of each batch is left out of the loss
(half of the rows, or half of the positions of a single row) and the mean
is taken over the rest.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _rounder(lowp: str | None):
    if lowp is None:
        return lambda x: x
    dt = jnp.dtype(lowp)
    return lambda x: x.astype(dt).astype(F32)


def dot(eq, a, b, rnd):
    return jnp.einsum(eq, rnd(a), rnd(b), precision=HI,
                      preferred_element_type=F32)


def tt_factors(cores, rnd):
    """``A (M, r)`` from the first half of the cores, ``B (r, N)`` from the
    second, each chained from its rank-1 end."""
    d = len(cores) // 2
    a = cores[0].reshape(cores[0].shape[1], cores[0].shape[2])
    for g in cores[1:d]:
        a = dot("pr,rms->pms", a, g, rnd).reshape(-1, g.shape[2])
    last = cores[-1]
    b = last.reshape(last.shape[0], last.shape[1])
    for g in cores[d:-1][::-1]:
        b = dot("rns,st->rnt", g, b, rnd).reshape(g.shape[0], -1)
    return a, b


def tt_apply(cores, x, rnd, bias=None, out_dim=None, in_dim=None):
    a, b = tt_factors(cores, rnd)
    if in_dim is not None and b.shape[1] != in_dim:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, b.shape[1] - in_dim)])
    t = dot("...n,rn->...r", x, b, rnd)
    y = dot("...r,mr->...m", t, a, rnd)
    if out_dim is not None:
        y = y[..., :out_dim]
    if bias is not None:
        y = y + bias
    return y


def ttm_table(cores, rnd):
    acc = cores[0].reshape(cores[0].shape[1:])          # (v, h, r)
    for f in cores[1:]:
        acc = dot("vhr,rwgs->vwhgs", acc, f, rnd)
        acc = acc.reshape(acc.shape[0] * acc.shape[1],
                          acc.shape[2] * acc.shape[3], acc.shape[4])
    return acc[..., 0]


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + g)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                      * (x + 0.044715 * x ** 3)))


def silu(x):
    return x * jax.nn.sigmoid(x)


def rope(x, theta):
    """``x (B, S, H, D)`` rotated by position, halves ``[x1, x2]``."""
    S, D = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, causal, rnd):
    """``q (B, S, H, D)``, ``k (B, S, KV, D)``, ``v (B, S, KV, Dv)`` ->
    ``(B, S, H Dv)``, softmax scale ``1 / sqrt(D)``; one KV head at a time."""
    B, S, H, D = q.shape
    KV, Dv = k.shape[2], v.shape[-1]
    qg = q.reshape(B, S, KV, H // KV, D).transpose(2, 0, 1, 3, 4)
    kt, vt = k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)
    keep = jnp.tril(jnp.ones((S, S), bool)) if causal else None

    @jax.checkpoint
    def one(args):
        qh, kh, vh = args                       # (B,S,G,D), (B,S,D), (B,S,Dv)
        s = dot("bqgd,bcd->bgqc", qh, kh, rnd) / math.sqrt(D)
        if keep is not None:
            s = jnp.where(keep, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return dot("bgqc,bcd->bqgd", p, vh, rnd)

    out = jax.lax.map(one, (qg, kt, vt))         # (KV, B, S, G, Dv)
    return out.transpose(1, 2, 0, 3, 4).reshape(B, S, H * Dv)


def padded_vocab(vocab: int) -> int:
    return (vocab + 255) // 256 * 256


class Reference:
    """Reference training steps for one configuration and traffic mix."""

    def __init__(self, config: dict, traffic: dict, *, lowp: str | None = None,
                 store: str | None = None, drop_half: bool = False):
        from bench import spec

        self.m = config["model"]
        self.model = spec.load_module("models", config["family"])
        self.traffic = traffic
        self.rnd = _rounder(lowp)
        self.drop_half = drop_half
        self.stored = jnp.dtype(store or self.m["dtype"])
        self._step = jax.jit(self._train_step)

    # --- model ---------------------------------------------------------

    def loss(self, w, batch):
        return self.model.loss(self.m, self.rnd, w, batch)

    # --- optimizer -------------------------------------------------------

    def lr(self, step):
        """launch.train's warmup-cosine schedule, peak ``lr``, floor 0.1."""
        t = self.traffic
        peak, warm, total = t["lr"], t["warmup_steps"], t["schedule_steps"]
        step = jnp.asarray(step, F32)
        rise = peak * step / max(warm, 1)
        prog = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
        cos = peak * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
        return jnp.where(step < warm, rise, cos)

    def _train_step(self, w, state, batch, step):
        wf = {p: a.astype(F32) for p, a in w.items()}
        loss, g = jax.value_and_grad(self.loss)(wf, batch)
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
        scale = jnp.minimum(1.0, 1.0 / jnp.maximum(gn, 1e-9))
        g = {p: x * scale for p, x in g.items()}
        lr = self.lr(step)
        if self.traffic["optimizer"] == "sgd":
            new = {p: (wf[p] - lr * g[p]).astype(self.stored) for p in w}
            return new, state, loss, g, gn
        b1, b2, eps = 0.9, 0.95, 1e-8
        t = jnp.asarray(step + 1, F32)
        m = {p: b1 * state["m"][p] + (1 - b1) * g[p] for p in w}
        v = {p: b2 * state["v"][p] + (1 - b2) * g[p] * g[p] for p in w}
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        new = {p: (wf[p] - lr * (m[p] / bc1) / (jnp.sqrt(v[p] / bc2) + eps))
               .astype(self.stored) for p in w}
        return new, {"m": m, "v": v}, loss, g, gn

    # --- the readings ----------------------------------------------------

    def batch(self, b: dict) -> dict:
        """The batch as the reference sees it (``drop_half`` applied)."""
        mask = np.array(b["mask"], np.float32)
        if self.drop_half:
            B, S = mask.shape
            if B > 1:
                mask[B // 2:] = 0.0
            else:
                mask[:, S // 2:] = 0.0
        return {"tokens": jnp.asarray(b["tokens"]),
                "labels": jnp.asarray(b["labels"]), "mask": jnp.asarray(mask)}

    def run(self, weights: dict, batches: list[dict]) -> dict:
        """Steps over ``batches`` from ``weights``: each step's loss and
        global gradient norm before clipping, each leaf's norm of the first
        (clipped) gradient, and each leaf's norm of the parameters' change
        after the last step."""
        w = {p: a.astype(self.stored) for p, a in weights.items()}
        w0 = w
        state = {"m": {p: jnp.zeros(a.shape, F32) for p, a in w.items()},
                 "v": {p: jnp.zeros(a.shape, F32) for p, a in w.items()}}
        losses, norms, first = [], [], None
        for i, b in enumerate(batches):
            w, state, loss, g, gn = self._step(w, state, self.batch(b), i)
            losses.append(float(loss))
            norms.append(float(gn))
            if first is None:
                first = leaf_norms(g)
        delta = leaf_norms({p: w[p].astype(F32) - w0[p].astype(F32) for p in w})
        return {"losses": losses, "grad_norms": norms, "first_grad": first,
                "delta": delta}


@jax.jit
def _norms(tree):
    return {p: jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))) for p, x in tree.items()}


def leaf_norms(tree: dict) -> dict:
    return {p: float(v) for p, v in jax.device_get(_norms(tree)).items()}
