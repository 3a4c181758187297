"""Serving driver: continuous-batched paged decode (attention families) or
lockstep dense-cache decode (ssm/rec hybrids).

Attention-family configs (every block kind in {attn, attn_moe, attn_local})
run the PAGED path — the serving stack this repo's decode kernels target:

  * ``runtime.PagedDecodeEngine`` — flash-decode Pallas attention against a
    paged KV cache, decode-shape BTT linear/FFN kernels, per-slot positions;
  * ``runtime.Scheduler`` — FIFO continuous batching: solo prefill on
    admission, one batched decode step over every running slot, retirement
    on EOS/budget, the freed slot refilled from the queue head.

Families with recurrent state (ssm/rec hybrids) keep the legacy lockstep
path: batched prefill, cache conversion to the decode layout (ring
placement for windowed layers, KV-head repeat to the TP degree),
token-by-token decode.

Usage (CPU examples):
  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --tt \
      --kernel-flow --scale-down --batch 4 --prompt-len 64 --gen 32
  PYTHONPATH=src python -m repro.launch.serve --arch recurrentgemma-2b \
      --scale-down --batch 4 --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import lm_batch
from repro.kernels.flash_decode import DEFAULT_PAGE_SIZE
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_decode_step, make_prefill, prepare_decode_cache
from repro.models.transformer import init_params, num_params
from repro.runtime import kv_repeat_for_mesh
from repro.runtime.decode_engine import (PagedDecodeEngine,
                                         finite_logit_rows, paged_supported)
from repro.runtime.scheduler import Request, Scheduler


def build(args):
    """Same config construction as ``launch.train.build`` — serving runs
    the flags it was trained with (tt rank, kernel flow, fused attn/ffn)."""
    cfg = get_config(args.arch)
    if args.scale_down:
        cfg = cfg.scaled_down()
    if args.tt:
        cfg = cfg.with_tt(mode="tt", rank=args.tt_rank,
                          embed_rank=args.tt_rank)
    if args.kernel_flow:
        cfg = cfg.with_tt(flow="kernel")
    if args.fused_attn is not None:
        cfg = cfg.with_fused_attn(args.fused_attn)
    if args.fused_ffn is not None:
        cfg = cfg.with_fused_ffn(args.fused_ffn)
    if args.fp32:
        cfg = dataclasses.replace(cfg, dtype="float32")
    return cfg


def _sampler(args, vocab: int):
    """Per-request sampling closure.  The key folds in (rid, n_generated)
    only — NEVER the slot or batch composition — so a request's sampled
    stream is identical whether it decodes solo or continuously batched."""
    base = jax.random.PRNGKey(args.seed + 1)

    def sample(logits_row, rid: int, n: int) -> int:
        lg = jnp.asarray(logits_row)[:vocab].astype(jnp.float32)
        if args.temperature <= 0:
            return int(jnp.argmax(lg))
        k = jax.random.fold_in(jax.random.fold_in(base, rid), n)
        return int(jax.random.categorical(k, lg / args.temperature))

    return sample


# ---------------------------------------------------------------------------
# Paged continuous-batching path.
# ---------------------------------------------------------------------------


def serve_paged(cfg, params, prompts, *, gen: int, max_concurrency: int,
                page_size: int = DEFAULT_PAGE_SIZE, fused_decode: bool = True,
                sample=None, eos_id: int | None = None,
                max_len: int | None = None, interpret: bool | None = None,
                max_queue: int | None = None,
                deadline_steps: int | None = None,
                chaos=None, quiet: bool = False) -> dict:
    """Run ``prompts`` (list of token lists) through the scheduler + paged
    engine until every request retires.  Reusable from tests/benchmarks;
    ``main`` wraps it with flag parsing.

    Hardening knobs: ``max_queue`` bounds the waiting queue (overflow is
    shed at submit), ``deadline_steps`` is the per-request TTL in
    scheduler steps (expired requests are timeout-evicted and their slot
    released), and any slot whose logits come back non-finite — a
    numerics fault or a poisoned request — is evicted instead of crashing
    the batch (``poisoned`` in the report).  ``chaos`` is an optional
    fault injector with a ``poison_logits(logits, decode_step)`` method
    (``runtime.chaos.LogitPoison``)."""
    if sample is None:
        def sample(lg, rid, n):  # greedy default
            return int(jnp.argmax(jnp.asarray(lg).astype(jnp.float32)))
    if max_len is None:
        max_len = max(len(p) for p in prompts) + gen
    eng = PagedDecodeEngine(cfg, params, page_size=page_size,
                            max_concurrency=max_concurrency, max_len=max_len,
                            fused_decode=fused_decode, interpret=interpret)
    sched = Scheduler(max_concurrency, max_queue=max_queue,
                      default_deadline=deadline_steps)
    sched.submit_all([Request(rid=i, prompt=list(map(int, p)), max_new=gen,
                              eos_id=eos_id) for i, p in enumerate(prompts)])

    t0 = time.time()
    t_prefill = 0.0
    decode_steps = 0
    poisoned = 0
    while sched.has_work():
        for req, slot in sched.expire():
            if slot is not None:  # was running: free its KV pages
                eng.release(slot)
        for req in sched.admit(
                can_admit=lambda r: eng.can_admit(len(r.prompt))):
            tp = time.time()
            lg = eng.prefill(req.slot, req.prompt)
            jax.block_until_ready(lg)
            t_prefill += time.time() - tp
            slot = req.slot
            if not finite_logit_rows(np.asarray(lg)[None])[0]:
                sched.evict(slot)
                eng.release(slot)
                poisoned += 1
                continue
            if sched.observe(slot, sample(lg, req.rid, 0)) is not None:
                eng.release(slot)
        running = sched.running()
        if running:
            toks = np.zeros((max_concurrency,), np.int32)
            poss = np.zeros((max_concurrency,), np.int32)
            for r in running:
                toks[r.slot] = r.out[-1]
                poss[r.slot] = len(r.prompt) + len(r.out) - 1
            logits = eng.decode_step(toks, poss)
            logits = np.asarray(logits)
            if chaos is not None:
                logits = chaos.poison_logits(logits, decode_steps)
            decode_steps += 1
            finite = finite_logit_rows(logits)
            for r in list(running):
                slot = r.slot
                if not finite[slot]:
                    # Poisoned slot: evict this request, keep the batch
                    # alive — the other lanes' math is row-independent,
                    # so their tokens are unaffected.
                    sched.evict(slot)
                    eng.release(slot)
                    poisoned += 1
                    continue
                tok = sample(logits[slot], r.rid, len(r.out))
                if sched.observe(slot, tok) is not None:
                    eng.release(slot)
        sched.end_step()

    t_total = time.time() - t0
    t_decode = max(t_total - t_prefill, 1e-9)
    rep = sched.report()
    rep["decode_steps"] = decode_steps
    rep["poisoned"] = poisoned
    by_rid = sorted(sched.retired, key=lambda r: r.rid)
    toks_per_s = rep["tokens_out"] / t_decode
    if not quiet:
        print(f"[serve] paged: {rep['finished']} finished, "
              f"{rep['evicted']} evicted, {rep['timed_out']} timed out, "
              f"{rep['shed']} shed in {rep['steps']} steps "
              f"({decode_steps} decode); prefill {t_prefill*1e3:.0f} ms, "
              f"decode {t_decode*1e3:.0f} ms ({toks_per_s:.1f} tok/s); "
              f"max wait {rep['max_wait_steps']} steps")
    return {
        "requests": by_rid,
        "tokens": np.asarray([r.out for r in by_rid
                              if len(r.out) == gen], np.int32),
        "t_prefill": t_prefill,
        "t_decode": t_decode,
        "tokens_per_sec": toks_per_s,
        "report": rep,
        "engine": eng,
        "mode": "paged",
    }


def _main_paged(cfg, args) -> dict:
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    B, P = args.batch, args.prompt_len
    mc = args.max_concurrency or B
    max_len = P + args.gen
    print(f"[serve] arch={cfg.name} tt={cfg.tt.mode} "
          f"params={num_params(params):,} mode=paged "
          f"fused_decode={args.fused_decode} page={args.page_size} "
          f"concurrency={mc}")
    prompts = np.asarray(
        lm_batch(args.seed, 0, B, P, cfg.vocab_size)["tokens"])
    out = serve_paged(cfg, params, [p.tolist() for p in prompts],
                      gen=args.gen, max_concurrency=mc,
                      page_size=args.page_size,
                      fused_decode=args.fused_decode,
                      sample=_sampler(args, cfg.vocab_size),
                      max_len=max_len, max_queue=args.max_queue,
                      deadline_steps=args.deadline_steps)
    if args.ledger:
        from repro.core.memory_ledger import decode_step_ledger

        led = decode_step_ledger(cfg, batch=mc, max_len=max_len,
                                 page_size=args.page_size,
                                 fused=args.fused_decode)
        mb = 1 / 2**20
        print(f"[serve] DECODE ledger {led.total_bytes*mb:.3f} MB "
              f"(bram {led.pool_bytes('bram')*mb:.3f}, "
              f"uram {led.pool_bytes('uram')*mb:.3f}):")
        for e in led.entries:
            print(f"    {e.name:<18} {e.nbytes*mb:8.3f} MB [{e.pool}]  "
                  f"{e.note}")
    gen = out["tokens"]
    if gen.size:
        print(f"[serve] sample generation (request 0): "
              f"{gen[0][:16].tolist()}")
        assert np.isfinite(gen).all()
    return out


# ---------------------------------------------------------------------------
# Legacy lockstep dense-cache path (ssm/rec hybrid families).
# ---------------------------------------------------------------------------


def _main_dense(cfg, args) -> dict:
    mesh = make_host_mesh()
    kvr = kv_repeat_for_mesh(cfg, mesh)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    print(f"[serve] arch={cfg.name} tt={cfg.tt.mode} "
          f"params={num_params(params):,} mode=dense kv_repeat={kvr}")

    B, P = args.batch, args.prompt_len
    max_len = P + args.gen
    prompts = lm_batch(args.seed, 0, B, P, cfg.vocab_size)["tokens"]
    batch = {"tokens": jnp.asarray(prompts)}
    if cfg.frontend == "patch":
        batch["patches"] = jax.random.normal(
            jax.random.PRNGKey(1), (B, min(cfg.frontend_len, P), cfg.d_model),
            jnp.dtype(cfg.dtype))

    prefill = jax.jit(make_prefill(cfg))
    decode = jax.jit(make_decode_step(cfg))

    t0 = time.time()
    last_logits, pcache = prefill(params, batch)
    cache = prepare_decode_cache(cfg, pcache, P, max_len, kv_repeat=kvr)
    t_prefill = time.time() - t0

    sample = _sampler(args, cfg.vocab_size)

    def sample_batch(logits, n):
        return jnp.asarray([[sample(logits[b, -1], b, n)]
                            for b in range(B)], jnp.int32)

    tok = sample_batch(last_logits, 0)
    out_tokens = [np.asarray(tok)]
    t1 = time.time()
    for i in range(args.gen - 1):
        logits, cache = decode(params, cache, tok,
                               jnp.asarray(P + i, jnp.int32))
        tok = sample_batch(logits, i + 1)
        out_tokens.append(np.asarray(tok))
    jax.block_until_ready(tok)
    t_decode = time.time() - t1
    gen = np.concatenate(out_tokens, axis=1)
    print(f"[serve] prefill {B}x{P} in {t_prefill*1e3:.0f} ms; "
          f"decoded {args.gen} tokens in {t_decode*1e3:.0f} ms "
          f"({args.gen * B / max(t_decode, 1e-9):.1f} tok/s)")
    print(f"[serve] sample generation (batch 0): {gen[0][:16].tolist()}")
    assert np.isfinite(gen).all()
    return {"tokens": gen, "t_prefill": t_prefill, "t_decode": t_decode,
            "tokens_per_sec": args.gen * B / max(t_decode, 1e-9),
            "mode": "dense"}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="recurrentgemma-2b")
    ap.add_argument("--tt", action="store_true")
    ap.add_argument("--tt-rank", type=int, default=16)
    ap.add_argument("--scale-down", action="store_true")
    ap.add_argument("--fp32", action="store_true")
    ap.add_argument("--kernel-flow", action="store_true",
                    help="TT contractions through the Pallas kernel flow")
    ap.add_argument("--fused-attn", action=argparse.BooleanOptionalAction,
                    default=None)
    ap.add_argument("--fused-ffn", action=argparse.BooleanOptionalAction,
                    default=None)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fused-decode", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="decode-shape Pallas kernels (flash-decode "
                         "attention + BTT decode tiles); off = paged "
                         "pure-JAX reference path")
    ap.add_argument("--page-size", type=int, default=DEFAULT_PAGE_SIZE)
    ap.add_argument("--max-concurrency", type=int, default=None,
                    help="decode slots (default: --batch)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound the scheduler's waiting queue: a submit "
                         "that would overflow it is shed immediately "
                         "(counted in the report) instead of queueing "
                         "unboundedly under overload")
    ap.add_argument("--deadline-steps", type=int, default=None,
                    help="per-request TTL in scheduler steps: requests "
                         "not finished within the deadline of arrival "
                         "are timeout-evicted (waiting or running) and "
                         "their KV pages freed")
    ap.add_argument("--ledger", action="store_true",
                    help="print the DECODE-stage memory ledger")
    args = ap.parse_args(argv)

    cfg = build(args)
    if paged_supported(cfg):
        return _main_paged(cfg, args)
    return _main_dense(cfg, args)


if __name__ == "__main__":
    enable_compile_cache()
    main()
