"""Numerics sentry + escalation policy for unattended training.

The paper's setting is single-batch training on an edge device with nobody
watching: an fp8 overflow, a loss spike, or a corrupted gradient must be
absorbed by the loop itself, not by an operator restarting the job.  This
module is that loop armor, split across the jit boundary:

**Inside the jitted step** (:func:`apply_guarded_update`): ONE fused
reduction — the f32 sum-of-squares over the (tier-cast) gradient tree —
serves simultaneously as

  * the global grad norm (the reported metric and the clip denominator;
    no second reduction),
  * the all-finite probe: NaN/Inf anywhere in the tree propagates into
    the scalar, so ``isfinite(gnorm) & isfinite(loss)`` covers every leaf
    with zero per-leaf host sync,
  * the skip-step mask: the optimizer update runs unconditionally, then a
    ``jnp.where(ok, new, old)`` select on params AND the full optimizer
    state discards it when the probe fails — moments, sketches
    (``vs``/``ms``), quantized masters (``pq``/``ps``) and the step
    counter all stay exactly at their pre-step values, for every state
    layout, without the builder knowing which layout it got.

It also computes the quant-saturation sentinel: for a scaled grad tier
(fp8_e5m2) the per-tensor max-abs scale means nothing ever clips at qmax —
the real hazard is the dual, an outlier inflating the scale until the
bulk of the tensor UNDERFLOWS to zero (``core.quant.lost_fraction``).
Both the fp8 and bf16 casts are computed and selected by a control scalar
(``grad_bf16``), so the host can escalate the tier mid-run without a
retrace.

**On the host** (:class:`TrainGuard`): an EWMA loss/grad-norm anomaly
detector (two ``StragglerMonitor`` instances — the same statistics shape
that flags slow steps flags spiky ones) driving the escalation ladder

    skip-step  ->  lr backoff  ->  rollback to last-good state

Nonfinite steps are true skips (masked in-jit, detected from the metrics
after the fact); finite spikes are flagged one step late, which is what
the lr backoff (an ``lr_scale`` leaf in the optimizer state — see
``optim.optimizers._scaled_lr``) and, after K consecutive bad steps, the
rollback to the last in-memory good snapshot (or the newest VALID on-disk
checkpoint, ``checkpoint.restore_latest_valid``) are for.

The chaos harness (``runtime.chaos``) injects faults through the same
``ctrl`` dict this module consumes, so every path here has a
deterministic, reproducible test (tests/test_robustness.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.runtime.straggler import StragglerMonitor
from repro.tracing import UPDATE

__all__ = ["GuardPolicy", "TrainGuard", "guard_controls",
           "apply_guarded_update", "make_guarded_step"]

# Actions TrainGuard.observe reports (one per step, most severe wins).
OK, SKIP, BACKOFF, ROLLBACK = "ok", "skip", "backoff", "rollback"


def guard_controls(*, fault_add: float = 0.0, grad_bf16: bool = False,
                   guard_on: bool = True) -> dict:
    """The per-step control scalars the guarded step consumes.

    All three are () device arrays, NOT Python values, so flipping them
    never retraces the jitted step:

    * ``fault_add``  — chaos-injection term added to one gradient element
      (0.0 in production; NaN/Inf/1e28 under ``runtime.chaos``).
    * ``grad_bf16``  — grad-tier escalation: select the bf16 round-trip
      instead of the configured fp8 tier.
    * ``guard_on``   — False disables the skip-step mask (the unguarded
      baseline the robustness tests diverge on purpose).
    """
    return {
        "fault_add": jnp.asarray(fault_add, jnp.float32),
        "grad_bf16": jnp.asarray(grad_bf16, jnp.bool_),
        "guard_on": jnp.asarray(guard_on, jnp.bool_),
    }


def apply_guarded_update(opt, loss, grads, params, opt_state, ctrl, *,
                         grad_fmt: str = "float32", clip_norm: float = 1.0):
    """Shared guarded tail of a training step (runs inside jit).

    ``(loss, grads)`` are this step's raw f32 loss/gradients; ``ctrl`` is
    a :func:`guard_controls` dict.  Applies, in order: chaos fault
    injection, the grad-tier round-trip (+ escalation select + saturation
    sentinel), the single fused norm/finite reduction, global-norm
    clipping, ``opt.update``, and the skip-step select.  Returns
    ``(params, opt_state, metrics)`` with metrics
    ``{loss, grad_norm, nonfinite, sat_frac, applied}``.  All of it runs
    under the ``update`` scope (``repro.tracing``).
    """
    from repro.core import quant

    if grad_fmt == "int8":
        raise ValueError("grad_dtype='int8' is unsupported: gradient "
                         "dynamic range collapses under a per-tensor "
                         "scale; use 'bfloat16' or 'fp8_e5m2'")

    with jax.named_scope(UPDATE):
        # Chaos injection: additive into ONE element of the first leaf.
        # Additive (not multiplicative) on purpose — a scaled tier rescales a
        # uniform multiply away, but a single huge outlier is exactly the
        # shape that blows up a per-tensor max-abs scale.
        leaves, tdef = jax.tree.flatten(grads)
        first = leaves[0].reshape(-1)
        first = first.at[0].add(ctrl["fault_add"].astype(first.dtype))
        leaves[0] = first.reshape(leaves[0].shape)
        grads = jax.tree.unflatten(tdef, leaves)

        # Grad tier: both casts live in the graph; grad_bf16 selects at run
        # time (elementwise where on a () predicate — no retrace, no branch).
        if grad_fmt == "float32":
            sat_frac = jnp.float32(0.0)
        elif quant.needs_scale(grad_fmt):
            lo = jax.tree.map(lambda g: quant.cast_format(g, grad_fmt), grads)
            hi = jax.tree.map(lambda g: quant.cast_format(g, "bfloat16"), grads)
            fracs = [quant.lost_fraction(g, l) for g, l in
                     zip(jax.tree.leaves(grads), jax.tree.leaves(lo))]
            sat_frac = jnp.max(jnp.stack(fracs))
            esc = ctrl["grad_bf16"]
            grads = jax.tree.map(lambda l, h: jnp.where(esc, h, l), lo, hi)
        else:  # bfloat16: cast-only round trip, nothing to escalate to
            sat_frac = jnp.float32(0.0)
            grads = jax.tree.map(lambda g: quant.cast_format(g, grad_fmt), grads)

        # ONE reduction: grad norm == finite probe == clip denominator.
        sumsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree.leaves(grads))
        gnorm = jnp.sqrt(sumsq)
        finite = jnp.isfinite(gnorm) & jnp.isfinite(loss)
        ok = finite | jnp.logical_not(ctrl["guard_on"])

        if clip_norm:
            cscale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-9))
            grads = jax.tree.map(
                lambda g: (g.astype(jnp.float32) * cscale).astype(g.dtype),
                grads)

        new_params, new_state = opt.update(grads, params, opt_state,
                                           opt_state["step"])
        # Skip-step: masked select on params AND the full state tree.  Old
        # and new leaves agree in shape/dtype for every layout (dense m/v,
        # sketched vs/ms, quantized pq/ps, lr_scale), so one tree.map keeps
        # the whole optimizer consistent on a skipped step — including NOT
        # advancing the bias-correction step counter.
        sel = lambda n, o: jnp.where(ok, n, o)
        params = jax.tree.map(sel, new_params, params)
        opt_state = jax.tree.map(sel, new_state, opt_state)
        metrics = {
            "loss": loss,
            "grad_norm": gnorm,
            "nonfinite": 1.0 - finite.astype(jnp.float32),
            "sat_frac": sat_frac,
            "applied": ok.astype(jnp.float32),
        }
        return params, opt_state, metrics


def make_guarded_step(loss_of: Callable[[Any, Any], jax.Array], opt, *,
                      grad_fmt: str = "float32", clip_norm: float = 1.0):
    """Generic guarded step over any ``loss_of(params, batch)`` scalar loss:
    ``(params, opt_state, batch, ctrl) -> (params, opt_state, metrics)``.
    The model-config-aware equivalent lives in ``launch.steps``
    (``make_train_step(..., guard=True)``); this builder is for tests,
    benchmarks, and custom losses (e.g. the ATIS task head)."""

    def step(params, opt_state, batch, ctrl):
        loss, grads = jax.value_and_grad(loss_of)(params, batch)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        return apply_guarded_update(opt, loss, grads, params, opt_state,
                                    ctrl, grad_fmt=grad_fmt,
                                    clip_norm=clip_norm)

    return step


@dataclasses.dataclass(frozen=True)
class GuardPolicy:
    """Escalation-ladder knobs (host side; all thresholds in steps)."""

    spike_z: float = 4.0        # EWMA z-score that flags a loss/gnorm spike
    alpha: float = 0.05         # EWMA weight (StragglerMonitor)
    warmup: int = 8             # samples before spike flagging starts
    backoff_after: int = 2      # consecutive bad steps -> lr backoff
    backoff_factor: float = 0.5
    min_lr_scale: float = 1.0 / 16.0
    recover_after: int = 20     # consecutive good steps -> one recovery step
    recover_factor: float = 2.0
    rollback_after: int = 4     # consecutive bad steps -> rollback
    snapshot_every: int = 20    # good steps between in-memory snapshots
    sat_threshold: float = 0.25  # grad-tier underflow fraction that counts
    sat_after: int = 2          # consecutive saturated steps -> bf16 tier


class TrainGuard:
    """Host-side controller around a guarded train step.

    Wiring (see ``launch.train`` for the full loop)::

        guard = TrainGuard(policy, manager=mgr, template=tmpl)
        opt_state = guard.attach(opt_state)          # adds lr_scale leaf
        step = jax.jit(make_train_step(cfg, opt, guard=True))
        for i in range(steps):
            p, s, metrics = step(p, s, batch, guard.controls())
            p, s, action = guard.observe(i, metrics, p, s)

    ``observe`` syncs the four metric scalars to host (the same sync the
    loop's loss print already pays), updates the EWMA monitors, and walks
    the ladder.  Rollback prefers the in-memory last-good snapshot and
    falls back to the newest checkpoint that passes CRC verification.
    """

    def __init__(self, policy: GuardPolicy | None = None, *,
                 manager=None, template=None):
        self.policy = policy or GuardPolicy()
        p = self.policy
        mon = lambda: StragglerMonitor(alpha=p.alpha, z_threshold=p.spike_z,
                                       warmup=p.warmup,
                                       escalate_after=10**9)
        self.loss_mon = mon()
        self.gnorm_mon = mon()
        self.manager = manager
        self.template = template
        self.lr_scale = 1.0
        self.grad_bf16 = False
        self.consecutive_bad = 0
        self.good_run = 0
        self.sat_run = 0
        self._last_good: tuple[int, Any, Any] | None = None
        self.counters = {"skipped": 0, "flagged": 0, "backoffs": 0,
                         "recoveries": 0, "rollbacks": 0, "escalations": 0,
                         "snapshots": 0}

    # -- jit-side plumbing ------------------------------------------------

    def attach(self, opt_state: dict) -> dict:
        """Add the guard's ``lr_scale`` leaf to a fresh optimizer state
        (and to the eval_shape template — checkpoints include it)."""
        state = dict(opt_state)
        state["lr_scale"] = jnp.asarray(self.lr_scale, jnp.float32)
        return state

    def controls(self, *, fault_add: float = 0.0) -> dict:
        """This step's control scalars (chaos passes ``fault_add``)."""
        return guard_controls(fault_add=fault_add, grad_bf16=self.grad_bf16,
                              guard_on=True)

    def _set_lr_scale(self, opt_state):
        state = dict(opt_state)
        state["lr_scale"] = jnp.asarray(self.lr_scale, jnp.float32)
        return state

    # -- the ladder -------------------------------------------------------

    def observe(self, step: int, metrics: dict, params, opt_state):
        """Digest one step's metrics; returns (params, opt_state, action).

        ``action`` is one of ``"ok" | "skip" | "backoff" | "rollback"``.
        params/opt_state pass through unchanged except on rollback.
        """
        pol = self.policy
        nonfinite = float(metrics["nonfinite"]) > 0.0
        sat = float(metrics["sat_frac"])

        # Saturation sentinel: independent of the bad-step ladder.  The
        # tier cast is destroying the gradient signal even though every
        # value is finite — escalate to bf16 before training stalls.
        if not self.grad_bf16 and sat >= pol.sat_threshold:
            self.sat_run += 1
            if self.sat_run >= pol.sat_after:
                self.grad_bf16 = True
                self.counters["escalations"] += 1
        else:
            self.sat_run = 0

        if nonfinite:
            bad = True
            self.counters["skipped"] += 1  # in-jit mask already held state
        else:
            # Feed ONLY finite samples to the EWMA stats — a NaN would
            # poison the mean and disarm the detector permanently.
            spike = self.loss_mon.observe(float(metrics["loss"]))
            spike |= self.gnorm_mon.observe(float(metrics["grad_norm"]))
            bad = spike
            if spike:
                self.counters["flagged"] += 1

        if bad:
            self.consecutive_bad += 1
            self.good_run = 0
            action = SKIP
            if self.consecutive_bad >= pol.rollback_after:
                params, opt_state = self._rollback(params, opt_state)
                self.consecutive_bad = 0
                action = ROLLBACK
            elif self.consecutive_bad >= pol.backoff_after:
                if self.lr_scale > pol.min_lr_scale:
                    self.lr_scale = max(self.lr_scale * pol.backoff_factor,
                                        pol.min_lr_scale)
                    self.counters["backoffs"] += 1
                    opt_state = self._set_lr_scale(opt_state)
                action = BACKOFF
            return params, opt_state, action

        self.consecutive_bad = 0
        self.good_run += 1
        if self.lr_scale < 1.0 and self.good_run % pol.recover_after == 0:
            self.lr_scale = min(1.0, self.lr_scale * pol.recover_factor)
            self.counters["recoveries"] += 1
            opt_state = self._set_lr_scale(opt_state)
        if self._last_good is None or self.good_run % pol.snapshot_every == 0:
            self._snapshot(step, params, opt_state)
        return params, opt_state, OK

    def _snapshot(self, step: int, params, opt_state) -> None:
        # Host copies (device_get materializes fresh numpy), so donation
        # and in-place device updates can never corrupt the snapshot.
        self._last_good = (step, jax.device_get(params),
                           jax.device_get(opt_state))
        self.counters["snapshots"] += 1

    def _rollback(self, params, opt_state):
        self.counters["rollbacks"] += 1
        restored = None
        if self._last_good is not None:
            _, p_h, s_h = self._last_good
            restored = (p_h, s_h)
        elif self.manager is not None and self.template is not None:
            from repro.checkpoint import restore_latest_valid
            got = restore_latest_valid(self.manager.root, self.template)
            if got is not None:
                (tree, _step), _skipped = got
                restored = tree  # template is the (params, opt_state) pair
        if restored is None:
            # Nothing to roll back to yet (faults before the first good
            # step): keep current state; the skip mask already held it.
            return params, opt_state
        p_h, s_h = restored
        params = jax.tree.map(jnp.asarray, p_h)
        opt_state = jax.tree.map(jnp.asarray, s_h)
        # Retry the replayed steps at the CURRENT (backed-off) lr.
        opt_state = self._set_lr_scale(opt_state)
        return params, opt_state

    # -- reporting --------------------------------------------------------

    def report(self) -> dict:
        return dict(self.counters, lr_scale=self.lr_scale,
                    grad_bf16=self.grad_bf16,
                    last_good_step=(self._last_good[0]
                                    if self._last_good else None))
