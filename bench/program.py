"""The system under test: ``repro``'s jitted train step, built as
``repro.launch.train.main`` builds it, driven as its loop drives it.

``Program`` makes one compiled step with its state.  Set-up drives it from
the seed through its first steps (``first_steps``), which compile it and
warm it up; the window then calls the same object (``step``).  Every step
builds its batch on the host with ``repro.data.lm_batch(seed, step, ...)``,
places it, calls the step, and reads the metrics back: the loop's one sync.
"""
from __future__ import annotations

import argparse
import dataclasses
from collections import Counter

import jax
import jax.numpy as jnp

from bench import weights as bw
from bench.workcount import kernel_calls


def _replaced(obj, changes: dict):
    """``obj`` with ``changes``: a nested section (a dict for a dataclass
    field such as ``moe``) replaces that section's fields, and a list
    stands for a tuple."""
    fields = {}
    for key, value in changes.items():
        here = getattr(obj, key)
        if isinstance(value, dict) and dataclasses.is_dataclass(here):
            value = _replaced(here, value)
        elif isinstance(value, list):
            value = tuple(value)
        fields[key] = value
    return dataclasses.replace(obj, **fields)


def differences(obj, want: dict, prefix: str = "") -> dict:
    """``{name: (got, want)}`` of every key of ``want`` that ``obj`` does not
    hold; a nested section is compared field by field (``moe.top_k``)."""
    wrong = {}
    for key, value in want.items():
        got, name = getattr(obj, key), prefix + key
        if isinstance(value, dict) and dataclasses.is_dataclass(got):
            wrong.update(differences(got, value, name + "."))
            continue
        if isinstance(got, tuple):
            got = list(got)
        if got != value:
            wrong[name] = (got, value)
    return wrong


def build_cfg(config: dict):
    """The model config through ``repro.launch.train.build``, checked key
    by key against the configuration's file: its ``model`` section against
    the config, its ``tt`` section against ``cfg.tt``."""
    from repro.launch.train import build

    b = config["build"]
    ns = argparse.Namespace(
        arch=config["arch"], scale_down=False, tt=b["tt"], tt_rank=b["tt_rank"],
        kernel_flow=b["kernel_flow"], fused_attn=b["fused_attn"],
        fused_ffn=b["fused_ffn"], fp32=False, param_dtype=None,
        act_dtype=None, grad_dtype=None)
    cfg = _replaced(build(ns), config["replace"])
    wrong = {**differences(cfg, config["model"]),
             **differences(cfg.tt, config["tt"], "tt.")}
    if wrong:
        raise ValueError(f"the program's config differs from "
                         f"{config['name']}.json: {wrong}")
    return cfg


def param_struct(cfg):
    """The program's parameter tree as shapes, without making it."""
    from repro.models.transformer import init_params

    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


def layout(config: dict) -> list:
    """The program's parameters as ``(name, shape, dtype)``."""
    return bw.describe(param_struct(build_cfg(config)))


class Program:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.launch.mesh import make_host_mesh
        from repro.launch.steps import make_train_step
        from repro.optim import adamw, sgd, warmup_cosine
        from repro.runtime import (batch_specs, named_sharding_tree,
                                   opt_state_specs, param_specs)

        self.seed = int(seed)
        self.traffic = traffic
        self.cfg = cfg = build_cfg(config)
        t = traffic
        self.B, self.S, self.V = t["batch"], t["seq"], cfg.vocab_size
        lr = warmup_cosine(t["lr"], t["warmup_steps"], t["schedule_steps"])
        self.lr = lr
        self.opt = (sgd(lr, fused=True) if t["optimizer"] == "sgd"
                    else adamw(lr, fused=True))

        struct = param_struct(cfg)
        self.layout = bw.describe(struct)
        self.treedef = jax.tree.structure(struct)
        self._make = bw.make_weights(self.layout, config["family"])
        self._init = jax.jit(self.opt.init)
        params, opt_state = self._fresh()

        mesh = make_host_mesh(1, 1)
        sample = self.host_batch(0)
        pspec = param_specs(cfg, params, mesh)
        self._psh = named_sharding_tree(mesh, pspec)
        self._ssh = named_sharding_tree(
            mesh, opt_state_specs(cfg, opt_state, pspec, mesh))
        self.bsh = named_sharding_tree(mesh, batch_specs(sample, mesh))
        self._put(params, opt_state)
        step_fn = jax.jit(make_train_step(cfg, self.opt),
                          in_shardings=(self._psh, self._ssh, self.bsh),
                          out_shardings=(self._psh, self._ssh, None),
                          donate_argnums=(0, 1))
        self.compiled = step_fn.lower(self.params, self.opt_state,
                                      self.place(sample)).compile()
        self.calls = kernel_calls(self.compiled.as_text())
        self.kernels = dict(Counter(c["kernel"] for c in self.calls.values()))

    def _fresh(self):
        params = jax.tree.unflatten(self.treedef,
                                    self._make(bw.seed_words(self.seed)))
        return params, self._init(params)

    def _put(self, params, opt_state) -> None:
        self.params = jax.tree.map(jax.device_put, params, self._psh)
        self.opt_state = jax.tree.map(jax.device_put, opt_state, self._ssh)

    def reseed(self, seed: int) -> None:
        """Fresh weights and optimizer state from ``seed``, same compiled
        step (readings over many seeds in one process)."""
        self.seed = int(seed)
        self._put(*self._fresh())

    # --- one step, as launch.train.main's loop makes it -----------------

    def host_batch(self, step: int) -> dict:
        from repro.data import lm_batch
        return lm_batch(self.seed, step, self.B, self.S, self.V)

    def place(self, batch: dict) -> dict:
        return jax.device_put(batch, self.bsh)

    def call(self, batch) -> dict:
        self.params, self.opt_state, metrics = self.compiled(
            self.params, self.opt_state, batch)
        return metrics

    def step(self, step: int) -> dict:
        metrics = self.call(self.place(self.host_batch(step)))
        return jax.device_get(metrics)

    # --- readings for the check -----------------------------------------

    def named(self, tree) -> dict:
        leaves = jax.tree.leaves(tree)
        return {p: a for (p, _, _), a in zip(self.layout, leaves)}

    def first_steps(self, n: int) -> dict:
        """Steps ``0 .. n-1``: each step's loss and global gradient norm
        before clipping, each leaf's norm of the first gradient as the optimizer got it (worked out from its state
        after step 0) and of the parameters' change after the ``n``."""
        from bench.reference import leaf_norms

        p0 = self.named(jax.tree.map(jnp.copy, self.params))
        losses, norms, first = [], [], None
        for i in range(n):
            metrics = self.step(i)
            losses.append(float(metrics["loss"]))
            norms.append(float(metrics["grad_norm"]))
            if i == 0:
                first = leaf_norms(self._first_grad(p0))
        p_n = self.named(self.params)
        delta = leaf_norms({p: p_n[p].astype(jnp.float32)
                            - p0[p].astype(jnp.float32) for p in p0})
        return {"losses": losses, "grad_norms": norms, "first_grad": first,
                "delta": delta}

    def _first_grad(self, p0: dict) -> dict:
        if self.traffic["optimizer"] == "sgd":
            lr0 = float(self.lr(0))
            p1 = self.named(self.params)
            return {p: (p0[p].astype(jnp.float32) - p1[p].astype(jnp.float32))
                    / lr0 for p in p0}
        m = self.named(self.opt_state["m"])
        return {p: m[p] / (1.0 - 0.9) for p in m}

    def free(self) -> None:
        """Drop the step and its state, so that the reference runs on a
        chip that holds nothing of the program."""
        for leaf in jax.tree.leaves((self.params, self.opt_state)):
            leaf.delete()
        self.params = self.opt_state = self.compiled = None


def reference_weights(layout, seed: int, family: str | None = None) -> dict:
    """The seed's weights again, by name, for the reference."""
    leaves = bw.make_weights(layout, family)(bw.seed_words(seed))
    return {p: a for (p, _, _), a in zip(layout, leaves)}
