"""Fault-tolerance showcase: train -> checkpoint -> RESHAPE THE CLUSTER ->
resume bit-exact on a different mesh.

Simulates the 1000-node reality where a pod is preempted mid-run: the job
restarts on a different topology, re-derives every sharding from the new
mesh, restores the checkpoint, and the deterministic seekable data pipeline
realigns to the exact batch stream — losses after the re-mesh continue the
same trajectory.

This example spawns itself (subprocess) with 8 placeholder devices so the
mesh change is real: phase A trains on (data=4, model=2), phase B resumes
the same run on (data=2, model=4).

Run:  PYTHONPATH=src python examples/elastic_restart.py
"""
import json
import os
import subprocess
import sys
import tempfile

PHASE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax, jax.numpy as jnp
from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import lm_batch
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step
from repro.models import init_params
from repro.optim import sgd
from repro.runtime import (batch_specs, named_sharding_tree, opt_state_specs,
                           param_specs)
from repro.core.meshctx import activation_mesh

data_ax, model_ax, start, steps, ckpt = (
    int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]),
    sys.argv[5])
cfg = get_config("qwen3-8b").scaled_down().with_tt(mode="tt", rank=8,
                                                   embed_rank=8)
mesh = make_mesh((data_ax, model_ax), ("data", "model"))
opt = sgd(1e-2)
train_step = make_train_step(cfg, opt)

params = init_params(jax.random.PRNGKey(0), cfg)
opt_state = opt.init(params)
pspec = param_specs(cfg, params, mesh)
sspec = opt_state_specs(cfg, opt_state, pspec, mesh)
psh, ssh = named_sharding_tree(mesh, pspec), named_sharding_tree(mesh, sspec)

mgr = CheckpointManager(ckpt, keep=2)
tmpl = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    (params, opt_state))
got = mgr.restore_latest(tmpl)
if got is not None:
    (params, opt_state), start_found = got
    assert start_found == start, (start_found, start)

# ELASTIC: device_put under the *current* mesh's freshly derived specs.
params = jax.tree.map(jax.device_put, params, psh)
opt_state = jax.tree.map(jax.device_put, opt_state, ssh)

sample = lm_batch(0, 0, 8, 64, cfg.vocab_size)
bsh = named_sharding_tree(mesh, batch_specs(sample, mesh))
with activation_mesh(mesh):
    step = jax.jit(train_step, in_shardings=(psh, ssh, bsh),
                   out_shardings=(psh, ssh, None), donate_argnums=(0, 1))
    losses = []
    for i in range(start, start + steps):
        batch = jax.tree.map(jax.device_put,
                             {k: jnp.asarray(v) for k, v in
                              lm_batch(0, i, 8, 64, cfg.vocab_size).items()},
                             bsh)
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(m["loss"]))
mgr.save_blocking(start + steps, (params, opt_state))
print("LOSSES", json.dumps(losses))
"""


def run_phase(data_ax, model_ax, start, steps, ckpt):
    env = {**os.environ,
           "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src")}
    r = subprocess.run(
        [sys.executable, "-c", PHASE, str(data_ax), str(model_ax),
         str(start), str(steps), ckpt],
        env=env, capture_output=True, text=True, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(r.stdout + r.stderr)
    line = [l for l in r.stdout.splitlines() if l.startswith("LOSSES")][0]
    return json.loads(line[len("LOSSES "):])


def main():
    with tempfile.TemporaryDirectory() as ckpt:
        print("[elastic] phase A: mesh (data=4, model=2), steps 0-10")
        la = run_phase(4, 2, 0, 10, ckpt)
        print(f"[elastic]   losses {la[0]:.4f} -> {la[-1]:.4f}")
        print("[elastic] phase B: RE-MESH to (data=2, model=4), resume at 10")
        lb = run_phase(2, 4, 10, 10, ckpt)
        print(f"[elastic]   losses {lb[0]:.4f} -> {lb[-1]:.4f}")
        assert lb[0] < la[0], "resumed run must continue, not restart"
        print("[elastic] OK: training continued across the topology change")


if __name__ == "__main__":
    main()
