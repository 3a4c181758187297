"""The benchmark's plain reference agrees with the program's loss and
gradients, at scaled-down copies of both configurations, in float32."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_tiny import tiny_cell  # noqa: E402

from bench import tokens  # noqa: E402
from bench.program import build_cfg  # noqa: E402
from bench.reference import Reference  # noqa: E402
from bench.weights import describe, make_weights, seed_words  # noqa: E402


@pytest.mark.parametrize("name", ["atis6-tt.b1s32", "granite8b-tt.b1s4096"])
def test_reference_matches_program_loss_and_grads(name):
    from repro.models.transformer import init_params, loss_fn

    cell = tiny_cell(name, float32=True)
    cfg = build_cfg(cell["config"])
    struct = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    layout = describe(struct)
    leaves = make_weights(layout)(seed_words(2**31 + 99))
    params = jax.tree.unflatten(jax.tree.structure(struct), leaves)
    t = cell["traffic"]
    batch = {k: jnp.asarray(v) for k, v in
             tokens.batch(5, 0, t["batch"], t["seq"], cfg.vocab_size).items()}

    loss, grads = jax.value_and_grad(loss_fn)(params, cfg, batch)
    ref = Reference(cell["config"], t)
    named = {p: a for (p, _, _), a in zip(layout, leaves)}
    rloss, rgrads = jax.value_and_grad(ref.loss)(named, batch)

    np.testing.assert_allclose(float(loss), float(rloss), rtol=2e-5)
    gnorm = max(float(jnp.linalg.norm(g)) for g in rgrads.values())
    for (p, _, _), g in zip(layout, jax.tree.leaves(grads)):
        err = float(jnp.linalg.norm(g - rgrads[p]))
        assert err <= 1e-3 * gnorm, (p, err, gnorm)
