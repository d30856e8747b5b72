"""The plain reference against the package on the CPU at a small size, on
seeded weights: the check a chip run repeats at published widths
(lib/refcheck.py). Float32 on both sides here, so the tolerances are tight."""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells, refcheck
from benchmarks.reference import federated as ref
from benchmarks.reference import icalstm as ref_icalstm


def _system(model, x, y, key=0):
    variables = model.init({"params": jax.random.PRNGKey(key),
                            "dropout": jax.random.PRNGKey(key)}, x, train=True)
    from dinunet_implementations_tpu.trainer.steps import cross_entropy

    def loss(params):
        logits, _ = model.apply({**variables, "params": params}, x, train=True,
                                rngs={"dropout": jax.random.PRNGKey(1)},
                                mutable=["batch_stats"])
        return cross_entropy(logits, y, jnp.ones(len(y))), logits

    (value, logits), grads = jax.value_and_grad(loss, has_aux=True)(
        variables["params"])
    return variables["params"], value, logits, grads


@pytest.mark.parametrize("bidirectional", [True, False])
def test_icalstm_reference_matches_the_scan_model(bidirectional):
    from dinunet_implementations_tpu.models.icalstm import ICALstm

    model = ICALstm(input_size=16, hidden_size=12, bidirectional=bidirectional,
                    num_comps=6, window_size=4, use_pallas=False,
                    dropout_rate=0.0)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((8, 10, 6, 4)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, 8), jnp.int32)
    params, loss, logits, grads = _system(model, x, y)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(ref_icalstm.forward(params, x), logits,
                                   atol=2e-5)
        ref_loss, ref_grads = jax.value_and_grad(
            lambda p: ref.nll_loss(ref_icalstm.forward, p, x, y))(params)
    assert abs(float(ref_loss) - float(loss)) < 1e-5
    assert ref.tree_cosine(grads, ref_grads) > 0.99999


def test_adam_step_is_optax_adam():
    import optax

    params = {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones(3)}
    grads = jax.tree.map(lambda p: jnp.sin(p) * 1e-2, params)
    opt = optax.adam(1e-3)
    updates, _ = opt.update(grads, opt.init(params), params)
    new, _, _ = ref.adam_step(params, grads, lr=1e-3)
    for a, b in zip(jax.tree.leaves(optax.apply_updates(params, updates)),
                    jax.tree.leaves(new)):
        np.testing.assert_allclose(a, b, rtol=1e-6)


def test_rank_r_reconstruction_is_exact_on_a_low_rank_matrix():
    rng = np.random.default_rng(2)
    low = rng.standard_normal((40, 3)) @ rng.standard_normal((3, 30))
    grads = {"w": jnp.asarray(low[None], jnp.float32),
             "b": jnp.ones((1, 30))}
    agg = ref.rankdad_aggregate(grads, jnp.ones(1), rank=10, iters=5)
    np.testing.assert_allclose(agg["w"], low, atol=1e-3)
    np.testing.assert_allclose(agg["b"], jnp.ones(30))


@pytest.mark.parametrize("name", ["icalstm-hcp32.dsgd", "icalstm-hcp32.rankdad"])
def test_the_chip_runs_comparison_passes_at_toy_size_in_float32(name):
    """lib/refcheck.py end to end (logits, per-site gradients, the dSGD round
    through FederatedTrainer or the rankDAD aggregate), float32 compute so the
    reference must be met closely."""
    from benchmarks.drivers import train

    cell = cells.load_cell(name)
    cell.config["train_config"]["ica_args"]["compute_dtype"] = ""
    args = argparse.Namespace(seed=7, rehearse="tiny")
    cfg, model, sites = train.build(cell, args)
    from dinunet_implementations_tpu.trainer.loop import FederatedTrainer

    trainer = FederatedTrainer(cfg, model, None)
    state = trainer.init_state(
        jnp.ones((cfg.batch_size,) + sites[0].inputs.shape[1:], jnp.float32),
        num_sites=len(sites))
    out = refcheck.run(cell, cfg, model, sites, jax.device_get(state.params),
                       jax.device_get(state.batch_stats), 1, trainer.engine)
    assert out["ok"], out
    assert out["logit_max_abs_err"] < 1e-4
    assert out["grad_cosine_min"] > 0.9999
    if cfg.agg_engine == "dSGD":
        assert out["update_cosine"] > 0.995, out
        assert out["round_loss_abs_err"] < 1e-5
    else:
        assert out["engine_cosine_to_reference"] > 0.99, out
