"""The comparison that decides ``correct``, part one: the system against the
plain reference on a seeded sample, outside the timed window.

The sample is the first batch of a few sites (what the float32 reference can
hold). Dropout is switched off on both sides for the comparison
(``model.clone(dropout_rate=0.0)`` where the model has one): the mask is a
draw from the program's own key chain, and a check that rebuilt that chain
would refuse every later PR that re-keys it. Everything else is the model the
registry built, the trainer, the engine and the optimizer as the cell runs
them.

(a) logits and per-site gradients of the system's model against the
    reference's (``logit_atol``, ``grad_cosine_min`` of the configuration);
(b) dSGD: the parameter update of ONE round through a ``FederatedTrainer`` on
    the sample (same cfg, engine, mesh kind; one batch a site, so an epoch is
    one round) against the reference round: cosine of the two updates
    (``update_cosine_min``; Adam's first step is lr*sign(g) wherever |g| >>
    eps, so elements whose gradient is near zero flip with rounding and the
    cosine, not an elementwise tolerance, is the meaningful comparison);
(c) rankDAD: the engine's aggregate of the sample's per-site gradients against
    the textbook rankDAD aggregate and against the exact mean, each with the
    floor the cell's file gives.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import federated as ref


def _without_dropout(model):
    return model.clone(dropout_rate=0.0) if hasattr(model, "dropout_rate") else model


def run(cell, cfg, model, sites, params0, batch_stats0, chips: int,
        engine) -> dict:
    from dinunet_implementations_tpu.trainer.steps import cross_entropy

    limits = cell.config["check"]
    forward = importlib.import_module(
        "benchmarks.reference." + cell.config["reference"]).forward
    model = _without_dropout(model)
    batch = min(cfg.batch_size, min(len(s) for s in sites))
    n = min(len(sites), 4 if chips == 1 else 2 * chips)
    xs = np.stack([s.inputs[:batch] for s in sites[:n]]).astype(np.float32)
    ys = np.stack([s.labels[:batch] for s in sites[:n]]).astype(np.int32)
    in_dtype = getattr(model, "compute_dtype", None) or jnp.float32
    variables = {"params": params0}
    if batch_stats0:
        variables["batch_stats"] = batch_stats0
    key = jax.random.PRNGKey(0)
    ones = jnp.ones((batch,), jnp.float32)

    def sys_logits(params, x):
        out, _ = model.apply({**variables, "params": params}, x, train=True,
                             mask=ones, rngs={"dropout": key},
                             mutable=["batch_stats"])
        return out

    def sys_loss(params, x, y):
        logits = sys_logits(params, x)
        return cross_entropy(logits, y, ones), logits

    @jax.jit
    def sys_all(params, xs, ys):
        (loss, logits), grads = jax.vmap(
            jax.value_and_grad(sys_loss, has_aux=True), in_axes=(None, 0, 0)
        )(params, xs.astype(in_dtype), ys)
        return loss, logits, grads

    out = {"sites": n, "batch": batch}
    weights = jnp.full((n,), float(batch))
    # the system first, at the precision it runs at; then the reference,
    # alone under matmul precision "highest" (the Pallas kernels refuse it)
    sys_loss_v, sys_logit_v, sys_grads = sys_all(params0, xs, ys)
    if cfg.agg_engine == "dSGD":
        system = _system_round(cfg, model, sites[:n], batch, chips)
    else:
        system = _system_aggregate(engine, params0, sys_grads, weights)
    rank_args = cfg.task_args()

    def reference(params, xs, ys, grads_in, weights):
        """Everything the reference computes, as one program."""
        new, losses, logits, grads = ref.dsgd_round(
            forward, params, xs, ys, lr=cfg.learning_rate)
        textbook = exact = None
        if cfg.agg_engine != "dSGD":
            textbook = ref.rankdad_aggregate(
                grads_in, weights, rank=rank_args.dad_reduction_rank,
                iters=rank_args.dad_num_pow_iters)
            exact = ref.weighted_mean(grads_in, weights)
        return new, losses, logits, grads, textbook, exact

    with jax.default_matmul_precision("highest"):
        new, ref_losses, ref_logits, ref_grads, textbook, exact = jax.jit(
            reference)(params0, xs, ys, sys_grads, weights)
    out["logit_max_abs_err"] = float(jnp.abs(sys_logit_v - ref_logits).max())
    out["loss_max_abs_err"] = float(jnp.abs(sys_loss_v - ref_losses).max())
    out["grad_cosine_min"] = min(
        ref.tree_cosine(jax.tree.map(lambda g: g[s], sys_grads),
                        jax.tree.map(lambda g: g[s], ref_grads))
        for s in range(n))
    ok = (out["logit_max_abs_err"] <= limits["logit_atol"]
          and out["grad_cosine_min"] >= limits["grad_cosine_min"])
    if cfg.agg_engine == "dSGD":
        ok &= _round_check(system, new, ref_losses, limits, out)
    else:
        ok &= _engine_check(cell, system, textbook, exact, out)
    out["ok"] = bool(ok)
    return out


def _system_round(cfg, model, sites, batch, chips):
    """``(params before, params after, losses)`` of one round through a
    ``FederatedTrainer`` on the sample: one batch a site, so one epoch is one
    round."""
    from dinunet_implementations_tpu.data.api import SiteArrays
    from dinunet_implementations_tpu.runner.fed_runner import auto_site_mesh
    from dinunet_implementations_tpu.trainer.loop import FederatedTrainer

    n = len(sites)
    small = cfg.replace(
        num_sites=n, batch_size=batch,
        sites_per_device=1 if chips == 1 else n // chips)
    sample = [SiteArrays(s.inputs[:batch], s.labels[:batch],
                         np.arange(batch, dtype=np.int32)) for s in sites]
    trainer = FederatedTrainer(small, model, auto_site_mesh(small, n))
    state = trainer.init_state(
        jnp.ones((batch,) + sample[0].inputs.shape[1:], jnp.float32),
        num_sites=n)
    before = jax.device_get(state.params)
    state, losses = trainer.run_epoch(state, sample, 1, batch_size=batch)
    return before, jax.device_get(state.params), np.asarray(losses)


def _system_aggregate(engine, params0, site_grads, weights):
    """The engine's aggregate of the sample's per-site gradients, through its
    public pair ``init`` / ``aggregate`` under a vmap named ``site``."""
    n = weights.shape[0]
    es = jax.tree.map(lambda a: jnp.stack([a] * n), engine.init(params0))
    agg, _ = jax.jit(jax.vmap(
        lambda g, s, w: engine.aggregate(g, s, w, "site"), axis_name="site"
    ))(site_grads, es, weights)
    return jax.tree.map(lambda a: a[0], agg)


def _round_check(system, new, ref_losses, limits, out) -> bool:
    before, after, losses = system

    def delta(a, b):
        return jax.tree.map(lambda x, y: np.asarray(x) - np.asarray(y), a, b)

    out["update_cosine"] = ref.tree_cosine(delta(after, before),
                                           delta(new, before))
    out["round_loss_abs_err"] = abs(float(np.mean(losses))
                                    - float(np.mean(np.asarray(ref_losses))))
    return (out["update_cosine"] >= limits["update_cosine_min"]
            and len(losses) == 1 and bool(np.isfinite(losses).all()))


def _engine_check(cell, agg, textbook, exact, out) -> bool:
    floors = cell.traffic["check"]
    out["engine_cosine_to_reference"] = ref.tree_cosine(agg, textbook)
    out["engine_cosine_to_exact_mean"] = ref.tree_cosine(agg, exact)
    out["reference_cosine_to_exact_mean"] = ref.tree_cosine(textbook, exact)
    return (out["engine_cosine_to_reference"] >= floors["cosine_to_reference_min"]
            and out["engine_cosine_to_exact_mean"] >= floors["cosine_to_exact_mean_min"])
