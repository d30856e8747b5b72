"""The comparison that decides ``correct`` for the next-token cells: the system
against the plain reference (``reference/afmoe.py``) at the PUBLISHED widths
and the TIMED sizes, outside the timed window, after the trained state has
been released (the reference's float32 round needs the chip's memory).

The sample is what the timed path itself trained on: the sequences of the
loop's first epoch, in the loop's own order (``first["positions"]``).

(a) logits of the system's model (the registry's, at its compute dtype) for
    round 1's sequence of every site against the reference's, from the same
    initial parameters: ``logit_rel_rms`` (root mean square of the difference
    over that of the reference's logits, all positions and rows);
(b) every site's round-1 gradient of the task's own loss against the
    reference's stage-by-stage chain: cosine per group of parameters
    (attention, dense_mlp, experts, shared, router, embedding, head, norms),
    the worst site's;
(c) what the TIMED PATH produced in its first epoch (the round losses the
    trainer returned and the parameters it held afterwards) against the same
    rounds of the reference (``reference/federated.py``: mean of the sites'
    gradients, one Adam step a round) from the same start: the losses round by
    round and the cosine of the two parameter changes, with the norm of their
    difference over the reference's.

Routing is discrete: a token whose 8th and 9th router scores lie closer than
bfloat16 moves the hidden state is sent to another expert by the system than
by the reference, and differs in that row by an expert's whole output. The
limits are therefore on means, root mean squares and cosines, never on a
maximum over positions (the maximum is printed).

Also returned, from the model's own routing of round 1's sequences:
``routing.load_max_over_mean`` (the fullest held expert's assignments over
the held experts' mean, worst layer and site) and ``routing.held_per_token``.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import cells
from benchmarks.reference import federated as fed

GROUPS = ("attention", "dense_mlp", "experts", "shared", "router", "embedding",
          "head", "norms")


def group_of(path: tuple) -> str | None:
    """The check's group of a parameter, by its path in the tree."""
    if path[-1] == "expert_bias":
        return None  # a buffer: selection only, no gradient on either side
    if "attn" in path and path[-1] != "scale":
        return "attention"
    if "mlp" in path:
        return "dense_mlp"
    if "shared" in path:
        return "shared"
    if path[-1] == "router":
        return "router"
    if "moe" in path:
        return "experts"
    if path[-1] == "embed":
        return "embedding"
    if path[-1] == "lm_head":
        return "head"
    return "norms"


def _paths(tree):
    return [(tuple(getattr(k, "key", str(k)) for k in path), leaf)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)]


@jax.jit
def _dots(a, b):
    hi = jax.lax.Precision.HIGHEST
    a, b = a.astype(jnp.float32).ravel(), b.astype(jnp.float32).ravel()
    return (jnp.dot(a, b, precision=hi), jnp.dot(a, a, precision=hi),
            jnp.dot(b, b, precision=hi))


def group_cosines(a, b) -> dict:
    """Cosine of two parameter-shaped trees group by group, and ``all``."""
    sums = {g: np.zeros(3) for g in GROUPS + ("all",)}
    for (path, x), (_, y) in zip(_paths(a), _paths(b)):
        g = group_of(path)
        if g is None:
            continue
        d = np.asarray([float(v) for v in _dots(x, y)], np.float64)
        sums[g] += d
        sums["all"] += d
    return {g: float(s[0] / np.sqrt(s[1] * s[2]))
            for g, s in sums.items() if s[1] > 0 and s[2] > 0}


def _difference(a, b, base) -> dict:
    """Of the changes ``a - base`` and ``b - base``: their cosine, and the
    norm of their difference over the norm of the second."""
    s = np.zeros(4)
    for x, y, z in zip(*(jax.tree.leaves(t) for t in (a, b, base))):
        da, db = jnp.asarray(x) - jnp.asarray(z), jnp.asarray(y) - jnp.asarray(z)
        s[:3] += [float(v) for v in _dots(da, db)]
        s[3] += float(_dots(da - db, da - db)[1])
    return {"update_cosine": float(s[0] / np.sqrt(s[1] * s[2])),
            "update_rel_err": float(np.sqrt(s[3] / s[2]))}


@functools.partial(jax.jit, static_argnames="lr")
def _round_leaf(p, site_grads, weights, m, v, step, lr):
    """One parameter's round: the mean of the sites' gradients and one Adam
    step (``reference/federated.py``), as one program a shape."""
    return fed.adam_step(p, fed.weighted_mean(site_grads, weights), step=step,
                         lr=lr, m=m, v=v)


def reference_dims(cell, cfg):
    ref = importlib.import_module(
        "benchmarks.reference." + cell.config["reference"])
    from dinunet_implementations_tpu.runner.registry import afmoe_layer_types

    a = cfg.lm_args
    return ref, ref.Dims.of(dataclasses.asdict(a),
                            layer_types=afmoe_layer_types(a))


def routing_counters(counts, tokens: int) -> dict:
    """From the model's own routing: ``counts [layers, sites, held]``
    assignments on each held expert (the ``held_counts`` the model sows)."""
    c = np.asarray(counts, np.float64)
    return {
        "load_max_over_mean": float(
            (c.max(-1) / np.maximum(c.mean(-1), 1e-9)).max()),
        "held_per_token": float(c.sum() / (c.shape[0] * c.shape[1] * tokens)),
        "held_per_expert": c.mean(axis=1).round(1).tolist(),
    }


def run(cell, cfg, model, sites, params0, first, rehearse=None) -> dict:
    # a rehearsal's toy widths round differently: its size may bring limits
    limits = cells.merged(cell.config["check"], *cell._rehearse("check", rehearse))
    ref, dims = reference_dims(cell, cfg)
    positions = np.asarray(first["positions"])  # [S, rounds, B]
    n_sites, rounds, batch = positions.shape
    if batch != 1:
        raise SystemExit("the next-token check reads one sequence a site a round")
    rows = np.stack([  # [rounds, S, T + 1]
        np.stack([sites[s].inputs[positions[s, r, 0]] for s in range(n_sites)])
        for r in range(rounds)]).astype(np.int32)
    clock = [time.perf_counter()]

    def lap() -> float:
        clock.append(time.perf_counter())
        return clock[-1] - clock[-2]

    p0 = jax.device_put(params0)
    out = {"sites": n_sites, "rounds": rounds, "tokens": int(rows.shape[-1] - 1)}

    @jax.jit
    def sys_logits(p, x):
        """The model's logits and its routing counters, one forward pass."""
        logits, inter = model.apply({"params": p}, x[None],
                                    mutable=["intermediates"])
        counts = jnp.stack([v["moe"]["held_counts"][0][0] for _, v in sorted(
            inter["intermediates"].items())])
        return logits[0], counts

    sys_grad = jax.jit(jax.value_and_grad(lambda p, x: model.task_loss(
        {"params": p}, x[None], jnp.ones((1,), jnp.float32))))

    @jax.jit
    def logit_errors(a, b):
        d = a - b
        return (jnp.sqrt(jnp.mean(d * d) / jnp.mean(b * b)), jnp.abs(d).max(),
                jnp.mean(jnp.abs(d).max(axis=-1) > 0.25 * jnp.abs(b).max()))

    # (a) and (b): round 1, site by site; the system first, at its own
    # precision, then the reference under matmul precision "highest"
    rel_rms, max_abs, rows_off, loss_err, cosines = [], [], [], [], []
    ref_grads, counts = [], []
    for s in range(n_sites):
        x = jnp.asarray(rows[0, s])
        got, held = sys_logits(p0, x)
        counts.append(np.asarray(held))
        loss_sys, g_sys = sys_grad(p0, x)
        with jax.default_matmul_precision("highest"):
            want = ref.logits(p0, x[:-1], dims)
            e = [float(v) for v in logit_errors(got, want)]
            del got, want
            loss_ref, g_ref = ref.grads(p0, x, dims)
        rel_rms.append(e[0]), max_abs.append(e[1]), rows_off.append(e[2])
        loss_err.append(abs(float(loss_sys) - float(loss_ref)))
        cosines.append(group_cosines(g_sys, g_ref))
        ref_grads.append((float(loss_ref), g_ref))
        del g_sys
    seconds = {"logits_and_gradients": lap()}
    out["routing"] = routing_counters(np.stack(counts, axis=1), out["tokens"])
    out["logit_rel_rms"] = max(rel_rms)
    out["logit_max_abs_err"] = max(max_abs)
    out["logit_rows_off_share"] = max(rows_off)
    out["loss_abs_err"] = max(loss_err)
    out["grad_cosine"] = {g: min(c[g] for c in cosines) for g in cosines[0]}
    ok = out["logit_rel_rms"] <= limits["logit_rel_rms_max"]
    ok &= out["loss_abs_err"] <= limits["loss_atol"]
    for g, floor in limits["grad_cosine_min"].items():
        ok &= out["grad_cosine"].get(g, 1.0) >= floor

    # (c) the reference's rounds from the same start, against what the timed
    # path's first epoch returned. Leaf by leaf, so that the float32 round
    # (parameters, Adam's two moments, the sites' gradients) fits the chip.
    weights = jnp.ones((n_sites,), jnp.float32)
    leaves, treedef = jax.tree.flatten(p0)
    del p0
    ms, vs = [None] * len(leaves), [None] * len(leaves)
    ref_losses = []
    for r in range(rounds):
        if r:
            with jax.default_matmul_precision("highest"):
                p = jax.tree.unflatten(treedef, leaves)
                ref_grads = [ref.grads(p, jnp.asarray(rows[r, s]), dims)
                             for s in range(n_sites)]
                del p
        ref_losses.append(float(np.mean([l for l, _ in ref_grads])))
        site_grads = [jax.tree.leaves(g) for _, g in ref_grads]
        del ref_grads
        for i in range(len(leaves)):
            stacked = jnp.stack([g[i] for g in site_grads])
            for g in site_grads:
                g[i] = None
            leaves[i], ms[i], vs[i] = _round_leaf(
                leaves[i], stacked, weights, ms[i], vs[i],
                jnp.float32(r + 1), cfg.learning_rate)
            del stacked
    del ms, vs, site_grads
    seconds["reference_rounds"] = lap()
    got_losses = np.asarray(first["losses"], np.float64)
    out["round_losses"] = got_losses.tolist()
    out["reference_round_losses"] = ref_losses
    out["round_loss_abs_err"] = float(
        np.abs(got_losses - np.asarray(ref_losses)).max())
    out.update(_difference(first["params"], leaves, params0))
    ok &= len(got_losses) == rounds and bool(np.isfinite(got_losses).all())
    ok &= out["round_loss_abs_err"] <= limits["round_loss_atol"]
    ok &= out["update_cosine"] >= limits["update_cosine_min"]
    ok &= out["update_rel_err"] <= limits["update_rel_err_max"]
    seconds["parameter_change"] = lap()
    out["seconds"] = seconds
    out["ok"] = bool(ok)
    return out
