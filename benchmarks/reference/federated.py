"""Plain reference: one federated round, and textbook rankDAD aggregation.

Independent of the package. A round is what the reference repository's
local/remote pair does in one iteration: every site computes the gradient of
the mean NLL loss of its batch, the aggregator takes the example-weighted mean
of the sites' gradients, and one Adam step (coinstac-dinunet's optimizer;
torch defaults beta 0.9/0.999, eps 1e-8 outside the root, bias-corrected)
updates the shared parameters.

rankDAD here is the textbook form: every site replaces each gradient matrix
``G [m, n]`` (leading axes flattened; vectors stay dense) by its rank-r
approximation ``P (G^T P)^T`` with ``P`` an orthonormal basis found by
subspace iteration from a cold random start, and the aggregate is the weighted
mean of the sites' reconstructions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _nll(forward, params, x, y):
    logits = forward(params, x)
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.take_along_axis(logp, y[:, None].astype(jnp.int32), axis=-1).mean()
    return loss, logits


def nll_loss(forward, params, x, y):
    return _nll(forward, params, x, y)[0]


def site_gradients(forward, params, xs, ys):
    """``(losses [S], logits [S, B, classes], grads [S, ...])`` for stacked
    site batches ``xs [S, B, ...]``: a Python loop over the sites, nothing
    batched across them."""
    grad = jax.value_and_grad(
        lambda p, x, y: _nll(forward, p, x, y), has_aux=True)
    out = [grad(params, xs[s], ys[s]) for s in range(xs.shape[0])]
    losses = jnp.stack([o[0][0] for o in out])
    logits = jnp.stack([o[0][1] for o in out])
    grads = jax.tree.map(lambda *g: jnp.stack(g), *[o[1] for o in out])
    return losses, logits, grads


def weighted_mean(site_grads, weights):
    w = weights / weights.sum()
    return jax.tree.map(
        lambda g: jnp.tensordot(w, g.astype(jnp.float32), axes=1), site_grads)


def adam_step(params, grads, step: int = 1, lr: float = 1e-3, b1: float = 0.9,
              b2: float = 0.999, eps: float = 1e-8, m=None, v=None):
    m = jax.tree.map(jnp.zeros_like, params) if m is None else m
    v = jax.tree.map(jnp.zeros_like, params) if v is None else v
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    new = jax.tree.map(
        lambda p, a, b: p - lr * (a / (1 - b1 ** step))
        / (jnp.sqrt(b / (1 - b2 ** step)) + eps),
        params, m, v)
    return new, m, v


def dsgd_round(forward, params, xs, ys, lr: float = 1e-3):
    """``(new_params, site_losses, site_logits, site_grads)`` after one round
    from fresh Adam state; every site's batch is full, so the weights are the
    batch sizes."""
    losses, logits, grads = site_gradients(forward, params, xs, ys)
    weights = jnp.full((xs.shape[0],), float(xs.shape[1]))
    new, _, _ = adam_step(params, weighted_mean(grads, weights), lr=lr)
    return new, losses, logits, grads


def _rank_r(g, rank: int, iters: int, key):
    if g.ndim < 2:
        return g
    mat = g.reshape(-1, g.shape[-1]).astype(jnp.float32)
    r = min(rank, *mat.shape)
    if r < 2 and min(mat.shape) < 2:
        return g
    q = jax.random.normal(key, (mat.shape[1], r), jnp.float32)
    for _ in range(iters):
        p, _ = jnp.linalg.qr(mat @ q)
        q = mat.T @ p
    return (p @ q.T).reshape(g.shape)


def rankdad_aggregate(site_grads, weights, rank: int = 10, iters: int = 5,
                      seed: int = 0):
    """Weighted mean of every site's rank-``rank`` reconstruction."""
    leaves, treedef = jax.tree.flatten(site_grads)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    recon = [
        jnp.stack([_rank_r(leaf[s], rank, iters, jax.random.fold_in(k, s))
                   for s in range(leaf.shape[0])])
        for leaf, k in zip(leaves, keys)
    ]
    return weighted_mean(jax.tree.unflatten(treedef, recon), weights)


def tree_cosine(a, b) -> float:
    """Cosine of two trees as flat vectors, on the host in float64 (a float32
    dot on a TPU runs in reduced precision unless asked otherwise)."""
    import numpy as np

    fa = np.concatenate([np.asarray(x, np.float64).ravel() for x in jax.tree.leaves(a)])
    fb = np.concatenate([np.asarray(x, np.float64).ravel() for x in jax.tree.leaves(b)])
    return float(fa @ fb / (np.linalg.norm(fa) * np.linalg.norm(fb)))
