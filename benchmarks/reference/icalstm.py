"""Plain reference: the ICA-LSTM classifier of the reference repository's
``comps/icalstm/models.py`` as SURVEY.md section 3.4 and
``tests/test_torch_parity.py`` record it, in float32 ``jax.numpy``.

Imports nothing from the package: it is handed the parameter tree
(``encoder``, ``lstm/{fwd,rev}``, ``cls_fc1``, ``cls_bn``, ``cls_fc2``,
``cls_fc3``) and arrays. Callers run it under
``jax.default_matmul_precision("highest")``.

Forward: windows flattened to ``[B, S, C*W]`` -> Linear + ReLU encoder ->
one LSTM per direction as a plain ``lax.scan`` over the S windows (gate order
i, f, o, g; zero initial state; the reverse direction reads the flipped
sequence and is not flipped back, which the time mean does not see) -> mean
over time of each direction, concatenated -> dropout (a given mask, scaled by
1/keep; none here means rate 0) -> Linear -> BatchNorm1d in training mode
(batch statistics, biased variance, eps 1e-5) -> ReLU -> Linear + ReLU ->
Linear.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BN_EPS = 1e-5


def _linear(p, x):
    return x @ p["kernel"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def _direction(p, seq):
    """seq [B, S, D] -> time mean of the hidden sequence [B, H]."""
    w_ih, w_hh = p["w_ih"].astype(jnp.float32), p["w_hh"].astype(jnp.float32)
    bias = (p["b_ih"] + p["b_hh"]).astype(jnp.float32)
    hidden = w_hh.shape[0]
    zeros = jnp.zeros((seq.shape[0], hidden), jnp.float32)

    def step(carry, xt):
        h, c = carry
        pre = xt @ w_ih + h @ w_hh + bias
        i = jax.nn.sigmoid(pre[:, :hidden])
        f = jax.nn.sigmoid(pre[:, hidden:2 * hidden])
        o = jax.nn.sigmoid(pre[:, 2 * hidden:3 * hidden])
        g = jnp.tanh(pre[:, 3 * hidden:])
        c = f * c + i * g
        h = o * jnp.tanh(c)
        return (h, c), h

    _, hs = jax.lax.scan(step, (zeros, zeros), jnp.swapaxes(seq, 0, 1))
    return hs.mean(axis=0)


def forward(params, x, dropout_mask=None, keep: float = 1.0):
    """Training-mode logits ``[B, classes]`` for ``x [B, S, C, W]``."""
    b, s = x.shape[0], x.shape[1]
    flat = x.reshape(b, s, -1).astype(jnp.float32)
    enc = jax.nn.relu(_linear(params["encoder"], flat))
    pooled = [_direction(params["lstm"]["fwd"], enc)]
    if "rev" in params["lstm"]:
        pooled.append(_direction(params["lstm"]["rev"], enc[:, ::-1]))
    o = jnp.concatenate(pooled, axis=-1)
    if dropout_mask is not None:
        o = o * dropout_mask / keep
    o = _linear(params["cls_fc1"], o)
    mean = o.mean(axis=0, keepdims=True)
    var = jnp.square(o - mean).mean(axis=0, keepdims=True)
    o = (o - mean) / jnp.sqrt(var + BN_EPS)
    o = o * params["cls_bn"]["scale"] + params["cls_bn"]["bias"]
    o = jax.nn.relu(o)
    o = jax.nn.relu(_linear(params["cls_fc2"], o))
    return _linear(params["cls_fc3"], o)
