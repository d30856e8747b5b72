"""Plain reference: the ``lfm2_moe`` decoder (LFM2-8B-A1B) as a next-token
task, in float32 ``jax.numpy``.

Imports nothing from the package (of the benchmark, the sibling reference's
norm, rotary term, SwiGLU and expert layer, which this model shares with
Trinity's to the letter): it is handed the parameter tree (``embed``,
``layer_<i>/{input_norm, attn/{w_in, filter, w_out} | attn/{wq, wk, wv, wo,
q_norm, k_norm}, pre_mlp_norm, mlp/{w1, w3, w2} | moe/{router, expert_bias,
w1, w3, w2}}``, ``final_norm``; NO ``lm_head``: the head is the embedding's
transpose), token ids and a :class:`Dims`. No kernels, no recomputation, no
batching over sites or sequences: one sequence ``[T]`` at a time, the
convolution as the sum over its shifted copies, an explicit mask per block of
query rows, a loop over the held experts (every token through every held
expert, times its routing weight or zero). Callers run it under
``jax.default_matmul_precision("highest")``.

The equations (h: ``[T, hidden]``, a layer's input; ``layer_types`` names the
token mixer of each layer; two pre-norms a block and no post-norm; no bias):

- ``h0 = E[tok]`` (no scale);
- a ``conv`` layer, the gated short convolution: ``a = rms(h)``; ``[B | C |
  x] = a W_in`` (hidden -> 3 x hidden); ``u = B * x``; ``c_t = sum_{j=0..L-1}
  f_j * u_{t-(L-1)+j}`` with ``u`` zero before position 0 (``L`` =
  ``conv_L_cache`` = 3; ``f`` is ``filter [hidden, L]``: one filter a
  channel, its LAST tap on the position itself); ``h' = h + (C * c) W_out``;
- a ``full_attention`` layer: ``a = rms(h)``; ``q, k, v = a Wq, a Wk, a Wv``
  as ``[T, heads | kv_heads, d]``; ``q, k <- rms_d(q), rms_d(k)`` (weights
  ``[d]``), THEN rotate-half rotary positions (``theta``) over the whole head;
  ``scores = q k^T / sqrt(d)``, query head ``n`` with key-value head ``n //
  (heads / kv_heads)``, kept where ``j <= i``; softmax; ``o = P v``; ``h' = h
  + o Wo`` (no gate, no window);
- the first ``num_dense_layers`` layers: ``m = rms(h')``; ``h_next = h' +
  (silu(m W1) * (m W3)) W2``;
- every other layer: ``s = sigmoid(m Wr)``; ``sel = top_k(s + b)`` (``b`` the
  ``expert_bias`` buffer, zero here); ``w = s[sel] / (sum s[sel] + 1e-20)``
  (``route_norm``), ``w <- route_scale * w``; ``y = sum over e in sel that are
  HELD of w_e expert_e(m)`` (SwiGLU; the held experts are ``first_expert ..
  first_expert + E - 1``, ``E`` the leading axis of the stacks: what the
  experts held elsewhere would add is left out, as in the program; no shared
  expert); ``h_next = h' + y``;
- ``logits = rms(h) E^T``; ``loss`` = mean over the ``T`` positions of the
  cross-entropy against the next token.

Departures from the published model, each also in the configuration's
``assumed``: the tied head, rotate-half pairing, QK-norm before rotary, 1e-20
for the family's 1e-6 in the weights' sum, ``expert_bias`` zero and never
updated, no balance term.

``grads`` is the same computation differentiated stage by stage (embedding;
a ``conv`` layer whole; an attention layer as projections, attention one
query block at a time, output and experts; the head in sequence blocks), each
stage's ``jax.vjp`` alone on the device. The embedding's gradient is the sum
of its two uses. ``benchmarks/tests/test_lfm2_cell.py`` holds it equal to
``jax.grad(loss)``.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

# the plain functions this decoder shares with Trinity's: the norm, the rotary
# term, SwiGLU and the sigmoid-routed expert layer (``reference/afmoe.py``,
# whose ``moe`` reads of ``dims`` the four routing fields and ``first_expert``)
from benchmarks.reference.afmoe import _f32, _rotary, moe, rms, swiglu

CONV = "conv"


@dataclasses.dataclass(frozen=True)
class Dims:
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 64
    layer_types: tuple = ()
    num_dense_layers: int = 2
    num_experts_per_tok: int = 4
    first_expert: int = 0
    route_norm: bool = True
    route_scale: float = 1.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    conv_L_cache: int = 3
    q_block: int = 256  # query rows per explicit mask block
    head_block: int = 1024  # positions per block of the head and the loss

    @classmethod
    def of(cls, args: dict, **over) -> "Dims":
        """From a mapping that uses the program's ``lm_args`` names."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in {**args, **over}.items() if k in names}
        if "layer_types" in kw:
            kw["layer_types"] = tuple(kw["layer_types"])
        return cls(**kw)


# -- the token mixers -----------------------------------------------------------


def short_conv(p, a, dims: Dims):
    """The gated short convolution of the normed tokens ``a [T, hidden]``."""
    b, c, x = jnp.split(a @ _f32(p["w_in"]), 3, axis=-1)
    u, f, taps = b * x, _f32(p["filter"]), dims.conv_L_cache
    conv = jnp.zeros_like(u)
    for j in range(taps):  # tap j reads the position taps - 1 - j back
        back = taps - 1 - j
        shifted = jnp.concatenate([jnp.zeros_like(u[:back]), u[: u.shape[0] - back]])
        conv = conv + f[:, j] * shifted
    return (c * conv) @ _f32(p["w_out"])


def pre(p, h, dims: Dims):
    """An attention layer's ``(q [T, N, d], k [T, G, d], v [T, G, d])``."""
    a = rms(h, p["input_norm"]["scale"], dims.rms_norm_eps)
    at = p["attn"]
    n, g, d = dims.num_attention_heads, dims.num_key_value_heads, dims.head_dim
    q = (a @ _f32(at["wq"])).reshape(-1, n, d)
    k = (a @ _f32(at["wk"])).reshape(-1, g, d)
    v = (a @ _f32(at["wv"])).reshape(-1, g, d)
    q = rms(q, at["q_norm"]["scale"], dims.rms_norm_eps)
    k = rms(k, at["k_norm"]["scale"], dims.rms_norm_eps)
    return _rotary(q, dims.rope_theta), _rotary(k, dims.rope_theta), v


def core(qb, k, v, start, dims: Dims):
    """Attention of the query rows ``start .. start + len(qb) - 1`` against
    all ``T`` keys under an explicit causal mask. ``qb [Q, N, d]`` -> ``[Q,
    N * d]``."""
    n, g = dims.num_attention_heads, dims.num_key_value_heads
    kk = jnp.repeat(k, n // g, axis=1)  # head n reads key-value head n // (n/g)
    vv = jnp.repeat(v, n // g, axis=1)
    scores = jnp.einsum("qnd,snd->nqs", qb, kk) / math.sqrt(dims.head_dim)
    i = start + jnp.arange(qb.shape[0])[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    probs = jax.nn.softmax(jnp.where((j <= i)[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("nqs,snd->qnd", probs, vv).reshape(qb.shape[0], -1)


def ffn(p, h1, dims: Dims, layer: int):
    """The block's second half: the dense MLP or the held experts' part."""
    m = rms(h1, p["pre_mlp_norm"]["scale"], dims.rms_norm_eps)
    if layer < dims.num_dense_layers:
        return h1 + swiglu(p["mlp"], m)
    return h1 + moe(p["moe"], m, dims)


def post(p, h, o, dims: Dims, layer: int):
    """An attention layer's output from its input and the heads' output."""
    return ffn(p, h + o @ _f32(p["attn"]["wo"]), dims, layer)


def conv_layer(p, h, dims: Dims, layer: int):
    a = rms(h, p["input_norm"]["scale"], dims.rms_norm_eps)
    return ffn(p, h + short_conv(p["attn"], a, dims), dims, layer)


def _query_blocks(t: int, dims: Dims):
    qb = min(dims.q_block, t)
    return [(s, min(s + qb, t)) for s in range(0, t, qb)]


def layer_forward(p, h, dims: Dims, layer: int):
    if dims.layer_types[layer] == CONV:
        return conv_layer(p, h, dims, layer)
    q, k, v = pre(p, h, dims)
    o = jnp.concatenate([core(q[s:e], k, v, s, dims)
                         for s, e in _query_blocks(h.shape[0], dims)])
    return post(p, h, o, dims, layer)


# -- the model's two ends: one matrix -------------------------------------------


def embed(table, tokens):
    return _f32(table)[tokens]


def head_logits(p, h, dims: Dims):
    """``p``: ``final_norm`` and the embedding ``embed [vocab, hidden]``."""
    return rms(h, p["final_norm"], dims.rms_norm_eps) @ _f32(p["embed"]).T


def head_nll(p, h, targets, dims: Dims):
    """Summed cross-entropy of the positions ``h [B, hidden]``."""
    logp = jax.nn.log_softmax(head_logits(p, h, dims), axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).sum()


def _layers(params):
    return [params[f"layer_{i}"] for i in range(
        sum(1 for k in params if k.startswith("layer_")))]


def _head(params):
    return {"final_norm": params["final_norm"], "embed": params["embed"]}


def hidden(params, tokens, dims: Dims):
    h = embed(params["embed"], tokens)
    for i, p in enumerate(_layers(params)):
        h = layer_forward(p, h, dims, i)
    return h


def forward(params, tokens, dims: Dims):
    """Logits ``[T, vocab]`` for the ids ``tokens [T]``."""
    return head_logits(_head(params), hidden(params, tokens, dims), dims)


def loss(params, sample, dims: Dims):
    """Mean next-token cross-entropy of ``sample [T + 1]``: the model reads
    the first ``T`` ids, the loss the last ``T``."""
    sample = sample.astype(jnp.int32)
    h = hidden(params, sample[:-1], dims)
    return head_nll(_head(params), h, sample[1:], dims) / h.shape[0]


# -- the same, differentiated stage by stage -----------------------------------


@functools.lru_cache(maxsize=None)
def _stages(dims: Dims, dense: bool) -> dict:
    """Jitted stage functions and their vjps, of the layers whose second half
    is the dense MLP (``dense``) or the experts."""
    layer = 0 if dense else dims.num_dense_layers

    def vjp_of(fn):
        def bwd(*args):
            *primals, ct = args
            return jax.vjp(fn, *primals)[1](ct)

        return jax.jit(bwd)

    def post_(p, h, o):
        return post(p, h, o, dims, layer)

    def conv_(p, h):
        return conv_layer(p, h, dims, layer)

    def pre_(p, h):
        return pre(p, h, dims)

    def core_(qb, k, v, start):
        return core(qb, k, v, start, dims)

    def core_bwd(qb, k, v, start, ct):
        return jax.vjp(lambda q_, k_, v_: core(q_, k_, v_, start, dims),
                       qb, k, v)[1](ct)

    return {
        "pre": jax.jit(pre_), "pre_bwd": vjp_of(pre_),
        "core": jax.jit(core_), "core_bwd": jax.jit(core_bwd),
        "post": jax.jit(post_), "post_bwd": vjp_of(post_),
        "conv": jax.jit(conv_), "conv_bwd": vjp_of(conv_),
    }


@functools.lru_cache(maxsize=None)
def _ends(dims: Dims) -> dict:
    def head_bwd(p, h, targets, scale):
        val, back = jax.vjp(lambda p_, h_: head_nll(p_, h_, targets, dims), p, h)
        return (val,) + back(scale)

    return {
        "embed": jax.jit(embed),
        "embed_bwd": jax.jit(lambda table, tokens, ct: jax.vjp(
            lambda t_: embed(t_, tokens), table)[1](ct)[0]),
        "head_bwd": jax.jit(head_bwd),
        "logits": jax.jit(lambda p, h: head_logits(p, h, dims)),
    }


def _of(dims: Dims, layer: int) -> dict:
    return _stages(dims, layer < dims.num_dense_layers)


def _attention_of(p, h, dims: Dims, layer: int):
    """``(q, k, v, o)`` of an attention layer, stage by stage."""
    st = _of(dims, layer)
    q, k, v = st["pre"](p, h)
    o = jnp.concatenate([st["core"](q[s:e], k, v, s)
                         for s, e in _query_blocks(h.shape[0], dims)])
    return q, k, v, o


def _layer_backward(p, h, ct, dims: Dims, layer: int):
    """``(dp, dh)`` of one layer for the cotangent ``ct`` of its output."""
    st = _of(dims, layer)
    if dims.layer_types[layer] == CONV:
        return st["conv_bwd"](p, h, ct)
    q, k, v, o = _attention_of(p, h, dims, layer)
    dp_post, dh, do = st["post_bwd"](p, h, o, ct)
    dq, dk, dv = [], jnp.zeros_like(k), jnp.zeros_like(v)
    for s, e in _query_blocks(h.shape[0], dims):
        dqb, dkb, dvb = st["core_bwd"](q[s:e], k, v, s, do[s:e])
        dq.append(dqb)
        dk, dv = dk + dkb, dv + dvb
    dp_pre, dh_pre = st["pre_bwd"](p, h, (jnp.concatenate(dq), dk, dv))
    return jax.tree.map(jnp.add, dp_post, dp_pre), dh + dh_pre


def hidden_states(params, tokens, dims: Dims):
    """The input of every layer and the last layer's output, ``[L + 1]``."""
    hs = [_ends(dims)["embed"](params["embed"], tokens)]
    for i, p in enumerate(_layers(params)):
        if dims.layer_types[i] == CONV:
            hs.append(_of(dims, i)["conv"](p, hs[-1]))
        else:
            o = _attention_of(p, hs[-1], dims, i)[-1]
            hs.append(_of(dims, i)["post"](p, hs[-1], o))
    return hs


def logits(params, tokens, dims: Dims):
    """``forward``, one jitted stage at a time."""
    return _ends(dims)["logits"](
        _head(params), hidden_states(params, tokens, dims)[-1])


def grads(params, sample, dims: Dims):
    """``(loss, gradient tree)`` of :func:`loss`, stage by stage."""
    sample = jnp.asarray(sample).astype(jnp.int32)
    tokens, targets = sample[:-1], sample[1:]
    t = tokens.shape[0]
    hs = hidden_states(params, tokens, dims)
    head, ends = _head(params), _ends(dims)
    total, dhead, dh = 0.0, None, []
    hb = min(dims.head_block, t)
    for s in range(0, t, hb):
        val, dp, dhb = ends["head_bwd"](head, hs[-1][s: s + hb],
                                        targets[s: s + hb], jnp.float32(1.0 / t))
        total = total + val / t
        dhead = dp if dhead is None else jax.tree.map(jnp.add, dhead, dp)
        dh.append(dhb)
    ct = jnp.concatenate(dh)
    out = {"final_norm": dhead["final_norm"]}
    layers = _layers(params)
    for i in reversed(range(len(layers))):
        out[f"layer_{i}"], ct = _layer_backward(layers[i], hs[i], ct, dims, i)
        hs[i + 1] = None  # the chain holds one layer's input at a time
    # the one matrix's two uses: as the head, and as the table of rows
    out["embed"] = dhead["embed"] + ends["embed_bwd"](params["embed"], tokens, ct)
    return total, out
