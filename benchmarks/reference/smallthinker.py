"""Plain reference: the ``smallthinker`` decoder (SmallThinker-21BA3B-Instruct)
as a next-token task, in float32 ``jax.numpy``.

Imports nothing from the package (of the benchmark, the sibling reference's
norm, rotary term, head and loss): it is handed the parameter tree (``embed``,
``layer_<i>/{input_norm, attn/{wq, wk, wv, wo}, pre_mlp_norm, moe/{router,
w1, w3, w2}}``, ``final_norm``, ``lm_head``), token ids and a :class:`Dims`.
No kernels, no recomputation, no batching over sites or sequences: one
sequence ``[T]`` at a time, an explicit mask per block of query rows, a loop
over the held experts (every token through every held expert, times its
routing weight or zero; ``lax.scan``, so that the body compiles once). Callers
run it under ``jax.default_matmul_precision("highest")``.

The equations (h: ``[T, hidden]``, the layer's input; all layers alike but for
the two flags of ``layer_types``; no bias anywhere):

- ``h0 = E[tok]`` (no scale);
- the router, BEFORE attention and on the un-normed input: ``r = h W_r``
  (``[T, experts]``); ``sel = top_k(r)``; ``p = softmax(r[sel])`` over the
  ``k`` chosen alone (no selection bias, no scale; ``norm_topk_prob`` would
  divide ``p`` by its sum, 1);
- ``a = rms(h)``; ``q, k, v = a Wq, a Wk, a Wv`` as ``[T, heads | kv_heads,
  d]``; no QK-norm; on ``sliding_attention`` layers rotate-half rotary
  positions (``theta``) on ``q`` and ``k``, on ``full_attention`` layers NO
  positional term; ``scores = q k^T / sqrt(d)``, query head ``n`` with
  key-value head ``n // (heads / kv_heads)``, kept where ``j <= i`` and, on
  sliding layers, ``j > i - window``; softmax; ``o = P v``; ``h' = h + o Wo``
  (no gate, no norm on the branch's output);
- ``m = rms(h')``; ``y = sum over e in sel that are HELD of p_e (relu(m
  W1_e) * (m W3_e)) W2_e`` (ReGLU; the held experts are ``first_expert ..
  first_expert + E - 1``, ``E`` the leading axis of the stacks: what the
  experts held elsewhere would add is left out, as in the program; ``p`` is
  NOT renormalised over the held ones); ``h_next = h' + y``;
- ``logits = rms(h) W_head``; ``loss`` = mean over the ``T`` positions of the
  cross-entropy against the next token.

Departures from the published model, each also in the configuration's
``assumed``: the rotary pairing is rotate-half; the family's secondary experts
and the load-balance term of its training recipe are absent (``config.json``
defines neither). One departure from the plainest form, for the chip
comparison at 16,384 positions: attention runs ``q_block`` query rows at a
time, and on a sliding layer a block is held against the ``window + q_block``
keys its rows can reach instead of all ``T`` (the mask is explicit either way),
so that ``[T, T]`` scores of 28 heads never exist.

``grads`` is the same computation differentiated stage by stage (embedding;
per layer: projections, attention one query block at a time, output, router
and experts; the head in sequence blocks), each stage's ``jax.vjp`` alone on
the device, so that the float32 gradients at the published widths fit one
chip. ``benchmarks/tests/test_smallthinker_cell.py`` holds it equal to
``jax.grad(loss)``.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

# the plain functions every decoder here shares: the norm, the rotary term,
# the head and its loss (``reference/afmoe.py``, which reads of ``dims`` only
# ``rms_norm_eps`` there)
from benchmarks.reference.afmoe import _f32, _rotary, head_logits, head_nll, rms

SLIDING = "sliding_attention"


@dataclasses.dataclass(frozen=True)
class Dims:
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 4096
    layer_types: tuple = ()
    num_experts_per_tok: int = 6
    first_expert: int = 0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1500000.0
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


# -- the three stages of a layer ----------------------------------------------


def pre(p, h, dims: Dims, layer: int):
    """``(q [T, N, d], k [T, G, d], v [T, G, d])``."""
    a = rms(h, p["input_norm"]["scale"], dims.rms_norm_eps)
    at = p["attn"]
    n, g, d = dims.num_attention_heads, dims.num_key_value_heads, dims.head_dim
    q = (a @ _f32(at["wq"])).reshape(-1, n, d)
    k = (a @ _f32(at["wk"])).reshape(-1, g, d)
    v = (a @ _f32(at["wv"])).reshape(-1, g, d)
    if dims.layer_types[layer] == SLIDING:
        q, k = _rotary(q, dims.rope_theta), _rotary(k, dims.rope_theta)
    return q, k, v


def core(qb, k, v, start, dims: Dims, layer: int):
    """Attention of the query rows ``start .. start + len(qb) - 1`` under an
    explicit mask: against all ``T`` keys on a full layer, against the
    ``window + len(qb)`` keys that end with the block's last row on a sliding
    one. ``qb [Q, N, d]`` -> ``[Q, N * d]``."""
    n, g = dims.num_attention_heads, dims.num_key_value_heads
    rows, t = qb.shape[0], k.shape[0]
    sliding = dims.layer_types[layer] == SLIDING
    span = min(t, dims.sliding_window + rows) if sliding else t
    lo = jnp.clip(start + rows - span, 0, t - span)
    ks = jax.lax.dynamic_slice_in_dim(k, lo, span, axis=0)
    vs = jax.lax.dynamic_slice_in_dim(v, lo, span, axis=0)
    kk = jnp.repeat(ks, n // g, axis=1)  # head n reads key-value head n // (n/g)
    vv = jnp.repeat(vs, n // g, axis=1)
    scores = jnp.einsum("qnd,snd->nqs", qb, kk) / math.sqrt(dims.head_dim)
    i = start + jnp.arange(rows)[:, None]
    j = lo + jnp.arange(span)[None, :]
    mask = j <= i
    if sliding:
        mask &= j > i - dims.sliding_window
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("nqs,snd->qnd", probs, vv).reshape(rows, -1)


def routing(p, h, dims: Dims):
    """``(sel [T, k], w [T, k])`` over ALL experts, from the layer's input."""
    top, sel = jax.lax.top_k(h @ _f32(p["router"]), dims.num_experts_per_tok)
    return sel, jax.nn.softmax(top, axis=-1)


def reglu(p, m):
    return (jax.nn.relu(m @ _f32(p["w1"])) * (m @ _f32(p["w3"]))) @ _f32(p["w2"])


def experts(p, m, sel, w, dims: Dims):
    """The held experts' part of the layer for the tokens ``m``."""
    def one(y, expert):  # a held expert: every token, times its weight or zero
        e, stacks = expert
        we = jnp.where(sel == dims.first_expert + e, w, 0.0).sum(-1)
        return y + we[:, None] * reglu(stacks, m), None

    stacks = {k: p[k] for k in ("w1", "w3", "w2")}
    return jax.lax.scan(
        one, jnp.zeros_like(m), (jnp.arange(p["w1"].shape[0]), stacks))[0]


def post(p, h, o, dims: Dims):
    """The layer's output from its input ``h`` and the heads' output ``o``."""
    sel, w = routing(p["moe"], h, dims)  # the INPUT, not the attention's sum
    h1 = h + o @ _f32(p["attn"]["wo"])
    m = rms(h1, p["pre_mlp_norm"]["scale"], dims.rms_norm_eps)
    return h1 + experts(p["moe"], m, sel, w, dims)


def _query_blocks(t: int, dims: Dims):
    qb = min(dims.q_block, t)
    return [(s, min(s + qb, t)) for s in range(0, t, qb)]


def layer_forward(p, h, dims: Dims, layer: int):
    q, k, v = pre(p, h, dims, layer)
    o = jnp.concatenate([core(q[s:e], k, v, s, dims, layer)
                         for s, e in _query_blocks(h.shape[0], dims)])
    return post(p, h, o, dims)


def embed(table, tokens):
    return _f32(table)[tokens]


def _layers(params):
    return [params[f"layer_{i}"] for i in range(
        sum(1 for k in params if k.startswith("layer_")))]


def _head(params):
    return {"final_norm": params["final_norm"], "lm_head": params["lm_head"]}


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
def _attention_stages(dims: Dims, sliding: bool) -> dict:
    """Jitted ``pre`` and ``core`` with their vjps, of a layer kind."""
    layer = next(i for i, kind in enumerate(dims.layer_types)
                 if (kind == SLIDING) == sliding)

    def pre_bwd(p, h, ct):
        return jax.vjp(lambda p_, h_: pre(p_, h_, dims, layer), p, h)[1](ct)

    def core_bwd(qb, k, v, start, ct):
        return jax.vjp(lambda q_, k_, v_: core(q_, k_, v_, start, dims, layer),
                       qb, k, v)[1](ct)

    return {
        "pre": jax.jit(lambda p, h: pre(p, h, dims, layer)),
        "core": jax.jit(lambda qb, k, v, s: core(qb, k, v, s, dims, layer)),
        "pre_bwd": jax.jit(pre_bwd), "core_bwd": jax.jit(core_bwd),
    }


@functools.lru_cache(maxsize=None)
def _ends(dims: Dims) -> dict:
    """The stages every layer shares, and the model's two ends."""
    def post_bwd(p, h, o, ct):
        return jax.vjp(lambda p_, h_, o_: post(p_, h_, o_, dims), p, h, o)[1](ct)

    def head_bwd(p, h, targets, scale):
        val, back = jax.vjp(lambda p_, h_: head_nll(p_, h_, targets, dims), p, h)
        return (val,) + back(scale)

    return {
        "post": jax.jit(lambda p, h, o: post(p, h, o, dims)),
        "post_bwd": jax.jit(post_bwd),
        "embed": jax.jit(embed),
        "embed_bwd": jax.jit(lambda table, tokens, ct: jax.vjp(
            lambda t_: embed(t_, tokens), table)[1](ct)[0]),
        "head_bwd": jax.jit(head_bwd),
        "logits": jax.jit(lambda p, h: head_logits(p, h, dims)),
    }


def _stages(dims: Dims, layer: int) -> dict:
    return {**_ends(dims),
            **_attention_stages(dims, dims.layer_types[layer] == SLIDING)}


def _attention_of(p, h, dims: Dims, layer: int):
    """``(q, k, v, o)`` of a layer, stage by stage."""
    st = _stages(dims, layer)
    q, k, v = st["pre"](p, h)
    o = jnp.concatenate([st["core"](q[s:e], k, v, s)
                         for s, e in _query_blocks(h.shape[0], dims)])
    return q, k, v, o


def _layer_backward(p, h, ct, dims: Dims, layer: int):
    """``(dp, dh)`` of one layer for the cotangent ``ct`` of its output."""
    st = _stages(dims, layer)
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
        o = _attention_of(p, hs[-1], dims, i)[-1]
        hs.append(_ends(dims)["post"](p, hs[-1], o))
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
    out = dict(dhead)
    layers = _layers(params)
    for i in reversed(range(len(layers))):
        out[f"layer_{i}"], ct = _layer_backward(layers[i], hs[i], ct, dims, i)
        hs[i + 1] = None  # the chain holds one layer's input at a time
    out["embed"] = ends["embed_bwd"](params["embed"], tokens, ct)
    return total, out
