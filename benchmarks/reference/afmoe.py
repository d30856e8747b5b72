"""Plain reference: the AFMoE decoder (Trinity family, ``model_type: afmoe``)
as a next-token task, in float32 ``jax.numpy``.

Imports nothing from the package: it is handed the parameter tree (``embed``,
``layer_<i>/{input_norm, attn/{wq, wk, wv, wg, wo, q_norm, k_norm},
post_attn_norm, pre_mlp_norm, mlp | moe/{router, expert_bias, w1, w3, w2,
shared}, post_mlp_norm}``, ``final_norm``, ``lm_head``), token ids and a
:class:`Dims`. No kernels, no recomputation, no batching over sites or
sequences: one sequence ``[T]`` at a time, an explicit ``[block, T]`` mask per
query block, a loop over the held experts (every token through every held
expert, times its routing weight or zero; ``lax.scan``, so that the body
compiles once). Callers run it under
``jax.default_matmul_precision("highest")``.

The equations (x: ``[T, hidden]``):

- ``h0 = E[tok] * sqrt(hidden)`` (``mup_enabled``);
- ``a = rms(h)``; ``q, k, v = a Wq, a Wk, a Wv`` as ``[T, heads, d]``; ``q, k
  <- rms_d(q), rms_d(k)``; on ``sliding_attention`` layers rotate-half rotary
  positions (``theta``), on ``full_attention`` layers none; ``scores = q k^T /
  sqrt(d)``, query head ``n`` with key-value head ``n // (heads / kv_heads)``,
  kept where ``j <= i`` and, on sliding layers, ``j > i - window``; softmax;
  ``o = P v``; ``o <- o * sigmoid(a Wg)``; ``h <- h + rms(o Wo)``;
- dense layers: ``m = rms(h)``; ``h <- h + rms((silu(m W1) * (m W3)) W2)``;
- MoE layers: ``s = sigmoid(m Wr)``; ``sel = top_k(s + b)``; ``w = s[sel]``,
  ``w <- w / (sum w + 1e-20)`` (``route_norm``), ``w <- route_scale * w``;
  ``y = shared(m) + sum over e in sel that are HELD of w_e expert_e(m)``
  (the held experts are ``first_expert .. first_expert + E - 1``, ``E`` the
  leading axis of the stacks: what the experts held elsewhere would add is
  left out, as in the program); ``h <- h + rms(y)``;
- ``logits = rms(h) W_head``; ``loss`` = mean over the ``T`` positions of the
  cross-entropy against the next token.

``grads`` is the same computation differentiated stage by stage (embedding;
per layer: projections, attention one query block at a time, output and MLP;
the head in sequence blocks), each stage's ``jax.vjp`` alone on the device,
so that the float32 gradients at the published widths fit one chip.
``benchmarks/tests/test_reference.py`` holds it equal to ``jax.grad(loss)``.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

SLIDING = "sliding_attention"


@dataclasses.dataclass(frozen=True)
class Dims:
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    layer_types: tuple = ()
    num_dense_layers: int = 2
    num_experts_per_tok: int = 8
    first_expert: int = 0
    route_norm: bool = True
    route_scale: float = 2.826
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    mup_enabled: bool = True
    q_block: int = 256  # query rows per explicit mask block
    head_block: int = 1024  # positions per block of the head and the loss

    @classmethod
    def of(cls, args: dict, **over) -> "Dims":
        """From a mapping that uses the published ``config.json`` names."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in {**args, **over}.items() if k in names}
        if "layer_types" in kw:
            kw["layer_types"] = tuple(kw["layer_types"])
        return cls(**kw)


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(scale)


def _rotary(x, theta):
    """``x [T, heads, d]``: rotate-half rotary embedding at positions 0..T-1."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + rot * sin


def swiglu(p, m):
    return (jax.nn.silu(m @ _f32(p["w1"])) * (m @ _f32(p["w3"]))) @ _f32(p["w2"])


# -- the three stages of a layer ----------------------------------------------


def pre(p, h, dims: Dims, layer: int):
    """``(q [T, N, d], k [T, G, d], v [T, G, d], gate [T, N*d])``."""
    a = rms(h, p["input_norm"]["scale"], dims.rms_norm_eps)
    at = p["attn"]
    n, g, d = dims.num_attention_heads, dims.num_key_value_heads, dims.head_dim
    q = (a @ _f32(at["wq"])).reshape(-1, n, d)
    k = (a @ _f32(at["wk"])).reshape(-1, g, d)
    v = (a @ _f32(at["wv"])).reshape(-1, g, d)
    q = rms(q, at["q_norm"]["scale"], dims.rms_norm_eps)
    k = rms(k, at["k_norm"]["scale"], dims.rms_norm_eps)
    if dims.layer_types[layer] == SLIDING:
        q, k = _rotary(q, dims.rope_theta), _rotary(k, dims.rope_theta)
    return q, k, v, jax.nn.sigmoid(a @ _f32(at["wg"]))


def core(qb, k, v, start, dims: Dims, layer: int):
    """Attention of the query rows ``start .. start + len(qb) - 1`` against
    all ``T`` keys under an explicit mask. ``qb [Q, N, d]`` -> ``[Q, N*d]``."""
    n, g = dims.num_attention_heads, dims.num_key_value_heads
    kk = jnp.repeat(k, n // g, axis=1)  # head n reads key-value head n // (n/g)
    vv = jnp.repeat(v, n // g, axis=1)
    scores = jnp.einsum("qnd,snd->nqs", qb, kk) / math.sqrt(dims.head_dim)
    i = start + jnp.arange(qb.shape[0])[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    mask = j <= i
    if dims.layer_types[layer] == SLIDING:
        mask &= j > i - dims.sliding_window
    scores = jnp.where(mask[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("nqs,snd->qnd", probs, vv).reshape(qb.shape[0], -1)


def routing(p, m, dims: Dims):
    """``(sel [T, k], w [T, k])`` over ALL experts."""
    s = jax.nn.sigmoid(m @ _f32(p["router"]))
    _, sel = jax.lax.top_k(s + jax.lax.stop_gradient(_f32(p["expert_bias"])),
                           dims.num_experts_per_tok)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if dims.route_norm:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return sel, w * dims.route_scale


def moe(p, m, dims: Dims):
    sel, w = routing(p, m, dims)
    y = swiglu(p["shared"], m) if "shared" in p else jnp.zeros_like(m)

    def one(y, expert):  # a held expert: every token, times its weight or zero
        e, stacks = expert
        we = jnp.where(sel == dims.first_expert + e, w, 0.0).sum(-1)
        return y + we[:, None] * swiglu(stacks, m), None

    stacks = {k: p[k] for k in ("w1", "w3", "w2")}
    return jax.lax.scan(one, y, (jnp.arange(p["w1"].shape[0]), stacks))[0]


def post(p, h, o, gate, dims: Dims, layer: int):
    eps = dims.rms_norm_eps
    h = h + rms((o * gate) @ _f32(p["attn"]["wo"]), p["post_attn_norm"]["scale"], eps)
    m = rms(h, p["pre_mlp_norm"]["scale"], eps)
    y = swiglu(p["mlp"], m) if layer < dims.num_dense_layers else moe(p["moe"], m, dims)
    return h + rms(y, p["post_mlp_norm"]["scale"], eps)


def _query_blocks(t: int, dims: Dims):
    qb = min(dims.q_block, t)
    return [(s, min(s + qb, t)) for s in range(0, t, qb)]


def layer_forward(p, h, dims: Dims, layer: int):
    q, k, v, gate = pre(p, h, dims, layer)
    o = jnp.concatenate([core(q[s:e], k, v, s, dims, layer)
                         for s, e in _query_blocks(h.shape[0], dims)])
    return post(p, h, o, gate, dims, layer)


def embed(table, tokens, dims: Dims):
    h = _f32(table)[tokens]
    return h * math.sqrt(table.shape[1]) if dims.mup_enabled else h


def head_logits(p, h, dims: Dims):
    return rms(h, p["final_norm"], dims.rms_norm_eps) @ _f32(p["lm_head"])


def head_nll(p, h, targets, dims: Dims):
    """Summed cross-entropy of the positions ``h [B, hidden]``."""
    logp = jax.nn.log_softmax(head_logits(p, h, dims), axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).sum()


def _layers(params):
    return [params[f"layer_{i}"] for i in range(
        sum(1 for k in params if k.startswith("layer_")))]


def hidden(params, tokens, dims: Dims):
    h = embed(params["embed"], tokens, dims)
    for i, p in enumerate(_layers(params)):
        h = layer_forward(p, h, dims, i)
    return h


def forward(params, tokens, dims: Dims):
    """Logits ``[T, vocab]`` for the ids ``tokens [T]``."""
    return head_logits(params, hidden(params, tokens, dims), dims)


def loss(params, sample, dims: Dims):
    """Mean next-token cross-entropy of ``sample [T + 1]``: the model reads
    the first ``T`` ids, the loss the last ``T``."""
    sample = sample.astype(jnp.int32)
    h = hidden(params, sample[:-1], dims)
    return head_nll(params, h, sample[1:], dims) / h.shape[0]


# -- the same, differentiated stage by stage -----------------------------------


def _stages(dims: Dims, layer: int) -> dict:
    """Jitted stage functions of a layer and their vjps. Layers that share a
    kind of attention share the programs of ``pre`` and ``core``, layers that
    share a kind of MLP those of ``post``."""
    first = lambda kind: next(i for i in range(len(dims.layer_types))
                              if kind(i) == kind(layer))
    attn = _attention_stages(dims, first(lambda i: dims.layer_types[i]))
    return {**attn, **_mlp_stages(dims, first(lambda i: i < dims.num_dense_layers))}


@functools.lru_cache(maxsize=None)
def _attention_stages(dims: Dims, layer: int) -> dict:
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
def _mlp_stages(dims: Dims, layer: int) -> dict:
    def post_bwd(p, h, o, gate, ct):
        return jax.vjp(lambda p_, h_, o_, g_: post(p_, h_, o_, g_, dims, layer),
                       p, h, o, gate)[1](ct)

    return {
        "post": jax.jit(lambda p, h, o, gate: post(p, h, o, gate, dims, layer)),
        "post_bwd": jax.jit(post_bwd),
    }


def _attention_of(p, h, dims: Dims, layer: int):
    """``(q, k, v, gate, o)`` of a layer, stage by stage."""
    st = _stages(dims, layer)
    q, k, v, gate = st["pre"](p, h)
    o = jnp.concatenate([st["core"](q[s:e], k, v, s)
                         for s, e in _query_blocks(h.shape[0], dims)])
    return q, k, v, gate, o


@functools.lru_cache(maxsize=None)
def _ends(dims: Dims):
    def head_bwd(p, h, targets, scale):
        val, back = jax.vjp(lambda p_, h_: head_nll(p_, h_, targets, dims), p, h)
        return (val,) + back(scale)

    return {
        "embed": jax.jit(lambda table, tokens: embed(table, tokens, dims)),
        "embed_bwd": jax.jit(lambda table, tokens, ct: jax.vjp(
            lambda t_: embed(t_, tokens, dims), table)[1](ct)[0]),
        "head_bwd": jax.jit(head_bwd),
        "logits": jax.jit(lambda p, h: head_logits(p, h, dims)),
    }


def _layer_backward(p, h, ct, dims: Dims, layer: int):
    """``(dp, dh)`` of one layer for the cotangent ``ct`` of its output."""
    st = _stages(dims, layer)
    q, k, v, gate, o = _attention_of(p, h, dims, layer)
    dp_post, dh, do, dgate = st["post_bwd"](p, h, o, gate, ct)
    dq, dk, dv = [], jnp.zeros_like(k), jnp.zeros_like(v)
    for s, e in _query_blocks(h.shape[0], dims):
        dqb, dkb, dvb = st["core_bwd"](q[s:e], k, v, s, do[s:e])
        dq.append(dqb)
        dk, dv = dk + dkb, dv + dvb
    dp_pre, dh_pre = st["pre_bwd"](p, h, (jnp.concatenate(dq), dk, dv, dgate))
    return jax.tree.map(jnp.add, dp_post, dp_pre), dh + dh_pre


def hidden_states(params, tokens, dims: Dims):
    """The input of every layer and the last layer's output, ``[L + 1]``."""
    hs = [_ends(dims)["embed"](params["embed"], tokens)]
    for i, p in enumerate(_layers(params)):
        _, _, _, gate, o = _attention_of(p, hs[-1], dims, i)
        hs.append(_stages(dims, i)["post"](p, hs[-1], o, gate))
    return hs


def logits(params, tokens, dims: Dims):
    """``forward``, one jitted layer at a time."""
    head = {"final_norm": params["final_norm"], "lm_head": params["lm_head"]}
    return _ends(dims)["logits"](head, hidden_states(params, tokens, dims)[-1])


def grads(params, sample, dims: Dims):
    """``(loss, gradient tree)`` of :func:`loss`, stage by stage."""
    sample = jnp.asarray(sample).astype(jnp.int32)
    tokens, targets = sample[:-1], sample[1:]
    t = tokens.shape[0]
    hs = hidden_states(params, tokens, dims)
    head = {"final_norm": params["final_norm"], "lm_head": params["lm_head"]}
    ends = _ends(dims)
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
