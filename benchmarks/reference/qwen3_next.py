"""Plain reference: the ``qwen3_next`` decoder (Qwen3-Next-80B-A3B) as a
next-token task, in float32 ``jax.numpy``.

Imports nothing from the package (of the benchmark, the sibling reference's
norm, rotary term and SwiGLU): it is handed the parameter tree (``embed``,
``layer_<i>/{input_norm, linear_attn/{in_proj_qkvz, in_proj_ba, conv1d, A_log,
dt_bias, norm, out_proj} | attn/{wq, wg, wk, wv, wo, q_norm, k_norm},
pre_mlp_norm, moe/{router, w1, w3, w2, shared, shared_expert_gate}}``,
``final_norm``, ``lm_head``), token ids and a :class:`Dims`. No kernels, no
chunks, no batching over sites or sequences: one sequence ``[T]`` at a time,
the convolution as the sum over its shifted copies, the delta rule POSITION BY
POSITION, an explicit mask per block of query rows, a loop over the held
experts (every token through every held expert, times its routing weight or
zero). Callers run it under ``jax.default_matmul_precision("highest")``.

The equations (h: ``[T, hidden]``, a layer's input; ``layer_types`` names the
token mixer of each layer; two pre-norms a block and no post-norm; no bias):

- ``h0 = E[tok]`` (no scale);
- a ``linear_attention`` layer, Gated DeltaNet: ``a = rms(h)``; ``[q | k | v
  | z] = a W_qkvz`` (each part head-major: ``q``, ``k`` of ``linear_num_key_heads``
  x ``linear_key_head_dim``, ``v``, ``z`` of ``linear_num_value_heads`` x
  ``linear_value_head_dim``), ``[b | a'] = a W_ba``; ``c_t = sum_{j=0..L-1}
  f_j * u_{t-(L-1)+j}`` over the channels ``u = q | k | v`` (``L`` =
  ``linear_conv_kernel_dim``, zeros before position 0, its LAST tap on the
  position itself); ``q, k, v = silu(c)``; value head ``h`` reads key head ``h
  // (value heads / key heads)``; ``q <- l2norm(q) / sqrt(d_k)``, ``k <-
  l2norm(k)`` (``x / sqrt(sum x^2 + 1e-6)``); ``beta = sigmoid(b)``; ``g =
  -exp(A_log) * softplus(a' + dt_bias)``; per value head, from ``S = 0
  [d_k, d_v]``, position by position: ``S <- exp(g_t) S``; ``S <- S + k_t
  (beta_t (v_t - S^T k_t))^T``; ``o_t = S^T q_t``; then ``o <- rms_dv(o) *
  silu(z)`` (a weight of ``d_v``) and ``h' = h + o W_out``;
- a ``full_attention`` layer: ``a = rms(h)``; ``q, gate = a Wq, a Wg`` as
  ``[T, heads, d]``, ``k, v = a Wk, a Wv`` as ``[T, kv_heads, d]``; ``q, k <-
  rms_d(q), rms_d(k)``, THEN rotate-half rotary positions (``theta``) over the
  first ``d * partial_rotary_factor`` dimensions of each head, the rest as
  they are; ``scores = q k^T / sqrt(d)``, query head ``n`` with key-value head
  ``n // (heads / kv_heads)``, kept where ``j <= i``; softmax; ``o = P v``;
  ``h' = h + (o * sigmoid(gate)) Wo``;
- every layer: ``m = rms(h')``; ``p = softmax(m Wr)`` over ALL experts; ``sel
  = top_k(p)``; ``w = p[sel] / sum p[sel]``; ``y = sigmoid(m w_sg) *
  swiglu_shared(m) + sum over e in sel that are HELD of w_e expert_e(m)``
  (SwiGLU; the held experts are ``first_expert .. first_expert + E - 1``,
  ``E`` the leading axis of the stacks: what the experts held elsewhere would
  add is left out, as in the program); ``h_next = h' + y``;
- ``logits = rms(h) W_head``; ``loss`` = mean over the ``T`` positions of the
  cross-entropy against the next token.

Departures from the published model, each also in the configuration's
``assumed``: the norms' weights stored as ``1 + w`` (the family's zero-centred
form, the same function), the projections' column layouts, no balance term,
no multi-token prediction.

The recurrence runs as a ``lax.scan`` over blocks of ``recurrence_block``
positions, each block's own scan under ``jax.checkpoint``: its backward pass
holds the states at the blocks' edges only (at 8,192 positions and 32 heads of
128 x 128: 268 MB, where every position's state would be 17 GB).

``grads`` is the same computation differentiated stage by stage (embedding; a
``linear_attention`` layer whole; an attention layer as projections, attention
one query block at a time, output and experts; the head in sequence blocks),
each stage's ``jax.vjp`` alone on the device.
``benchmarks/tests/test_qwen3_next_cell.py`` holds it equal to
``jax.grad(loss)``.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

# the plain functions this decoder shares with Trinity's: the norm, the rotary
# term and SwiGLU (``reference/afmoe.py``)
from benchmarks.reference.afmoe import _f32, _rotary, rms, swiglu

LINEAR = "linear_attention"


@dataclasses.dataclass(frozen=True)
class Dims:
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    layer_types: tuple = ()
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts_per_tok: int = 10
    first_expert: int = 0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000000.0
    q_block: int = 256  # query rows per explicit mask block
    head_block: int = 1024  # positions per block of the head and the loss
    recurrence_block: int = 64  # positions per checkpointed block of the recurrence

    @classmethod
    def of(cls, args: dict, **over) -> "Dims":
        """From a mapping that uses the program's ``lm_args`` names."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in {**args, **over}.items() if k in names}
        if "layer_types" in kw:
            kw["layer_types"] = tuple(kw["layer_types"])
        return cls(**kw)


# -- Gated DeltaNet -------------------------------------------------------------


def l2norm(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def delta_rule(q, k, v, beta, g, block: int):
    """The gated delta rule position by position: ``q, k [T, H, d_k]``, ``v
    [T, H, d_v]``, ``beta, g [T, H]`` -> ``o [T, H, d_v]``, from ``S = 0``."""
    t, heads, dk = k.shape
    dv = v.shape[-1]

    def position(s, x):
        qt, kt, vt, bt, gt = x
        s = jnp.exp(gt)[:, None, None] * s
        err = vt - jnp.einsum("hkv,hk->hv", s, kt)
        s = s + jnp.einsum("hk,hv->hkv", kt, bt[:, None] * err)
        return s, jnp.einsum("hkv,hk->hv", s, qt)

    @jax.checkpoint
    def one_block(s, xs):
        return jax.lax.scan(position, s, xs)

    pad = -t % block  # positions past the end: beta = g = 0 change nothing before

    def blocks(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((-1, block) + x.shape[1:])

    _, o = jax.lax.scan(one_block, jnp.zeros((heads, dk, dv), jnp.float32),
                        tuple(blocks(x) for x in (q, k, v, beta, g)))
    return o.reshape((-1, heads, dv))[:t]


def linear_attention(p, a, dims: Dims):
    """Gated DeltaNet of the normed tokens ``a [T, hidden]``."""
    nk, nv = dims.linear_num_key_heads, dims.linear_num_value_heads
    dk, dv = dims.linear_key_head_dim, dims.linear_value_head_dim
    keys, values, t = nk * dk, nv * dv, a.shape[0]
    qkvz = a @ _f32(p["in_proj_qkvz"])
    u, f, taps = qkvz[:, : 2 * keys + values], _f32(p["conv1d"]), dims.linear_conv_kernel_dim
    conv = jnp.zeros_like(u)
    for j in range(taps):  # tap j reads the position taps - 1 - j back
        back = taps - 1 - j
        shifted = jnp.concatenate([jnp.zeros_like(u[:back]), u[: t - back]])
        conv = conv + f[:, j] * shifted
    mixed = jax.nn.silu(conv)
    q = l2norm(mixed[:, :keys].reshape(t, nk, dk))
    k = l2norm(mixed[:, keys: 2 * keys].reshape(t, nk, dk))
    q = jnp.repeat(q, nv // nk, axis=1) / math.sqrt(dk)  # value head h: key head h // (nv / nk)
    k = jnp.repeat(k, nv // nk, axis=1)
    v = mixed[:, 2 * keys:].reshape(t, nv, dv)
    ba = a @ _f32(p["in_proj_ba"])
    beta = jax.nn.sigmoid(ba[:, :nv])
    g = -jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(ba[:, nv:] + _f32(p["dt_bias"]))
    o = delta_rule(q, k, v, beta, g, dims.recurrence_block)
    z = qkvz[:, 2 * keys + values:].reshape(t, nv, dv)
    o = rms(o, p["norm"]["scale"], dims.rms_norm_eps) * jax.nn.silu(z)
    return o.reshape(t, values) @ _f32(p["out_proj"])


# -- gated attention over partial rotary ----------------------------------------


def _partial_rotary(x, dims: Dims):
    r = int(dims.head_dim * dims.partial_rotary_factor)
    return jnp.concatenate([_rotary(x[..., :r], dims.rope_theta), x[..., r:]], -1)


def pre(p, h, dims: Dims):
    """An attention layer's ``(q [T, N, d], k [T, G, d], v [T, G, d], gate
    [T, N * d])``."""
    a = rms(h, p["input_norm"]["scale"], dims.rms_norm_eps)
    at = p["attn"]
    n, g, d = dims.num_attention_heads, dims.num_key_value_heads, dims.head_dim
    q = (a @ _f32(at["wq"])).reshape(-1, n, d)
    k = (a @ _f32(at["wk"])).reshape(-1, g, d)
    v = (a @ _f32(at["wv"])).reshape(-1, g, d)
    q = rms(q, at["q_norm"]["scale"], dims.rms_norm_eps)
    k = rms(k, at["k_norm"]["scale"], dims.rms_norm_eps)
    return (_partial_rotary(q, dims), _partial_rotary(k, dims), v,
            jax.nn.sigmoid(a @ _f32(at["wg"])))


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


# -- the experts -------------------------------------------------------------------


def routing(p, m, dims: Dims):
    """``(sel [T, k], w [T, k])``: a softmax over ALL experts, the top k,
    renormalised over the chosen."""
    probs = jax.nn.softmax(m @ _f32(p["router"]), axis=-1)
    top, sel = jax.lax.top_k(probs, dims.num_experts_per_tok)
    return sel, top / top.sum(-1, keepdims=True)


def moe(p, m, dims: Dims):
    sel, w = routing(p, m, dims)
    y = jax.nn.sigmoid(m @ _f32(p["shared_expert_gate"])) * swiglu(p["shared"], m)

    def one(y, expert):  # a held expert: every token, times its weight or zero
        e, stacks = expert
        we = jnp.where(sel == dims.first_expert + e, w, 0.0).sum(-1)
        return y + we[:, None] * swiglu(stacks, m), None

    stacks = {k: p[k] for k in ("w1", "w3", "w2")}
    return jax.lax.scan(one, y, (jnp.arange(p["w1"].shape[0]), stacks))[0]


def ffn(p, h1, dims: Dims):
    """The block's second half: the router, the shared and the held experts."""
    return h1 + moe(p["moe"], rms(h1, p["pre_mlp_norm"]["scale"], dims.rms_norm_eps), dims)


def post(p, h, o, gate, dims: Dims):
    """An attention layer's output from its input and the heads' output."""
    return ffn(p, h + (o * gate) @ _f32(p["attn"]["wo"]), dims)


def linear_layer(p, h, dims: Dims):
    a = rms(h, p["input_norm"]["scale"], dims.rms_norm_eps)
    return ffn(p, h + linear_attention(p["linear_attn"], a, dims), dims)


def _query_blocks(t: int, dims: Dims):
    qb = min(dims.q_block, t)
    return [(s, min(s + qb, t)) for s in range(0, t, qb)]


def layer_forward(p, h, dims: Dims, layer: int):
    if dims.layer_types[layer] == LINEAR:
        return linear_layer(p, h, dims)
    q, k, v, gate = pre(p, h, dims)
    o = jnp.concatenate([core(q[s:e], k, v, s, dims)
                         for s, e in _query_blocks(h.shape[0], dims)])
    return post(p, h, o, gate, dims)


# -- the model's two ends ---------------------------------------------------------


def embed(table, tokens):
    return _f32(table)[tokens]


def head_logits(p, h, dims: Dims):
    return rms(h, p["final_norm"], dims.rms_norm_eps) @ _f32(p["lm_head"])


def head_nll(p, h, targets, dims: Dims):
    """Summed cross-entropy of the positions ``h [B, hidden]``."""
    logp = jax.nn.log_softmax(head_logits(p, h, dims), axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).sum()


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
def _stages(dims: Dims) -> dict:
    """Jitted stage functions and their vjps."""

    def vjp_of(fn):
        def bwd(*args):
            *primals, ct = args
            return jax.vjp(fn, *primals)[1](ct)

        return jax.jit(bwd)

    def post_(p, h, o, gate):
        return post(p, h, o, gate, dims)

    def linear_(p, h):
        return linear_layer(p, h, dims)

    def pre_(p, h):
        return pre(p, h, dims)

    def core_(qb, k, v, start):
        return core(qb, k, v, start, dims)

    def core_bwd(qb, k, v, start, ct):
        return jax.vjp(lambda q_, k_, v_: core(q_, k_, v_, start, dims),
                       qb, k, v)[1](ct)

    def head_bwd(p, h, targets, scale):
        val, back = jax.vjp(lambda p_, h_: head_nll(p_, h_, targets, dims), p, h)
        return (val,) + back(scale)

    return {
        "pre": jax.jit(pre_), "pre_bwd": vjp_of(pre_),
        "core": jax.jit(core_), "core_bwd": jax.jit(core_bwd),
        "post": jax.jit(post_), "post_bwd": vjp_of(post_),
        "linear": jax.jit(linear_), "linear_bwd": vjp_of(linear_),
        "embed": jax.jit(embed),
        "embed_bwd": jax.jit(lambda table, tokens, ct: jax.vjp(
            lambda t_: embed(t_, tokens), table)[1](ct)[0]),
        "head_bwd": jax.jit(head_bwd),
        "logits": jax.jit(lambda p, h: head_logits(p, h, dims)),
    }


def _attention_of(p, h, dims: Dims):
    """``(q, k, v, gate, o)`` of an attention layer, stage by stage."""
    st = _stages(dims)
    q, k, v, gate = st["pre"](p, h)
    o = jnp.concatenate([st["core"](q[s:e], k, v, s)
                         for s, e in _query_blocks(h.shape[0], dims)])
    return q, k, v, gate, o


def _layer_backward(p, h, ct, dims: Dims, layer: int):
    """``(dp, dh)`` of one layer for the cotangent ``ct`` of its output."""
    st = _stages(dims)
    if dims.layer_types[layer] == LINEAR:
        return st["linear_bwd"](p, h, ct)
    q, k, v, gate, o = _attention_of(p, h, dims)
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
    st = _stages(dims)
    hs = [st["embed"](params["embed"], tokens)]
    for i, p in enumerate(_layers(params)):
        if dims.layer_types[i] == LINEAR:
            hs.append(st["linear"](p, hs[-1]))
        else:
            _, _, _, gate, o = _attention_of(p, hs[-1], dims)
            hs.append(st["post"](p, hs[-1], o, gate))
    return hs


def logits(params, tokens, dims: Dims):
    """``forward``, one jitted stage at a time."""
    return _stages(dims)["logits"](
        _head(params), hidden_states(params, tokens, dims)[-1])


def grads(params, sample, dims: Dims):
    """``(loss, gradient tree)`` of :func:`loss`, stage by stage."""
    sample = jnp.asarray(sample).astype(jnp.int32)
    tokens, targets = sample[:-1], sample[1:]
    t = tokens.shape[0]
    hs = hidden_states(params, tokens, dims)
    head, st = _head(params), _stages(dims)
    total, dhead, dh = 0.0, None, []
    hb = min(dims.head_block, t)
    for s in range(0, t, hb):
        val, dp, dhb = st["head_bwd"](head, hs[-1][s: s + hb],
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
    out["embed"] = st["embed_bwd"](params["embed"], tokens, ct)
    return total, out
