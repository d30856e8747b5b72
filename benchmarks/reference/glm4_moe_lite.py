"""Plain reference: the ``glm4_moe_lite`` decoder (GLM-4.7-Flash; DeepSeek-V2/V3's
layer equations, which this ``model_type`` reuses) as a next-token task with
one multi-token-prediction module, in float32 ``jax.numpy``.

Imports nothing from the package (of the benchmark, the sibling reference's
shared functions): it is handed the parameter tree (``embed``,
``layer_<i>/{input_norm, attn/{wq_a, q_a_norm, wq_b, wkv_a, kv_a_norm, wkv_b,
wo}, pre_mlp_norm, mlp | moe/{router, expert_bias, w1, w3, w2, shared}}``,
``final_norm``, ``lm_head`` and, with a second prediction depth, ``mtp/{enorm,
hnorm, eh_proj, block/<a layer>, norm}``), token ids and a :class:`Dims`. No
kernels, no recomputation, no batching over sites or sequences: one sequence
``[T]`` at a time, an explicit ``[block, T]`` causal mask per query block,
keys and values of all heads built whole, a loop over the held experts (every
token through every held expert, times its routing weight or zero;
``lax.scan``, so that the body compiles once). Callers run it under
``jax.default_matmul_precision("highest")``.

The equations (x: ``[T, hidden]``):

- ``h0 = E[tok]`` (no scale);
- ``a = rms(h)``; ``c_q = rms(a W_qa)``; ``q = c_q W_qb`` as ``[T, heads,
  nope + rope]``; ``[c_kv | k_r] = a W_kva``; ``c_kv <- rms(c_kv)``; ``[k_nope
  | v] = c_kv W_kvb`` as ``[T, heads, nope | v]``; rotate-half rotary
  positions (``theta``) over all ``rope`` dimensions of ``q``'s rotary slice
  and of ``k_r``, which every head shares; ``k = [k_nope | k_r]``; ``scores =
  q k^T / sqrt(nope + rope)``, kept where ``j <= i``; softmax; ``o = P v``;
  ``h <- h + o W_o`` (no norm on the branch's output, no gate);
- dense layers: ``m = rms(h)``; ``h <- h + (silu(m W1) * (m W3)) W2``;
- MoE layers: ``s = sigmoid(m Wr)``; ``sel = top_k(s + b)``; ``w = s[sel]``,
  ``w <- w / (sum w + 1e-20)`` (``route_norm``), ``w <- route_scale * w``;
  ``h <- h + shared(m) + sum over e in sel that are HELD of w_e expert_e(m)``
  (the held experts are ``first_expert .. first_expert + E - 1``, ``E`` the
  leading axis of the stacks: what the experts held elsewhere would add is
  left out, as in the program);
- ``logits = rms(h) W_head``; ``L_main`` = mean over the ``T`` positions of
  the cross-entropy against the next token;
- with ``num_nextn_predict_layers`` 1 (DeepSeek-V3 section 2.2), for the
  positions ``i < T - 1``: ``h'_i = [rms_e(E[t_{i+1}]) ; rms_h(h_i)] W_eh``,
  ``h`` the last layer's output before the final norm; one more MoE layer over
  those ``T - 1`` positions; ``logits' = rms_mtp(h') W_head`` (the main
  model's head and embedding) against ``t_{i+2}``; ``L_mtp`` their mean
  cross-entropy; ``loss = L_main + mtp_loss_weight * L_mtp``.

Departures from the published model, each also in the configuration's
``assumed``: the rotary pairing is rotate-half (the family's code rotates
interleaved pairs after a de-interleave of the projection's columns, which is
the same function of differently ordered columns: with seeded weights no
number moves); ``e_score_correction_bias`` is ``expert_bias`` and stays zero.

``grads`` is the same computation differentiated stage by stage (embedding;
per layer: projections, attention one query block at a time, output and MLP;
the heads in sequence blocks), each stage's ``jax.vjp`` alone on the device,
so that the float32 gradients at the published widths fit one chip. It hands
the gradient tree back ON THE HOST, part by part as the chain finishes them
(``jax.device_get``): 591 M float32 parameters with Adam's two moments and
two sites' gradients beside them are 11.8 GB of a 16 GB chip before the
chain's own working set, and did not fit (PERF.md, PR 32).
``benchmarks/tests/test_glm_cell.py`` holds it equal to ``jax.grad(loss)``.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

# what the two decoder families share is one set of plain functions: the norm,
# the rotary term, SwiGLU, the sigmoid router and the held experts' loop, the
# head and its loss (``reference/afmoe.py``, which reads of ``dims`` only the
# fields both Dims carry)
from benchmarks.reference.afmoe import (
    _f32,
    _rotary,
    head_logits,
    head_nll,
    moe,
    rms,
    swiglu,
)


@dataclasses.dataclass(frozen=True)
class Dims:
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    layer_types: tuple = ()  # the count is the tree's; every layer is full
    num_dense_layers: int = 1
    num_experts_per_tok: int = 4
    first_expert: int = 0
    route_norm: bool = True
    route_scale: float = 1.8
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    num_nextn_predict_layers: int = 1
    mtp_loss_weight: float = 0.3
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


def pre(p, h, dims: Dims):
    """``(q [T, N, nope + rope], k [T, N, nope + rope], v [T, N, v])``."""
    eps, at = dims.rms_norm_eps, p["attn"]
    n, dn, dr = dims.num_attention_heads, dims.qk_nope_head_dim, dims.qk_rope_head_dim
    a = rms(h, p["input_norm"]["scale"], eps)
    c_q = rms(a @ _f32(at["wq_a"]), at["q_a_norm"]["scale"], eps)
    q = (c_q @ _f32(at["wq_b"])).reshape(-1, n, dn + dr)
    latent = a @ _f32(at["wkv_a"])
    c_kv = rms(latent[:, : dims.kv_lora_rank], at["kv_a_norm"]["scale"], eps)
    kv = (c_kv @ _f32(at["wkv_b"])).reshape(-1, n, dn + dims.v_head_dim)
    q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], dims.rope_theta)], -1)
    k_r = _rotary(latent[:, None, dims.kv_lora_rank:], dims.rope_theta)
    k = jnp.concatenate([kv[..., :dn], jnp.repeat(k_r, n, axis=1)], -1)
    return q, k, kv[..., dn:]


def core(qb, k, v, start, dims: Dims):
    """Attention of the query rows ``start .. start + len(qb) - 1`` against
    all ``T`` keys under an explicit causal mask. ``qb [Q, N, d]`` -> ``[Q,
    N * v]``."""
    scores = jnp.einsum("qnd,snd->nqs", qb, k) / math.sqrt(qb.shape[-1])
    i = start + jnp.arange(qb.shape[0])[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    probs = jax.nn.softmax(jnp.where((j <= i)[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("nqs,snd->qnd", probs, v).reshape(qb.shape[0], -1)


def post(p, h, o, dims: Dims):
    """The layer's output from its input ``h`` and the heads' output ``o``;
    a layer is dense or an expert layer by what its tree holds."""
    h = h + o @ _f32(p["attn"]["wo"])
    m = rms(h, p["pre_mlp_norm"]["scale"], dims.rms_norm_eps)
    return h + (swiglu(p["mlp"], m) if "mlp" in p else moe(p["moe"], m, dims))


def _query_blocks(t: int, dims: Dims):
    qb = min(dims.q_block, t)
    return [(s, min(s + qb, t)) for s in range(0, t, qb)]


def layer_forward(p, h, dims: Dims):
    q, k, v = pre(p, h, dims)
    o = jnp.concatenate([core(q[s:e], k, v, s, dims)
                         for s, e in _query_blocks(h.shape[0], dims)])
    return post(p, h, o, dims)


def embed(table, tokens):
    return _f32(table)[tokens]


def mtp_input(p, h, e, dims: Dims):
    """The second depth's input: ``[rms_e(e) ; rms_h(h)] W_eh``."""
    eps = dims.rms_norm_eps
    both = jnp.concatenate([rms(e, p["enorm"]["scale"], eps),
                            rms(h, p["hnorm"]["scale"], eps)], -1)
    return both @ _f32(p["eh_proj"])


def _layers(params):
    return [params[f"layer_{i}"] for i in range(
        sum(1 for k in params if k.startswith("layer_")))]


def _main_head(params):
    return {"final_norm": params["final_norm"], "lm_head": params["lm_head"]}


def _mtp_head(params):
    """The main model's head form with the module's own norm."""
    return {"final_norm": params["mtp"]["norm"], "lm_head": params["lm_head"]}


def _deeper(params, dims: Dims) -> bool:
    return bool(dims.num_nextn_predict_layers) and "mtp" in params


def hidden(params, tokens, dims: Dims):
    h = embed(params["embed"], tokens)
    for p in _layers(params):
        h = layer_forward(p, h, dims)
    return h


def forward(params, tokens, dims: Dims):
    """Next-token logits ``[T, vocab]`` for the ids ``tokens [T]``."""
    return head_logits(_main_head(params), hidden(params, tokens, dims), dims)


def mtp_hidden(params, h, sample, dims: Dims):
    """The second depth's hidden states for the positions ``i < T - 1``, from
    the main model's ``h [T, hidden]`` and the sample's ids ``[T + 1]``."""
    x = mtp_input(params["mtp"], h[:-1], embed(params["embed"], sample[1:-1]),
                  dims)
    return layer_forward(params["mtp"]["block"], x, dims)


def losses(params, sample, dims: Dims):
    """``(L_main, L_mtp)`` of ``sample [T + 1]``: the model reads the first
    ``T`` ids; position ``i`` predicts ``t_{i+1}`` and, at the second depth,
    ``t_{i+2}``."""
    sample = sample.astype(jnp.int32)
    h = hidden(params, sample[:-1], dims)
    t = h.shape[0]
    main = head_nll(_main_head(params), h, sample[1:], dims) / t
    if not _deeper(params, dims):
        return main, jnp.float32(0.0)
    h2 = mtp_hidden(params, h, sample, dims)
    return main, head_nll(_mtp_head(params), h2, sample[2:], dims) / (t - 1)


def loss(params, sample, dims: Dims):
    main, deeper = losses(params, sample, dims)
    return main + dims.mtp_loss_weight * deeper if _deeper(params, dims) else main


# -- the same, differentiated stage by stage -----------------------------------


@functools.lru_cache(maxsize=None)
def _stages(dims: Dims) -> dict:
    """Jitted stage functions and their vjps; the layers share them (jit
    compiles one program a kind of MLP, by the tree it is handed)."""
    def pre_bwd(p, h, ct):
        return jax.vjp(lambda p_, h_: pre(p_, h_, dims), p, h)[1](ct)

    def core_bwd(qb, k, v, start, ct):
        return jax.vjp(lambda q_, k_, v_: core(q_, k_, v_, start, dims),
                       qb, k, v)[1](ct)

    def post_bwd(p, h, o, ct):
        return jax.vjp(lambda p_, h_, o_: post(p_, h_, o_, dims), p, h, o)[1](ct)

    def head_bwd(p, h, targets, scale):
        val, back = jax.vjp(lambda p_, h_: head_nll(p_, h_, targets, dims), p, h)
        return (val,) + back(scale)

    def mtp_input_bwd(p, h, e, ct):
        return jax.vjp(lambda p_, h_, e_: mtp_input(p_, h_, e_, dims), p, h, e)[1](ct)

    return {
        "pre": jax.jit(lambda p, h: pre(p, h, dims)),
        "core": jax.jit(lambda qb, k, v, s: core(qb, k, v, s, dims)),
        "post": jax.jit(lambda p, h, o: post(p, h, o, dims)),
        "pre_bwd": jax.jit(pre_bwd), "core_bwd": jax.jit(core_bwd),
        "post_bwd": jax.jit(post_bwd),
        "embed": jax.jit(embed),
        "embed_bwd": jax.jit(lambda table, tokens, ct: jax.vjp(
            lambda t_: embed(t_, tokens), table)[1](ct)[0]),
        "head_bwd": jax.jit(head_bwd),
        "logits": jax.jit(lambda p, h: head_logits(p, h, dims)),
        "mtp_input": jax.jit(lambda p, h, e: mtp_input(p, h, e, dims)),
        "mtp_input_bwd": jax.jit(mtp_input_bwd),
    }


def _attention_of(p, h, dims: Dims):
    """``(q, k, v, o)`` of a layer, stage by stage."""
    st = _stages(dims)
    q, k, v = st["pre"](p, h)
    o = jnp.concatenate([st["core"](q[s:e], k, v, s)
                         for s, e in _query_blocks(h.shape[0], dims)])
    return q, k, v, o


def _layer(p, h, dims: Dims):
    return _stages(dims)["post"](p, h, _attention_of(p, h, dims)[-1])


def _layer_backward(p, h, ct, dims: Dims):
    """``(dp, dh)`` of one layer for the cotangent ``ct`` of its output."""
    st = _stages(dims)
    q, k, v, o = _attention_of(p, h, dims)
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
    hs = [_stages(dims)["embed"](params["embed"], tokens)]
    for p in _layers(params):
        hs.append(_layer(p, hs[-1], dims))
    return hs


def logits(params, tokens, dims: Dims):
    """``forward``, one jitted stage at a time."""
    return _stages(dims)["logits"](
        _main_head(params), hidden_states(params, tokens, dims)[-1])


def _head_backward(head, h, targets, scale, dims: Dims):
    """``(summed loss * scale, dhead, dh)`` of the head over ``h [P,
    hidden]``, ``head_block`` positions at a time."""
    st, n = _stages(dims), h.shape[0]
    total, dhead, dh = 0.0, None, []
    hb = min(dims.head_block, n)
    for s in range(0, n, hb):
        val, dp, dhb = st["head_bwd"](head, h[s: s + hb], targets[s: s + hb],
                                      jnp.float32(scale))
        total = total + val * scale
        dhead = dp if dhead is None else jax.tree.map(jnp.add, dhead, dp)
        dh.append(dhb)
    return total, dhead, jnp.concatenate(dh)


def grads(params, sample, dims: Dims):
    """``(loss, gradient tree)`` of :func:`loss`, stage by stage; the tree's
    leaves are host arrays."""
    sample = jnp.asarray(sample).astype(jnp.int32)
    tokens, targets = sample[:-1], sample[1:]
    t = tokens.shape[0]
    st = _stages(dims)
    hs = hidden_states(params, tokens, dims)
    total, dhead, ct = _head_backward(
        _main_head(params), hs[-1], targets, 1.0 / t, dims)
    out = dict(dhead)
    d_embed = None
    if _deeper(params, dims):
        # the second depth: its head, its block, its input, then into the
        # main model's last hidden states, head and embedding
        pm, lam = params["mtp"], dims.mtp_loss_weight
        e = st["embed"](params["embed"], sample[1:-1])
        x = st["mtp_input"](pm, hs[-1][:-1], e)
        x1 = _layer(pm["block"], x, dims)
        deeper, dh2, dx1 = _head_backward(
            _mtp_head(params), x1, sample[2:], lam / (t - 1), dims)
        total = total + deeper
        out["lm_head"] = out["lm_head"] + dh2["lm_head"]
        d_block, dx = _layer_backward(pm["block"], x, dx1, dims)
        d_in, dh_last, de = st["mtp_input_bwd"](pm, hs[-1][:-1], e, dx)
        out["mtp"] = jax.device_get(
            {**d_in, "block": d_block, "norm": dh2["final_norm"]})
        ct = ct.at[:-1].add(dh_last)
        d_embed = st["embed_bwd"](params["embed"], sample[1:-1], de)
    out = jax.device_get(out)
    layers = _layers(params)
    for i in reversed(range(len(layers))):
        dp, ct = _layer_backward(layers[i], hs[i], ct, dims)
        out[f"layer_{i}"] = jax.device_get(dp)
        hs[i + 1] = None  # the chain holds one layer's input at a time
    d_table = st["embed_bwd"](params["embed"], tokens, ct)
    if d_embed is not None:
        d_table = d_table + d_embed
    out["embed"] = jax.device_get(d_table)
    return total, out
