"""The ``smallthinker`` decoder (models/afmoe.py: the router reads the block's
input before attention, a softmax over the chosen experts' logits, ReGLU
experts, no shared expert and no dense layer, a full layer without positions
first in each period) against its plain reference
(benchmarks/reference/smallthinker.py), at toy widths on the CPU.

Held: logits, loss and gradients group by group, first and last share of the
experts; the eight shares of a layer add up to the uncut layer; the router
reads the block's input and neither its norm nor anything attention has
touched; the ReGLU hand backward against ``jax.grad`` of the plain form under
the site vmap, through both ways back to token order; a windowed layer with
seven query heads a key-value head; what the registry refuses; one
``FederatedTrainer`` round equals the reference round; the comparison notices
each term that goes missing.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib.refcheck_lm import GROUPS, group_cosines, group_of
from benchmarks.reference import federated as fed
from benchmarks.reference import smallthinker as ref
from dinunet_implementations_tpu.core.config import NNComputation, TrainConfig
from dinunet_implementations_tpu.data.api import SiteArrays
from dinunet_implementations_tpu.models import afmoe
from dinunet_implementations_tpu.models.afmoe import FULL, SLIDING, SMALLTHINKER
from dinunet_implementations_tpu.runner.registry import (
    afmoe_layer_types,
    get_task,
)
from dinunet_implementations_tpu.trainer.loop import FederatedTrainer

T, VOCAB, EXPERTS, HELD, TOP_K, WINDOW, HIDDEN = 32, 96, 64, 8, 6, 8, 64
TOY = dict(
    model_type=SMALLTHINKER, seq_len=T, vocab_size=VOCAB, vocab_rows=VOCAB,
    hidden_size=HIDDEN, num_attention_heads=14, num_key_value_heads=2,
    head_dim=16, moe_intermediate_size=32, num_experts=EXPERTS,
    num_experts_per_tok=TOP_K, num_shared_experts=0, experts_held=HELD,
    first_expert=0, num_hidden_layers=2, num_dense_layers=0,
    sliding_window_layout=(0, 1), rope_layout=(0, 1), sliding_window=WINDOW,
    rope_theta=1500000.0, rms_norm_eps=1e-6, q_block=8, kv_chunk=16,
    loss_block=8,
)
#: the check's groups this model has parameters in
MY_GROUPS = tuple(g for g in GROUPS if g not in ("dense_mlp", "shared"))
CONFIG = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "configs",
                      "smallthinker-21b-ep8.json")


def toy_cfg(**over) -> TrainConfig:
    train = {k: over.pop(k) for k in list(over)
             if k in ("num_sites", "batch_size", "learning_rate")}
    return TrainConfig(task_id=NNComputation.TASK_LM, **train).with_overrides(
        {"lm_args": {**TOY, **over}})


def build(**over):
    cfg = toy_cfg(**over)
    model = get_task(cfg.task_id).build_model(cfg)
    dims = ref.Dims.of(dataclasses.asdict(cfg.lm_args),
                       layer_types=afmoe_layer_types(cfg.lm_args),
                       q_block=8, head_block=8)
    return cfg, model, dims


def tokens(seed: int, rows: int = 2, t: int = T):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, t + 1), 0, VOCAB)


def init_params(model, scale: float = 5.0):
    """Seeded random weights, the matrices scaled up so that every term of
    the block moves the result, the norms' scales drawn too (at ones a norm
    scales a token's router logits by one positive number and moves no
    choice)."""
    params = model.init({"params": jax.random.PRNGKey(0)}, tokens(9),
                        train=True)["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    return jax.tree.map(
        lambda a: a * scale if a.ndim >= 2
        else a * (1.0 + 0.5 * jax.random.normal(next(keys), a.shape)), params)


def task_loss(model, x):
    return jax.jit(
        lambda p: model.task_loss({"params": p}, x, jnp.ones(x.shape[0])))


def rel_rms(got, want) -> float:
    return float(jnp.sqrt(jnp.mean((got - want) ** 2) / jnp.mean(want ** 2)))


def ref_logits(params, x, dims):
    with jax.default_matmul_precision("highest"):
        forward = jax.jit(lambda row: ref.forward(params, row[:-1], dims))
        return jnp.stack([forward(row) for row in x])


# -- the model against the reference -------------------------------------------


def test_the_tree_holds_what_the_type_has_and_nothing_else():
    _, model, _ = build()
    params = init_params(model)
    assert sorted(params) == ["embed", "final_norm", "layer_0", "layer_1",
                              "lm_head"]
    for layer in (params["layer_0"], params["layer_1"]):
        assert sorted(layer) == ["attn", "input_norm", "moe", "pre_mlp_norm"]
        assert sorted(layer["attn"]) == ["wk", "wo", "wq", "wv"]
        assert sorted(layer["moe"]) == ["router", "w1", "w2", "w3"]
        assert layer["moe"]["router"].shape == (HIDDEN, EXPERTS)
        assert layer["moe"]["w1"].shape == (HELD, HIDDEN, 32)
    paths = [tuple(k.key for k in path) for path, _ in
             jax.tree_util.tree_leaves_with_path(params)]
    by_group = {g: [p for p in paths if group_of(p) == g] for g in GROUPS}
    assert {g for g, found in by_group.items() if found} == set(MY_GROUPS)
    assert not model.mup_enabled


@pytest.mark.parametrize("first", [0, EXPERTS - HELD])
def test_logits_loss_and_gradients_match_the_reference(first):
    _, model, dims = build(first_expert=first)
    params, x = init_params(model), tokens(1)
    got, inter = model.apply({"params": params}, x, mutable=["intermediates"])
    assert float(jnp.abs(got - ref_logits(params, x, dims)).max()) < 5e-5
    # the routing counter, as MoE sows it: every layer is an expert layer
    assert sorted(inter["intermediates"]) == ["layer_0", "layer_1"]
    for v in inter["intermediates"].values():
        assert v["moe"]["held_counts"][0].shape == (2, HELD)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(task_loss(model, x[:1]))(params)
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, x[0], dims)))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want)):
        scale = max(float(jnp.abs(w).max()), 1e-3)
        assert float(jnp.abs(g - w).max()) < 2e-4 * scale, jax.tree_util.keystr(path)
    cosines = group_cosines(grads, want)
    assert set(cosines) == set(MY_GROUPS) | {"all"}
    assert min(cosines.values()) > 1 - 1e-6, cosines
    # the router's gradient reaches the block's input past the attention:
    # it is not zero, and the reference's is the same
    assert float(jnp.abs(want["layer_1"]["moe"]["router"]).max()) > 1e-4


def test_vmap_over_sites_folds_the_expert_layer():
    _, model, _ = build()
    params = init_params(model)
    xs = jnp.stack([tokens(s) for s in (3, 4)])
    vg = jax.jit(jax.value_and_grad(
        lambda p, x: model.task_loss({"params": p}, x, jnp.ones(2))))
    losses, grads = jax.jit(jax.vmap(vg, in_axes=(None, 0)))(params, xs)
    for s in range(2):
        loss, g = vg(params, xs[s])
        assert abs(float(losses[s]) - float(loss)) < 1e-5
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(g)):
            assert float(jnp.abs(a[s] - b).max()) <= 1e-4 * max(
                float(jnp.abs(b).max()), 1e-3)


# -- the shares ----------------------------------------------------------------


def _cut(params, first, held):
    moe = {k: (v[first: first + held] if k in ("w1", "w3", "w2") else v)
           for k, v in params["moe"].items()}
    return {**params, "moe": moe}


@pytest.mark.parametrize("layer", [0, 1])  # the full layer, a sliding one
def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer(layer):
    """One layer, its 64 experts held 8 a share by 8 shares (``first_expert``
    0, 8, ..., 56): what the shares compute alike (the residual stream and
    attention) counted once plus every share's routed part equals the uncut
    reference layer; a share weights by the softmax over all six chosen, not
    over the ones it holds."""
    _, whole, dims = build(experts_held=EXPERTS)
    params = init_params(whole)[f"layer_{layer}"]
    h = jax.random.normal(jax.random.PRNGKey(5), (1, T, HIDDEN))
    with jax.default_matmul_precision("highest"):
        run = jax.jit(lambda p: ref.layer_forward(p, h[0], dims, layer))
        uncut, alike = run(params), run(_cut(params, 0, 0))  # no routed expert
        total = alike
        for first in range(0, EXPERTS, HELD):
            share = build(first_expert=first)[1]
            block = jax.jit(afmoe.Block(share.dims, layer).apply)
            total = total + block({"params": _cut(params, first, HELD)}, h)[0] - alike
    assert float(jnp.abs(uncut - alike).max()) > 0.1  # the experts did something
    assert float(jnp.abs(total - uncut).max()) < 1e-4 * float(jnp.abs(uncut).max())


# -- what the router reads -------------------------------------------------------


def _layer_routed_on(p, h, dims, layer, reads: str):
    """The reference's layer with the router handed something else."""
    eps = dims.rms_norm_eps
    q, k, v = ref.pre(p, h, dims, layer)
    o = jnp.concatenate([ref.core(q[s:e], k, v, s, dims, layer)
                         for s, e in ref._query_blocks(h.shape[0], dims)])
    h1 = h + o @ p["attn"]["wo"]
    m = ref.rms(h1, p["pre_mlp_norm"]["scale"], eps)
    x = {"input": h, "normed_input": ref.rms(h, p["input_norm"]["scale"], eps),
         "post_attention": h1, "pre_mlp_norm": m}[reads]
    sel, w = ref.routing(p["moe"], x, dims)
    return h1 + ref.experts(p["moe"], m, sel, w, dims)


@pytest.mark.parametrize("reads", ["input", "normed_input", "post_attention",
                                   "pre_mlp_norm"])
def test_the_router_reads_the_blocks_input(reads):
    """The block equals the reference layer whose router reads the block's
    un-normed INPUT, and differs by a routed expert's whole output from the
    layers whose router reads the normed input, the state after attention
    (where the other two types route) or its norm."""
    _, model, dims = build(experts_held=EXPERTS)
    params = init_params(model)["layer_1"]
    h = jax.random.normal(jax.random.PRNGKey(6), (1, T, HIDDEN))
    got = jax.jit(afmoe.Block(model.dims, 1).apply)({"params": params}, h)[0]
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: _layer_routed_on(p, h[0], dims, 1, reads))(params)
    err = float(jnp.abs(got - want).max()) / float(jnp.abs(want).max())
    if reads == "input":
        assert err < 1e-5
        with jax.default_matmul_precision("highest"):
            same = ref.layer_forward(params, h[0], dims, 1)
        assert float(jnp.abs(want - same).max()) < 1e-5  # jit or not
    else:
        assert err > 0.05, (reads, err)


# -- the ReGLU experts' own backward ----------------------------------------------


def _plain_experts(m, sel, w, w1, w3, w2, first):
    """``sum over the held e in sel of w_e (relu(m w1_e) * (m w3_e)) w2_e``,
    every token through every held expert."""
    y = jnp.zeros_like(m)
    for e in range(w1.shape[0]):
        we = jnp.where(sel == first + e, w, 0.0).sum(-1)
        y = y + we[:, None] * ((jax.nn.relu(m @ w1[e]) * (m @ w3[e])) @ w2[e])
    return y


@pytest.mark.parametrize("combine", ["whole", "by_slot"])
def test_reglu_backward_is_jax_grad_of_the_plain_form_under_the_site_vmap(
        monkeypatch, combine):
    """``routed_experts(..., relu=True)`` under the trainer's fold (vmap over
    sites, the stacks shared) against ``jax.grad`` of the plain form: the
    result and the cotangents of tokens, routing weights and the three
    stacks; back to token order in one gather and, past COMBINE_BYTES, a
    token's slots one at a time."""
    if combine == "by_slot":
        monkeypatch.setattr(afmoe, "COMBINE_BYTES", 0)
    afmoe._expert_layer.cache_clear()
    first, sites, n = 8, 3, 24
    k = jax.random.split(jax.random.PRNGKey(2), 6)
    m = jax.random.normal(k[0], (sites, n, HIDDEN))
    logits = jax.random.normal(k[1], (sites, n, EXPERTS))
    sel, w = afmoe.route_chosen(logits, TOP_K)
    w1 = 0.3 * jax.random.normal(k[2], (HELD, HIDDEN, 32))
    w3 = 0.3 * jax.random.normal(k[3], (HELD, HIDDEN, 32))
    w2 = 0.3 * jax.random.normal(k[4], (HELD, 32, HIDDEN))
    probe = jax.random.normal(k[5], (sites, n, HIDDEN))

    def through(fn):
        def site(m, sel, w, probe, w1, w3, w2):
            return (fn(m, sel, w, w1, w3, w2) * probe).sum()
        grad = jax.value_and_grad(site, argnums=(0, 2, 4, 5, 6))
        return jax.jit(jax.vmap(grad, in_axes=(0, 0, 0, 0, None, None, None)))(
            m, sel, w, probe, w1, w3, w2)

    try:
        got = through(lambda *a: afmoe.routed_experts(*a, first, None, True))
    finally:
        afmoe._expert_layer.cache_clear()
    with jax.default_matmul_precision("highest"):
        want = through(lambda *a: _plain_experts(*a, first))
    assert float(jnp.abs(want[1][2]).max()) > 1e-2  # the stacks' cotangents
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
        assert float(jnp.abs(a - b).max()) <= 2e-5 * max(
            float(jnp.abs(b).max()), 1.0)
    # and the other activation is another function
    silu = through(lambda *a: afmoe.routed_experts(*a, first, None, False))
    assert float(jnp.abs(silu[0] - want[0]).max()) > 0.1


def test_route_chosen_is_a_softmax_over_the_chosen_alone():
    logits = jax.random.normal(jax.random.PRNGKey(3), (5, EXPERTS))
    sel, w = afmoe.route_chosen(logits, TOP_K)
    top = np.sort(np.asarray(logits), axis=-1)[:, ::-1][:, :TOP_K]
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(logits), np.asarray(sel), -1), top)
    np.testing.assert_allclose(
        np.asarray(w), np.exp(top) / np.exp(top).sum(-1, keepdims=True), rtol=1e-6)
    over_all = jax.nn.softmax(logits, -1)
    assert float(jnp.take_along_axis(over_all, sel, -1).sum(-1).max()) < 0.9


# -- attention -----------------------------------------------------------------


def _qkv(t, heads=14, kv_heads=2, d=16):
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(k[0], (1, t, heads, d)),
            jax.random.normal(k[1], (1, t, kv_heads, d)),
            jax.random.normal(k[2], (1, t, kv_heads, d)))


@pytest.mark.parametrize("path,t,window", [
    ("xla", T, WINDOW), ("xla", T, None),
    ("kernel", 256, 128), ("kernel", 256, None)])
def test_seven_query_heads_a_key_value_head_against_the_reference(path, t, window):
    """A layer's attention with seven query heads a key-value head, windowed
    with ``T`` several windows long and full, against the reference's blocks
    (which hold a sliding block against the keys its rows can reach) and
    against plain attention over ``[T, T]``."""
    q, k, v = _qkv(t)
    kinds = (SLIDING,) if window else (FULL,)
    dims = ref.Dims(num_attention_heads=14, num_key_value_heads=2, head_dim=16,
                    sliding_window=window or 0, layer_types=kinds, q_block=8)
    want = jnp.concatenate([ref.core(q[0, s:e], k[0], v[0], s, dims, 0)
                            for s, e in ref._query_blocks(t, dims)])
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    keep = (j <= i) & ((j > i - window) if window else True)
    s = jnp.einsum("tnd,snd->nts", q[0], jnp.repeat(k[0], 7, axis=1)) / 4.0
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    plain = jnp.einsum("nts,snd->tnd", p, jnp.repeat(v[0], 7, axis=1))
    assert float(jnp.abs(want - plain.reshape(t, -1)).max()) < 1e-5
    if path == "xla":
        got = afmoe.blocked_attention(q, k, v, window, q_block=8, kv_chunk=16)
    else:
        got = afmoe.kernel_attention(q, k, v, window, blocks=128)
    assert float(jnp.abs(got[0].reshape(t, -1) - want).max()) < 1e-5


# -- the registry ----------------------------------------------------------------


@pytest.mark.parametrize("bad", [
    {"model_type": "smallthinker2"},
    {"rope_layout": (1, 1)},  # a full layer with a rotary term
    {"rope_layout": (0, 0)},  # a sliding layer without one
    {"sliding_window_layout": (0, 1, 1)},  # names three layers of two
    {"num_dense_layers": 1},
    {"num_shared_experts": 1},
    {"num_nextn_predict_layers": 1},
])
def test_the_registry_refuses_what_the_type_cannot_be(bad):
    with pytest.raises(ValueError):
        build(**bad)


def test_the_published_period_puts_the_full_layer_first():
    a = toy_cfg(num_hidden_layers=4, sliding_window_layout=(0, 1, 1, 1),
                rope_layout=(0, 1, 1, 1)).lm_args
    assert afmoe_layer_types(a) == (FULL, SLIDING, SLIDING, SLIDING)
    # Trinity's period, untouched: full LAST
    other = TrainConfig(task_id=NNComputation.TASK_LM).with_overrides(
        {"lm_args": {"num_hidden_layers": 4}}).lm_args
    assert afmoe_layer_types(other) == (SLIDING, SLIDING, SLIDING, FULL)
    assert set(afmoe.MODEL_TYPES) == {"afmoe", "glm4_moe_lite", "smallthinker",
                                      "lfm2_moe", "qwen3_next"}


# -- the task through the trainer ------------------------------------------------


def test_one_trainer_round_matches_the_reference_round():
    """2 sites, dSGD, Adam, the device pipeline, bfloat16 compute: the
    parameters after one epoch of one round against the reference's round."""
    cfg, model, dims = build(num_sites=2, batch_size=1, learning_rate=1e-3,
                             compute_dtype="bfloat16")
    rng = np.random.default_rng(0)
    sites = [SiteArrays(rng.integers(0, VOCAB, (1, T + 1)).astype(np.int32),
                        np.zeros((1,), np.int32), np.arange(1, dtype=np.int32))
             for _ in range(2)]
    trainer = FederatedTrainer(cfg, model, None)
    state = trainer.init_state(jnp.ones((1, T + 1), jnp.int32), num_sites=2)
    before = jax.device_get(state.params)
    state, losses = trainer.run_epoch(state, sites, 1, batch_size=1)
    after = jax.device_get(state.params)
    with jax.default_matmul_precision("highest"):
        outs = [ref.grads(before, jnp.asarray(s.inputs[0]), dims) for s in sites]
        agg = fed.weighted_mean(
            jax.tree.map(lambda *g: jnp.stack(g), *[g for _, g in outs]),
            jnp.ones((2,)))
        want, _, _ = fed.adam_step(before, agg, lr=1e-3)
    assert len(losses) == 1
    assert abs(float(losses[0]) - float(np.mean([l for l, _ in outs]))) < 5e-3
    delta = lambda a: jax.tree.map(lambda x, y: np.asarray(x) - np.asarray(y),
                                   a, before)
    # Adam's first step is lr * sign(g): elements whose gradient is near zero
    # flip with bfloat16 rounding, so the cosine is the comparison
    assert fed.tree_cosine(delta(after), delta(want)) > 0.9


# -- the comparison notices a missing term ---------------------------------------


def _dropped(term: str, monkeypatch):
    """The system with one term taken away; the reference keeps them all."""
    if term == "reglu":  # SwiGLU experts under this type's name
        plain = afmoe.routed_experts
        monkeypatch.setattr(afmoe, "routed_experts",
                            lambda *a: plain(*a[:-1], False))
    elif term == "softmax_over_the_chosen":  # over all 64, then the chosen
        def over_all(logits, top_k):
            _, sel = jax.lax.top_k(logits, top_k)
            return sel, jnp.take_along_axis(jax.nn.softmax(logits, -1), sel, -1)
        monkeypatch.setattr(afmoe, "route_chosen", over_all)
    elif term == "no_positions_on_the_full_layer":  # rotary everywhere
        plain = afmoe.masked_attention
        pos = jnp.arange(T)
        monkeypatch.setattr(afmoe, "masked_attention", lambda q, k, v, w, *a: (
            plain(q, k, v, w, *a) if w is not None else plain(
                afmoe.rotary(q, pos, 1.5e6), afmoe.rotary(k, pos, 1.5e6),
                v, w, *a)))


@pytest.mark.parametrize("term", ["none", "reglu", "softmax_over_the_chosen",
                                  "no_positions_on_the_full_layer"])
def test_the_comparison_notices_a_dropped_term(monkeypatch, term):
    """``logit_rel_rms`` (benchmarks/lib/refcheck_lm.py) of the bfloat16
    system against the float32 reference stays inside the configuration's
    limit, and leaves it by far when a term goes missing."""
    with open(CONFIG) as fh:
        limits = json.load(fh)["check"]
    # every expert held, so that the routed part is a large share of a layer
    _, reference_model, dims = build(experts_held=EXPERTS)
    params, x = init_params(reference_model), tokens(8)
    if term != "none":
        # rows as small as the branches' outputs, so that a branch's term is
        # a large share of the stream (at unit variance the rows hide most of
        # it, which is also why the cell's limit is as tight as it is)
        params["embed"] = params["embed"] * 0.02
    want = ref_logits(params, x, dims)
    _dropped(term, monkeypatch)
    _, model, _ = build(compute_dtype="bfloat16", experts_held=EXPERTS)
    err = rel_rms(model.apply({"params": params}, x), want)
    if term == "none":
        assert err < limits["logit_rel_rms_max"], err
    else:
        assert err > 8 * limits["logit_rel_rms_max"], (term, err)
