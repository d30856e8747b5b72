"""The ``lfm2_moe`` decoder (models/afmoe.py: a gated short convolution where
attention sits on ``conv`` layers, QK-norm then rotary and no gate on the full
layers, Trinity's sigmoid routing without a shared expert, a tied head)
against its plain reference (benchmarks/reference/lfm2_moe.py), at toy widths
on the CPU.

Held: the short convolution against a position-by-position loop, and causal;
logits, loss and gradients group by group, first and last share of the
experts; the four shares of an expert layer add up to the uncut layer; the
expert layer under the site vmap; a full layer at head width 64 on both
attention paths; the tied head (no ``lm_head``, the embedding's gradient the
sum of its two uses); what the registry refuses; one ``FederatedTrainer``
round equals the reference round; the comparison notices each term that goes
missing; ``Attention``'s three facts leave the accepted types as they were.
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
from benchmarks.reference import lfm2_moe as ref
from dinunet_implementations_tpu.core.config import NNComputation, TrainConfig
from dinunet_implementations_tpu.data.api import SiteArrays
from dinunet_implementations_tpu.models import afmoe
from dinunet_implementations_tpu.models.afmoe import CONV, FULL, LFM2_MOE, SLIDING
from dinunet_implementations_tpu.runner.registry import (
    afmoe_layer_types,
    get_task,
)
from dinunet_implementations_tpu.trainer.loop import FederatedTrainer

T, VOCAB, EXPERTS, HELD, TOP_K, HIDDEN = 32, 96, 16, 4, 4, 64
KINDS = (CONV, FULL, CONV)  # a dense conv layer, then an expert layer of each
TOY = dict(
    model_type=LFM2_MOE, seq_len=T, vocab_size=VOCAB, vocab_rows=VOCAB,
    hidden_size=HIDDEN, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, intermediate_size=96, moe_intermediate_size=32,
    num_experts=EXPERTS, num_experts_per_tok=TOP_K, num_shared_experts=0,
    experts_held=HELD, first_expert=0, num_hidden_layers=3,
    num_dense_layers=1, layer_types=KINDS, conv_L_cache=3,
    tie_word_embeddings=True, rope_theta=1e6, rms_norm_eps=1e-5,
    route_norm=True, route_scale=1.0, q_block=8, kv_chunk=16, loss_block=8,
)
#: the check's groups this model has parameters in (no head: it is tied)
MY_GROUPS = tuple(g for g in GROUPS if g not in ("shared", "head"))
CONFIG = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "configs",
                      "lfm2-8b-a1b-ep4.json")


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
    the block moves the result, the norms' scales drawn too."""
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


# -- the short convolution --------------------------------------------------------


def _conv_layer(taps: int = 3):
    layer = afmoe.ShortConv(HIDDEN, taps)
    a = jax.random.normal(jax.random.PRNGKey(2), (2, T, HIDDEN))
    params = jax.tree.map(lambda w: w * 10.0, layer.init(
        jax.random.PRNGKey(3), a)["params"])
    return layer, params, a


@pytest.mark.parametrize("taps", [3, 4])
def test_short_conv_against_a_position_by_position_loop(taps):
    layer, p, a = _conv_layer(taps)
    assert {k: v.shape for k, v in p.items()} == {
        "w_in": (HIDDEN, 3 * HIDDEN), "filter": (HIDDEN, taps),
        "w_out": (HIDDEN, HIDDEN)}
    got = np.asarray(layer.apply({"params": p}, a))
    w_in, f, w_out = (np.asarray(p[k], np.float64)
                      for k in ("w_in", "filter", "w_out"))
    for row in range(a.shape[0]):
        bcx = np.asarray(a[row], np.float64) @ w_in
        b, c, x = bcx[:, :HIDDEN], bcx[:, HIDDEN: 2 * HIDDEN], bcx[:, 2 * HIDDEN:]
        u = b * x
        want = np.zeros((T, HIDDEN))
        for t in range(T):
            conv = np.zeros(HIDDEN)
            for j in range(taps):  # tap j reads the position taps - 1 - j back
                at = t - (taps - 1) + j
                if at >= 0:
                    conv += f[:, j] * u[at]
            want[t] = (c[t] * conv) @ w_out
        assert np.abs(got[row] - want).max() < 1e-4 * np.abs(want).max()
    # and the reference's sum over shifted copies is the same function
    dims = ref.Dims(conv_L_cache=taps)
    with jax.default_matmul_precision("highest"):
        same = ref.short_conv(p, a[0], dims)
    assert float(jnp.abs(same - got[0]).max()) < 1e-4 * np.abs(got[0]).max()


def test_short_conv_is_causal_and_starts_from_zeros():
    """Changing token ``t + 1`` moves no output at or before ``t``, position
    ``t`` reads ``t - 2 .. t`` and nothing older, and the first two positions
    see zeros where the sequence has no tokens."""
    layer, p, a = _conv_layer()
    run = jax.jit(lambda a: layer.apply({"params": p}, a))
    base = run(a)
    for t in (0, 1, 7, T - 5):
        moved = run(a.at[:, t + 1].add(1.0))
        assert float(jnp.abs(moved[:, : t + 1] - base[:, : t + 1]).max()) == 0.0
        assert float(jnp.abs(moved[:, t + 1] - base[:, t + 1]).max()) > 1e-3
        # three taps: the change reaches positions t + 1 .. t + 3 and no further
        assert float(jnp.abs(moved[:, t + 4:] - base[:, t + 4:]).max()) == 0.0
    # the first positions: the same tokens after two rows of zeros read alike
    padded = run(jnp.pad(a, ((0, 0), (2, 0), (0, 0))))
    assert float(jnp.abs(padded[:, 2:] - base).max()) < 1e-5
    # the mapped form is the unmapped one (the trainer's site vmap)
    folded = jax.vmap(lambda a: layer.apply({"params": p}, a[None])[0])(a)
    assert float(jnp.abs(folded - base).max()) < 1e-5


def test_short_conv_passes_the_blocks_checkpoint_and_the_site_vmap():
    layer, p, a = _conv_layer()

    def loss(p, a):
        run = jax.checkpoint(layer.apply, policy=afmoe.BLOCK_KEEPS)
        return jnp.sum(run({"params": p}, a) ** 2)

    sites = jnp.stack([a, a[::-1]])
    got = jax.jit(jax.vmap(jax.grad(loss), in_axes=(None, 0)))(p, sites)
    with jax.default_matmul_precision("highest"):
        for s in range(2):
            want = jax.grad(lambda p: sum(jnp.sum(ref.short_conv(
                p, row, ref.Dims()) ** 2) for row in sites[s]))(p)
            for k in want:
                assert float(jnp.abs(got[k][s] - want[k]).max()) < 1e-4 * float(
                    jnp.abs(want[k]).max()), k


# -- the model against the reference -------------------------------------------


def test_the_tree_holds_what_the_type_has_and_nothing_else():
    _, model, _ = build()
    params = init_params(model)
    assert sorted(params) == ["embed", "final_norm", "layer_0", "layer_1",
                              "layer_2"]  # no lm_head: the head is embed
    assert params["embed"].shape == (VOCAB, HIDDEN)
    for i, kind in enumerate(KINDS):
        layer = params[f"layer_{i}"]
        assert sorted(layer) == ["attn", "input_norm",
                                 "mlp" if i == 0 else "moe", "pre_mlp_norm"]
        # the token mixer sits under attention's name, whichever it is
        assert sorted(layer["attn"]) == (
            ["filter", "w_in", "w_out"] if kind == CONV
            else ["k_norm", "q_norm", "wk", "wo", "wq", "wv"])
    assert sorted(params["layer_0"]["mlp"]) == ["w1", "w2", "w3"]
    assert sorted(params["layer_1"]["moe"]) == ["expert_bias", "router", "w1",
                                                "w2", "w3"]
    paths = [tuple(k.key for k in path) for path, _ in
             jax.tree_util.tree_leaves_with_path(params)]
    by_group = {g: [p for p in paths if group_of(p) == g] for g in GROUPS}
    assert {g for g, found in by_group.items() if found} == set(MY_GROUPS)
    # the check's "attention" is the token mixer: the convolution's three too
    assert ("layer_0", "attn", "filter") in by_group["attention"]
    assert not model.mup_enabled and model.tie_word_embeddings


#: the attention subtree of each accepted type, as it was before
#: ``Attention`` told QK-norm, gate and rotary apart (PR 38)
ACCEPTED = {
    "afmoe": (dict(), ["k_norm", "q_norm", "wg", "wk", "wo", "wq", "wv"]),
    "smallthinker": (
        dict(num_dense_layers=0, num_shared_experts=0, rope_layout=(1, 0),
             sliding_window_layout=(1, 0)), ["wk", "wo", "wq", "wv"]),
    "glm4_moe_lite": (
        dict(q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
             qk_rope_head_dim=8, v_head_dim=16, layer_types=(FULL, FULL)),
        ["kv_a_norm", "q_a_norm", "wkv_a", "wkv_b", "wo", "wq_a", "wq_b"]),
}


@pytest.mark.parametrize("model_type", sorted(ACCEPTED))
def test_the_accepted_types_keep_their_trees(model_type):
    over, attn = ACCEPTED[model_type]
    cfg = TrainConfig(task_id=NNComputation.TASK_LM).with_overrides({"lm_args": {
        **{k: v for k, v in TOY.items()
           if k not in ("layer_types", "tie_word_embeddings", "conv_L_cache")},
        "model_type": model_type, "num_hidden_layers": 2, "sliding_window": 8,
        "num_shared_experts": 1, **over}})
    model = get_task(cfg.task_id).build_model(cfg)
    params = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, tokens(9), train=True))["params"]
    assert "lm_head" in params and not model.tie_word_embeddings
    for i in range(2):
        assert sorted(params[f"layer_{i}"]["attn"]) == attn


@pytest.mark.parametrize("window", [8, None])
def test_rotary_follows_the_window_unless_told(window):
    """``rope`` None is the accepted types' rule (positions iff a window), so
    saying the same thing aloud changes nothing; a full layer WITH positions
    is another function, and this type's."""
    a = jax.random.normal(jax.random.PRNGKey(4), (1, T, HIDDEN))

    def layer(**kw):
        return afmoe.Attention(4, 2, 16, window, 1e4, 1e-5, 8, 16, **kw)

    params = layer().init(jax.random.PRNGKey(5), a)
    base = layer().apply(params, a)
    same = layer(rope=window is not None).apply(params, a)
    other = layer(rope=window is None).apply(params, a)
    assert float(jnp.abs(same - base).max()) == 0.0
    assert float(jnp.abs(other - base).max()) > 1e-4


@pytest.mark.parametrize("first", [0, EXPERTS - HELD])
def test_logits_loss_and_gradients_match_the_reference(first):
    _, model, dims = build(first_expert=first)
    params, x = init_params(model), tokens(1)
    got, inter = model.apply({"params": params}, x, mutable=["intermediates"])
    assert float(jnp.abs(got - ref_logits(params, x, dims)).max()) < 5e-5
    # the routing counter, as MoE sows it: the dense layer sows none
    assert sorted(inter["intermediates"]) == ["layer_1", "layer_2"]
    for v in inter["intermediates"].values():
        assert v["moe"]["held_counts"][0].shape == (2, HELD)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(task_loss(model, x[:1]))(params)
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, x[0], dims)))(params)
        chain_loss, chain = ref.grads(params, x[0], dims)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert abs(float(chain_loss) - float(want_loss)) < 1e-5
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    assert jax.tree.structure(chain) == jax.tree.structure(want)
    for (path, g), c, w in zip(jax.tree_util.tree_leaves_with_path(grads),
                               jax.tree.leaves(chain), jax.tree.leaves(want)):
        scale = max(float(jnp.abs(w).max()), 1e-3)
        assert float(jnp.abs(g - w).max()) < 2e-4 * scale, jax.tree_util.keystr(path)
        assert float(jnp.abs(c - w).max()) < 2e-4 * scale, jax.tree_util.keystr(path)
    cosines = group_cosines(grads, want)
    assert set(cosines) == set(MY_GROUPS) | {"all"}
    assert min(cosines.values()) > 1 - 1e-6, cosines
    assert float(jnp.abs(want["layer_2"]["attn"]["filter"]).max()) > 1e-4


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


@pytest.mark.parametrize("layer", [1, 2])  # the attention layer, a conv one
def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer(layer):
    """One expert layer, its 16 experts held 4 a share by 4 shares
    (``first_expert`` 0, 4, 8, 12): what the shares compute alike (the
    residual stream and the token mixer) counted once plus every share's
    routed part equals the uncut reference layer; a share weights by the
    chosen scores over the sum of ALL four chosen, not of the ones it holds."""
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


# -- a full layer at head width 64 ------------------------------------------------


@pytest.mark.parametrize("path,t", [("xla", T), ("kernel", 256)])
def test_a_full_layer_at_head_width_64_against_the_reference(monkeypatch, path, t):
    """QK-norm, then rotary, no gate, causal, four query heads on two
    key-value heads of width 64 (half a lane tile): the layer as the block
    builds it against the reference's ``pre`` / ``core`` / ``W_o``, on the XLA
    blocks and on the splash kernels (interpret mode; the rotary hand-over
    kernel refuses 64 lanes, so XLA's ``rotary`` feeds them)."""
    if path == "kernel":
        monkeypatch.setattr(afmoe, "_auto_pallas", lambda: True)
        afmoe._splash.cache_clear()
    n, g, d = 4, 2, 64
    layer = afmoe.Attention(n, g, d, None, 1e6, 1e-5, 8, 16, qk_norm=True,
                            gate=False, rope=True)
    # the layer is handed the normed tokens, the reference norms them itself
    raw = jax.random.normal(jax.random.PRNGKey(6), (t, HIDDEN))
    a = ref.rms(raw, jnp.ones((HIDDEN,)), 1e-5)[None]
    params = layer.init(jax.random.PRNGKey(7), a)["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(8), 8))
    params = jax.tree.map(
        lambda w: w * 5.0 if w.ndim >= 2
        else w * (1.0 + 0.5 * jax.random.normal(next(keys), w.shape)), params)
    assert sorted(params) == ["k_norm", "q_norm", "wk", "wo", "wq", "wv"]
    assert params["q_norm"]["scale"].shape == (d,)
    assert afmoe.takes_kernels(t) == (path == "kernel")
    try:
        got = jax.jit(layer.apply)({"params": params}, a)[0]
    finally:
        afmoe._splash.cache_clear()
    dims = ref.Dims(num_attention_heads=n, num_key_value_heads=g, head_dim=d,
                    layer_types=(FULL,), q_block=8)
    p = {"attn": params, "input_norm": {"scale": jnp.ones((HIDDEN,))}}
    with jax.default_matmul_precision("highest"):
        q, k, v = ref.pre(p, raw, dims)
        o = jnp.concatenate([ref.core(q[s:e], k, v, s, dims)
                             for s, e in ref._query_blocks(t, dims)])
        want = o @ params["wo"]
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())


# -- the tied head ---------------------------------------------------------------


def test_the_tied_heads_gradient_is_the_sum_of_the_matrixs_two_uses():
    """No ``lm_head`` in the tree; the embedding's gradient against
    ``jax.grad`` of the plain form, and against the two uses told apart: the
    same model untied, its head the embedding's transpose."""
    _, tied, dims = build()
    _, untied, _ = build(tie_word_embeddings=False)
    params, x = init_params(tied), tokens(2)
    assert "lm_head" not in params
    apart = {**params, "lm_head": params["embed"].T}
    assert jax.tree.structure(apart) == jax.tree.structure(jax.eval_shape(
        lambda: untied.init({"params": jax.random.PRNGKey(0)}, x, train=True)
    )["params"])
    with jax.default_matmul_precision("highest"):
        got = jax.grad(task_loss(tied, x[:1]))(params)
        two = jax.grad(task_loss(untied, x[:1]))(apart)
        want = jax.jit(jax.grad(lambda p: ref.loss(p, x[0], dims)))(params)
    summed = two["embed"] + two["lm_head"].T
    scale = float(jnp.abs(want["embed"]).max())
    assert float(jnp.abs(two["lm_head"]).max()) > 0.01 * scale  # both uses count
    assert float(jnp.abs(two["embed"]).max()) > 0.01 * scale
    assert float(jnp.abs(got["embed"] - summed).max()) < 1e-5 * scale
    assert float(jnp.abs(got["embed"] - want["embed"]).max()) < 2e-4 * scale
    # the logits of the tied model are the untied one's
    assert float(jnp.abs(tied.apply({"params": params}, x)
                         - untied.apply({"params": apart}, x)).max()) < 1e-5


# -- the registry ----------------------------------------------------------------


@pytest.mark.parametrize("bad", [
    {"model_type": "lfm2"},
    {"model_type": "afmoe"},  # a conv layer in another type
    {"model_type": "smallthinker", "num_dense_layers": 0,
     "rope_layout": (0, 1, 0), "sliding_window_layout": (0, 1, 0)},
    {"layer_types": (CONV, FULL)},  # names two layers of three
    {"layer_types": ()},  # the type has no rule to derive them from
    {"layer_types": (CONV, SLIDING, CONV)},  # no window in this type
    {"num_shared_experts": 1},
    {"head_dim": 32},  # 4 heads of 32 are not the hidden 64
    {"num_nextn_predict_layers": 1},
    {"first_expert": EXPERTS - HELD + 1},
])
def test_the_registry_refuses_what_the_type_cannot_be(bad):
    with pytest.raises(ValueError):
        build(**bad)


def test_the_layers_are_the_lists_and_the_dense_ones_lead_the_kept_list():
    cfg, model, _ = build()
    assert afmoe_layer_types(cfg.lm_args) == KINDS
    assert model.dims.layer_types == KINDS and model.dims.num_dense_layers == 1
    assert model.dims.conv_L_cache == 3 and model.dims.route_scale == 1.0
    assert LFM2_MOE in afmoe.MODEL_TYPES
    # untied, the same file builds a head of its own
    assert not build(tie_word_embeddings=False)[1].tie_word_embeddings


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
    assert "lm_head" not in before
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
    if term == "the_filters_middle_tap":
        plain = afmoe.short_conv
        monkeypatch.setattr(afmoe, "short_conv", lambda bcx, filt: plain(
            bcx, filt.at[:, 1].set(0.0)))
    elif term == "the_c_gate":  # o = c W_out for (C * c) W_out
        plain = afmoe.short_conv
        monkeypatch.setattr(afmoe, "short_conv", lambda bcx, filt: plain(
            bcx.at[..., HIDDEN: 2 * HIDDEN].set(1.0), filt))
    elif term == "the_qk_norm":  # the norm over a head's dimensions alone
        plain = afmoe.rms_norm
        monkeypatch.setattr(afmoe, "rms_norm", lambda x, scale, eps: (
            x if x.ndim == 4 else plain(x, scale, eps)))
    elif term == "positions_on_the_full_layer":
        monkeypatch.setattr(afmoe, "rotary", lambda x, pos, theta: x)


@pytest.mark.parametrize("term", ["none", "the_filters_middle_tap", "the_c_gate",
                                  "the_qk_norm", "positions_on_the_full_layer"])
def test_the_comparison_notices_a_dropped_term(monkeypatch, term):
    """``logit_rel_rms`` (benchmarks/lib/refcheck_lm.py) of the bfloat16
    system against the float32 reference stays inside the configuration's
    limit, and leaves it by far when a term goes missing."""
    with open(CONFIG) as fh:
        limits = json.load(fh)["check"]
    _, reference_model, dims = build(experts_held=EXPERTS)
    params, x = init_params(reference_model), tokens(8)
    # rows as small as the branches' outputs, so that a branch's term is a
    # large share of the stream
    params["embed"] = params["embed"] * 0.02
    want = ref_logits(params, x, dims)
    _dropped(term, monkeypatch)
    _, model, _ = build(compute_dtype="bfloat16", experts_held=EXPERTS)
    err = rel_rms(model.apply({"params": params}, x), want)
    if term == "none":
        assert err < limits["logit_rel_rms_max"], err
    else:
        assert err > 4 * limits["logit_rel_rms_max"], (term, err)
