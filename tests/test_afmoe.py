"""The AFMoE decoder (models/afmoe.py) as a federated next-token task, against
the plain reference (benchmarks/reference/afmoe.py), at toy widths on the CPU.

Held: logits, loss and gradients with every share of the experts; the shares'
routed parts plus the shared expert once add up to the uncut layer; no token
is dropped at any imbalance; a token outside the window reaches a full layer
and not a sliding one; one ``FederatedTrainer`` round equals the reference
round; token ids survive the resident inventory; the program wears its
scopes; the comparison notices each term of the block that goes missing; and
a block's checkpoint keeps the forward kernel's result (one forward kernel a
layer in the gradient's program) without moving a gradient.
"""

import dataclasses
import itertools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import afmoe as ref
from benchmarks.reference import federated as fed
from dinunet_implementations_tpu.checks.semantic import _sub_jaxprs
from dinunet_implementations_tpu.core.config import NNComputation, TrainConfig
from dinunet_implementations_tpu.data.api import SiteArrays, stack_site_inventory
from dinunet_implementations_tpu.models import afmoe
from dinunet_implementations_tpu.models.afmoe import FULL, SLIDING
from dinunet_implementations_tpu.parallel.distributed import put_site_inventory
from dinunet_implementations_tpu.runner.registry import (
    afmoe_layer_types,
    get_task,
)
from dinunet_implementations_tpu.telemetry import scopes
from dinunet_implementations_tpu.trainer.loop import FederatedTrainer
from dinunet_implementations_tpu.trainer.steps import _gather_batch

T, VOCAB, EXPERTS, HELD, TOP_K, WINDOW = 32, 96, 16, 4, 4, 8
LAYERS = (SLIDING, SLIDING, SLIDING, SLIDING, FULL)
TOY = dict(
    seq_len=T, vocab_size=VOCAB, vocab_rows=VOCAB, hidden_size=64,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, num_experts=EXPERTS,
    num_experts_per_tok=TOP_K, experts_held=HELD, first_expert=0,
    num_hidden_layers=len(LAYERS), num_dense_layers=1, layer_types=LAYERS,
    sliding_window=WINDOW, q_block=8, kv_chunk=16, loss_block=8,
)


def toy_cfg(**over) -> TrainConfig:
    train = {k: over.pop(k) for k in list(over)
             if k in ("num_sites", "batch_size", "learning_rate", "epochs",
                      "monitor_metric", "metric_direction", "patience",
                      "validation_epochs")}
    return TrainConfig(task_id=NNComputation.TASK_LM, **train).with_overrides(
        {"lm_args": {**TOY, **over}})


def build(**over):
    cfg = toy_cfg(**over)
    model = get_task(cfg.task_id).build_model(cfg)
    dims = ref.Dims.of(dataclasses.asdict(cfg.lm_args),
                       layer_types=afmoe_layer_types(cfg.lm_args),
                       q_block=8, head_block=8)
    return cfg, model, dims


def tokens(seed: int, rows: int = 2):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, T + 1), 0, VOCAB)


def init_params(model, scale: float = 5.0):
    """Seeded random weights, the matrices scaled up so that every term of
    the block moves the result (at std 0.02 the norms hide most of them)."""
    params = model.init({"params": jax.random.PRNGKey(0)}, tokens(9),
                        train=True)["params"]
    return jax.tree.map(lambda a: a * scale if a.ndim >= 2 else a, params)


def rel_rms(got, want) -> float:
    return float(jnp.sqrt(jnp.mean((got - want) ** 2) / jnp.mean(want ** 2)))


def ref_logits(params, x, dims):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([ref.forward(params, row[:-1], dims) for row in x])


# -- the model against the reference, every share -----------------------------


@pytest.mark.parametrize("first", range(0, EXPERTS, HELD))
def test_logits_loss_and_gradients_match_the_reference(first):
    _, model, dims = build(first_expert=first)
    params, x = init_params(model), tokens(1)
    assert float(jnp.abs(
        model.apply({"params": params}, x) - ref_logits(params, x, dims)
    ).max()) < 5e-5
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: model.task_loss(
            {"params": p}, x[:1], jnp.ones(1)))(params)
        want_loss, want = jax.value_and_grad(
            lambda p: ref.loss(p, x[0], dims))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want)):
        scale = max(float(jnp.abs(w).max()), 1e-3)
        assert float(jnp.abs(g - w).max()) < 2e-4 * scale, jax.tree_util.keystr(path)


def test_row_weights_pick_the_rows_the_loss_reads():
    _, model, dims = build()
    params, x = init_params(model), tokens(2, rows=3)
    got = model.task_loss({"params": params}, x, jnp.asarray([1.0, 0.0, 1.0]))
    with jax.default_matmul_precision("highest"):
        want = (ref.loss(params, x[0], dims) + ref.loss(params, x[2], dims)) / 2
    assert abs(float(got) - float(want)) < 1e-5


def test_vmap_over_sites_folds_into_the_groups():
    """The trainer's fold: vmap(value_and_grad) over sites equals site by
    site, through the expert layer's own vmap rule (no batched ragged dot)."""
    _, model, _ = build()
    params = init_params(model)
    xs = jnp.stack([tokens(s) for s in (3, 4, 5)])
    vg = jax.value_and_grad(
        lambda p, x: model.task_loss({"params": p}, x, jnp.ones(2)))
    losses, grads = jax.jit(jax.vmap(vg, in_axes=(None, 0)))(params, xs)
    for s in range(3):
        loss, g = vg(params, xs[s])
        assert abs(float(losses[s]) - float(loss)) < 1e-5
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(g)):
            assert float(jnp.abs(a[s] - b).max()) <= 1e-4 * max(
                float(jnp.abs(b).max()), 1e-3)


@pytest.mark.parametrize("chunk", [16, 64])
def test_walking_the_assignments_in_chunks_changes_nothing(monkeypatch, chunk):
    _, model, _ = build()
    params, x = init_params(model), tokens(6)
    vg = jax.value_and_grad(
        lambda p: model.task_loss({"params": p}, x, jnp.ones(2)))
    whole = vg(params)
    monkeypatch.setattr(afmoe, "ROW_CHUNK", chunk)
    afmoe._expert_layer.cache_clear()
    try:
        parts = vg(params)
    finally:
        afmoe._expert_layer.cache_clear()
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(parts)):
        assert float(jnp.abs(a - b).max()) <= 1e-5 * max(
            float(jnp.abs(a).max()), 1e-3)


# -- the expert layer ----------------------------------------------------------


def _moe_layer(seed: int = 0):
    """An uncut MoE layer's reference parameters and a token block."""
    h, f = 64, 32
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    p = {"router": jax.random.normal(k[0], (h, EXPERTS)),
         "expert_bias": 0.1 * jax.random.normal(k[1], (EXPERTS,)),
         "w1": 0.2 * jax.random.normal(k[2], (EXPERTS, h, f)),
         "w3": 0.2 * jax.random.normal(k[3], (EXPERTS, h, f)),
         "w2": 0.2 * jax.random.normal(k[4], (EXPERTS, f, h)),
         "shared": {"w1": 0.2 * jax.random.normal(k[5], (h, f)),
                    "w3": 0.2 * jax.random.normal(k[6], (h, f)),
                    "w2": 0.2 * jax.random.normal(k[7], (f, h))}}
    m = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, T, h))
    return p, m


def _share(p, first: int, shared: bool):
    """The system's MoE module holding experts ``first .. first + HELD - 1``
    of the layer ``p``, and its parameters."""
    module = afmoe.MoE(64, EXPERTS, TOP_K, HELD, first, 32,
                       32 if shared else 0, True, 2.826)
    mine = {k: p[k] for k in ("router", "expert_bias")}
    mine.update({k: p[k][first: first + HELD] for k in ("w1", "w3", "w2")})
    if shared:
        mine["shared"] = p["shared"]
    return module, mine


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of all 16/4 shares, plus the shared expert counted
    once, equal the uncut reference's layer."""
    p, m = _moe_layer()
    dims = ref.Dims(num_experts_per_tok=TOP_K, first_expert=0)
    with jax.default_matmul_precision("highest"):
        whole = jnp.stack([ref.moe(p, m[b], dims) for b in range(2)])
        total = 0.0
        for i, first in enumerate(range(0, EXPERTS, HELD)):
            module, mine = _share(p, first, shared=(i == 0))
            total = total + module.apply({"params": mine}, m)
    assert float(jnp.abs(total - whole).max()) < 1e-4 * float(jnp.abs(whole).max())


@pytest.mark.parametrize("first", [0, HELD])
def test_no_token_is_dropped_when_all_pick_the_same_held_expert(first):
    """Every token's assignments fall on the same four experts, all held by
    one share (the worst case of its buffer) and none by the next."""
    p, m = _moe_layer(seed=3)
    bias = jnp.full((EXPERTS,), -10.0).at[:HELD].set(10.0)
    p = {**p, "expert_bias": bias}
    dims = ref.Dims(num_experts_per_tok=TOP_K, first_expert=first)
    module, mine = _share(p, first, shared=False)
    held = {k: (v[first: first + HELD] if k in ("w1", "w3", "w2") else v)
            for k, v in p.items() if k != "shared"}
    with jax.default_matmul_precision("highest"):
        got, inter = module.apply({"params": mine}, m, mutable=["intermediates"])
        want = jnp.stack([ref.moe(held, m[b], dims) for b in range(2)])
    counts = np.asarray(inter["intermediates"]["held_counts"][0])
    assert counts.sum() == (2 * T * TOP_K if first == 0 else 0)
    assert float(jnp.abs(got - want).max()) < 1e-4 * max(
        float(jnp.abs(want).max()), 1.0)
    if first == 0:
        assert float(jnp.abs(want).max()) > 0.1  # the share did the work


# -- the backward pass's chunk: the stacks' window, the weights' cotangent -------

FOLDS, K2, WIN_CHUNK = 2, 2, 16  # 2 folds x 4 held = 8 groups, rungs of 4, 6


def _by_group(sizes):
    """``[fold][expert]`` counts of expert-major group sizes."""
    return [list(sizes[f::FOLDS]) for f in range(FOLDS)]


# assignments on each held expert, [fold][expert], of T * K2 = 64 a fold; the
# rest go to experts held elsewhere. Then what window_chunks counts:
# ((chunks that take each rung, narrowest first), chunks that take the whole
# stacks, live chunks)
ROUTINGS = {
    # 16 rows a group: a chunk of 16 lies in one or two groups
    "narrow": ([[16, 16, 16, 16], [16, 16, 16, 16]], ((8, 0), 0, 8)),
    # 2 rows a group: the one live chunk spans all 8
    "wide": ([[2, 2, 2, 2], [2, 2, 2, 2]], ((0, 0), 1, 1)),
    # expert 0 fills two chunks and a half; the third spans five groups
    "mix": ([[21, 2, 2, 2], [20, 2, 3, 2]], ((3, 1), 0, 4)),
    # everything on the last expert: the window's start is clamped
    "last": ([[0, 0, 0, 24], [0, 0, 0, 24]], ((3, 0), 0, 3)),
    # the last chunk starts in the last group but one, past the last window
    "clamped": ([[16, 16, 16, 9], [16, 16, 16, 7]], ((7, 0), 0, 7)),
    # 8 held experts, 16 groups, rungs of 4, 6, ..., 14; between them every
    # rung and the whole stacks, three paths or more in a walk. A chunk in
    # group 0, one over 0-5 (a rung of 6 exactly), one over 5-15 (11: the
    # rung of 12), one in group 15 (a window clamped to the last groups)
    "ladder-6-12": (_by_group([17, 3, 3, 3, 3, 4] + [1] * 9 + [20]),
                    ((2, 1, 0, 0, 1, 0), 0, 4)),
    # over groups 0-7 (8) and 7-15 (9: the rung of 10)
    "ladder-8-10": (_by_group([2] * 7 + [3] + [2] * 7 + [1]),
                    ((0, 0, 1, 1, 0, 0), 0, 2)),
    # over groups 0-13 (14), then 13-15 (clamped)
    "ladder-14": (_by_group([1] * 13 + [4, 5, 10]), ((1, 0, 0, 0, 0, 1), 0, 2)),
    # over all 16 groups (the whole stacks), then two in group 15
    "ladder-whole": (_by_group([1] * 15 + [20]), ((2, 0, 0, 0, 0, 0), 1, 3)),
}


def _routed(counts, seed: int = 0):
    """``(m, sel, w, w1, w3, w2, dy)`` of two folds whose tokens' slots go to
    the held experts ``counts`` times, in a seeded order."""
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    held = len(counts[0])
    sel = []
    for s, row in enumerate(counts):
        slots = np.concatenate(
            [np.full(n, e) for e, n in enumerate(row)]
            + [held + np.arange(T * K2 - sum(row)) % (EXPERTS - held)])
        sel.append(np.asarray(jax.random.permutation(k[s], slots)).reshape(T, K2))
    h, f = 64, 32
    return (jax.random.normal(k[2], (FOLDS, T, h)),
            jnp.asarray(np.stack(sel), jnp.int32),
            jax.random.uniform(k[3], (FOLDS, T, K2), minval=0.2),
            0.2 * jax.random.normal(k[4], (held, h, f)),
            0.2 * jax.random.normal(k[5], (held, h, f)),
            0.2 * jax.random.normal(k[6], (held, f, h)),
            jax.random.normal(k[7], (FOLDS, T, h)))


def _plain_experts(m, sel, w, w1, w3, w2, relu):
    """One fold's held part, every assignment's expert gathered whole."""
    held = sel < w1.shape[0]
    e = jnp.where(held, sel, 0)
    a = jnp.einsum("th,tkhf->tkf", m, w1[e])
    b = jnp.einsum("th,tkhf->tkf", m, w3[e])
    mid = (jax.nn.relu(a) if relu else jax.nn.silu(a)) * b
    return jnp.einsum("tkf,tkfh,tk->th", mid, w2[e], jnp.where(held, w, 0.0))


def _counted(taken, whole, live):
    return tuple(int(v) for v in taken.values()), int(whole), int(live)


@pytest.mark.parametrize("relu", [False, True], ids=["swiglu", "reglu"])
@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_the_stacks_window_changes_no_cotangent(monkeypatch, routing, relu):
    """Chunks that add into a window of the stacks (each rung), chunks that
    add into the whole stacks, and several of them in one walk: the five
    cotangents are the whole path's, and the plain layer's."""
    counts, expect = ROUTINGS[routing]
    m, sel, w, w1, w3, w2, dy = _routed(counts)
    held = len(counts[0])
    monkeypatch.setattr(afmoe, "ROW_CHUNK", WIN_CHUNK)
    taken = afmoe.window_chunks(np.asarray(counts), FOLDS * T * K2)
    assert tuple(taken[0]) == tuple(range(4, 2 * held, 2))
    assert _counted(*taken) == expect
    with jax.default_matmul_precision("highest"):
        got = afmoe._experts_backward(m, sel, w, w1, w3, w2, dy, 0, None, relu)
        monkeypatch.setattr(afmoe, "WINDOW_EXPERTS", held)  # no window built
        whole = afmoe._experts_backward(m, sel, w, w1, w3, w2, dy, 0, None, relu)
        want = jax.vmap(lambda m, sel, w, dy: jax.vjp(
            lambda m, w, w1, w3, w2: _plain_experts(m, sel, w, w1, w3, w2, relu),
            m, w, w1, w3, w2)[1](dy))(m, sel, w, dy)
    for a, b in zip(got, whole):
        assert float(jnp.abs(a - b).max()) <= 1e-6 * float(jnp.abs(b).max())
    for name, a, b in zip(("dm", "dw", "dw1", "dw3", "dw2"), got, want):
        assert float(jnp.abs(a - b).max()) < 2e-4 * max(
            float(jnp.abs(b).max()), 1e-3), name
    assert float(jnp.abs(got[1]).max()) > 0.1  # the weights' cotangent is there


def test_a_window_past_the_last_groups_is_clamped():
    """The chunk's first nonempty group is the last: the window starts
    ``window`` groups before the end and only empty groups come before."""
    g0, inside, narrow = afmoe.stack_window(jnp.asarray([0, 0, 0, 0, 0, 0, 0, 5]), 4)
    assert (int(g0), inside.tolist(), bool(narrow)) == (4, [0, 0, 0, 5], True)
    g0, inside, narrow = afmoe.stack_window(jnp.asarray([0, 3, 0, 0, 2, 1, 0, 0]), 4)
    assert (int(g0), inside.tolist(), bool(narrow)) == (1, [3, 0, 0, 2], False)
    g0, inside, narrow = afmoe.stack_window(jnp.asarray([0, 0, 3, 0, 0, 2, 0, 0]), 4)
    assert (int(g0), inside.tolist(), bool(narrow)) == (2, [3, 0, 0, 2], True)


@pytest.mark.parametrize("sizes,rung,g0", [
    ([2, 0, 0, 3, 0, 0, 0, 0], 0, 0),  # a span of exactly the first rung
    ([2, 0, 0, 0, 3, 0, 0, 0], 1, 0),  # one past it: the second
    ([0, 1, 0, 0, 0, 0, 1, 0], 1, 1),  # exactly the second
    ([0, 1, 0, 0, 0, 0, 0, 1], 2, None),  # one past the last: the whole stacks
    ([0, 0, 0, 0, 0, 1, 0, 4], 0, 4),  # clamped at the last groups, each rung
    ([0, 0, 0, 1, 0, 0, 0, 4], 1, 2),
], ids=["at-first", "past-first", "at-second", "past-last", "clamped-first",
        "clamped-second"])
def test_a_chunk_takes_the_narrowest_rung_that_holds_it(sizes, rung, g0):
    """Rungs of 4 and 6 of 8 groups: the rung's index (``len(rungs)``: the
    whole stacks), and where the window it takes starts."""
    sizes, rungs = jnp.asarray(sizes), (4, 6)
    assert int(afmoe.rung_of(sizes, rungs)) == rung
    if g0 is not None:
        start, inside, narrow = afmoe.stack_window(sizes, rungs[rung])
        assert (int(start), int(inside.sum()), bool(narrow)) == (
            g0, int(sizes.sum()), True)


@pytest.mark.parametrize("folds,held,rungs", [
    (2, 8, (4, 6, 8, 10, 12, 14)),  # the four language-model cells
    (2, 3, (4,)),
    (1, 4, (2, 3)),
    (2, 2, ()),  # no window narrower than the stacks
])
def test_window_rungs_follow_the_stacks_shape(folds, held, rungs):
    """A rung for each whole number of experts from ``WINDOW_EXPERTS`` to all
    held but one, in groups of ``folds``."""
    assert afmoe.window_rungs(folds, held) == rungs


@pytest.mark.parametrize("seed", range(4))
def test_window_chunks_counts_what_a_walk_over_the_rows_finds(monkeypatch, seed):
    """The counter against a brute-force walk: a chunk takes the narrowest
    rung whose window holds its rows' groups, from the first (or from the
    last window's start), and the whole stacks where none does."""
    monkeypatch.setattr(afmoe, "ROW_CHUNK", 64)
    rng = np.random.default_rng(seed)
    folds, held, rows = 2, 8, 2048
    counts = rng.integers(0, (8, 40, 150, 300)[seed], (folds, held))
    counts[:, rng.integers(held)] = 0  # an empty expert
    group_of = np.repeat(np.arange(folds * held), counts.T.reshape(-1))

    def walk(rungs):
        taken, whole, live = dict.fromkeys(rungs, 0), 0, 0
        for lo in range(0, rows, 64):
            inside = group_of[lo: lo + 64]
            if not len(inside):
                continue
            live += 1
            for w in rungs:
                if inside[-1] < min(inside[0], folds * held - w) + w:
                    taken[w] += 1
                    break
            else:
                whole += 1
        return tuple(taken.values()), whole, live

    for experts, rungs in ((2, (4, 6, 8, 10, 12, 14)), (4, (8, 10, 12, 14)),
                           (held, ())):  # the last: no window is built
        monkeypatch.setattr(afmoe, "WINDOW_EXPERTS", experts)
        got = afmoe.window_chunks(counts, rows)
        assert tuple(got[0]) == rungs
        assert _counted(*got) == walk(rungs), experts


@pytest.mark.parametrize("relu", [False, True], ids=["swiglu", "reglu"])
def test_the_weights_cotangent_needs_no_product_by_w2(relu):
    """``sum_h (mid w2)_h g_h = sum_f mid_f (g w2^T)_f`` row by row under the
    grouped products, in float32: what the chunk reads for ``dwk``."""
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    sizes = jnp.asarray([5, 0, 9, 2], jnp.int32)
    a, b = jax.random.normal(k[0], (2, 16, 32))
    mid = (jax.nn.relu(a) if relu else jax.nn.silu(a)) * b
    w2, g = jax.random.normal(k[1], (4, 32, 64)), jax.random.normal(k[2], (16, 64))
    with jax.default_matmul_precision("highest"):
        ys = jax.lax.ragged_dot(mid, w2, sizes)
        u = jax.lax.ragged_dot(g, jnp.swapaxes(w2, 1, 2), sizes)
    old, new = (ys * g).sum(-1), (mid * u).sum(-1)
    assert float(jnp.abs(old - new).max()) < 1e-5 * float(jnp.abs(old).max())
    assert float(jnp.abs(old[:16]).max()) > 1.0


# -- attention ----------------------------------------------------------------


@pytest.mark.parametrize("kind,reaches", [(SLIDING, False), (FULL, True)])
def test_a_token_outside_the_window_reaches_full_layers_only(kind, reaches):
    _, model, _ = build(num_hidden_layers=1, num_dense_layers=1,
                        layer_types=(kind,))
    params, x = init_params(model), tokens(7, rows=1)
    other = x.at[0, 0].set((x[0, 0] + 1) % VOCAB)  # position 0 changes
    a = model.apply({"params": params}, x)[0]
    b = model.apply({"params": params}, other)[0]
    inside = float(jnp.abs(a[:WINDOW] - b[:WINDOW]).max())
    outside = float(jnp.abs(a[WINDOW:] - b[WINDOW:]).max())
    assert inside > 1e-3
    assert (outside > 1e-3) == reaches
    if not reaches:
        assert outside == 0.0


def test_blocked_attention_is_plain_attention():
    q = jax.random.normal(jax.random.PRNGKey(0), (2, T, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, T, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, T, 2, 16))
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    for window in (None, WINDOW):
        mask = (j <= i) if window is None else (j <= i) & (j > i - window)
        s = jnp.einsum("btnd,bsnd->bnts", q, jnp.repeat(k, 2, axis=2)) / 4.0
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        want = jnp.einsum("bnts,bsnd->btnd", p, jnp.repeat(v, 2, axis=2))
        got = afmoe.blocked_attention(q, k, v, window, q_block=8, kv_chunk=16)
        assert float(jnp.abs(got - want).max()) < 1e-5


def unequal_blocks():
    """A ``BlockSizes`` whose query, key and compute blocks all differ, each
    kernel with its own."""
    return afmoe.block_sizes(fwd=(256, 512, 128), dq=(512, 256, 256),
                             dkv=(128, 512, 256))


@pytest.mark.parametrize("window,t,blocks", [
    (None, 256, 128), (100, 256, 128),
    (None, 512, "unequal"), (300, 512, "unequal"),
])
def test_kernel_attention_is_blocked_attention(window, t, blocks):
    """The splash-attention kernels (interpret mode here) against the XLA
    blocks, forward and backward; the causal-window mask keeps ``j > i -
    window``. Equal blocks, and a geometry in which query, key and compute
    block of every kernel differ."""
    if blocks == "unequal":
        blocks = unequal_blocks()
    q = jax.random.normal(jax.random.PRNGKey(0), (1, t, 4, 128))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, t, 2, 128))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, t, 2, 128))
    plain = lambda q, k, v: afmoe.blocked_attention(q, k, v, window, 64, 128)
    kernel = lambda q, k, v: afmoe.kernel_attention(q, k, v, window, blocks=blocks)
    assert float(jnp.abs(plain(q, k, v) - kernel(q, k, v)).max()) < 1e-5
    grad = lambda f: jax.grad(lambda *a: (f(*a) ** 2).sum(), argnums=(0, 1, 2))
    for a, b in zip(grad(plain)(q, k, v), grad(kernel)(q, k, v)):
        assert float(jnp.abs(a - b).max()) < 1e-4


@pytest.mark.parametrize("t", [128, 384, 512, 1536, 2048, 2560, 4096, 8192, 16384])
@pytest.mark.parametrize("window", [None, 100, 2048, 4096])
def test_attention_blocks_are_blocks_the_kernels_can_run(t, window):
    """Over head widths, heads a key-value head and operand dtypes: every
    block divides the sequence and none exceeds it, a compute block divides
    its key block in whole lanes, and each kernel's VMEM estimate stays
    within the budget (up to float32 at width 256 and bfloat16 at 512: past
    that the granule itself, the floor, is estimated over it)."""
    for heads_per_kv, (d, dtype) in itertools.product((1, 8), (
            (64, jnp.float32), (128, jnp.bfloat16), (128, jnp.float32),
            (256, jnp.bfloat16), (256, jnp.float32), (512, jnp.bfloat16))):
        sizes = afmoe.attention_blocks(t, heads_per_kv, d, window, dtype)
        assert not sizes.use_fused_bwd_kernel
        for kernel, (bq, bkv, c) in afmoe.kernel_blocks(sizes).items():
            where = (t, heads_per_kv, d, window, dtype, kernel, bq, bkv, c)
            assert t % bq == 0 and t % bkv == 0, where
            assert bq <= t and bkv <= t, where
            assert bkv % c == 0 and c % 128 == 0, where
            assert afmoe.attention_vmem_bytes(
                kernel, bq, bkv, c, d, dtype) <= afmoe.VMEM_BUDGET, where


@pytest.mark.parametrize("shape,edge", [
    # the shapes the language-model cells run (step 0, PERF.md §6, PR 33)
    ((8192, 1, 256, None), 1024),  # latent attention: full causal, width 256
    ((8192, 8, 128, None), 1024),  # grouped-query attention, a full layer
    ((8192, 8, 128, 2048), 512),  # the same, a sliding layer: the control
    # smallthinker at 16,384 positions, seven query heads a key-value head
    # (step 0, PERF.md §6, PR 34): the quarter of a 4,096 window is 1,024
    ((16384, 7, 128, 4096), 1024), ((16384, 7, 128, None), 1024),
    # lfm2_moe's one attention layer: heads HALF a lane tile wide, four query
    # heads a key-value head (PERF.md §6, PR 38: the kernels take 64 natively)
    ((8192, 4, 64, None), 1024),
    # what masked_attention takes and larger blocks do not divide, or a band
    # too narrow for them: the granule
    ((1536, 8, 128, None), 512), ((2560, 8, 128, 2048), 512),
    ((2048, 8, 128, None), 512), ((128, 1, 256, None), 128),
])
def test_attention_blocks_at_the_cells_shapes(shape, edge):
    """The counter of a static chooser is its answer: a later PR that moves
    the geometry of a cell's call moves this table knowingly."""
    sizes = afmoe.attention_blocks(*shape, jnp.bfloat16)
    compute = min(512, edge)
    assert afmoe.kernel_blocks(sizes) == {"fwd": (edge, edge, compute),
                                "dq": (edge, edge, edge),
                                "dkv": (edge, edge, compute)}


def test_attention_blocks_shrink_by_kernel_where_vmem_is_short():
    """Head width and operand dtype enter through the VMEM estimate: float32
    operands at width 256 leave the forward and dq a long KEY block and the
    dkv kernel the granule; an override is used as given."""
    sizes = afmoe.attention_blocks(8192, 1, 256, None, jnp.float32)
    assert afmoe.kernel_blocks(sizes) == {"fwd": (512, 1024, 512), "dq": (512, 1024, 1024),
                                "dkv": (512, 512, 512)}
    assert afmoe.attention_vmem_bytes(
        "fwd", 1024, 1024, 512, 256, jnp.float32) > afmoe.VMEM_BUDGET
    given = unequal_blocks()
    assert afmoe.attention_blocks(512, 2, 128, None, jnp.float32, given) is given
    assert afmoe.kernel_blocks(afmoe.attention_blocks(
        256, 2, 128, None, jnp.float32, 128))["dkv"] == (128, 128, 128)


def test_kernel_names_are_the_librarys():
    """The constants a metric's pattern holds on to are prefixes of the names
    the splash-attention kernels give their Pallas calls."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    names = {phase: sk.get_kernel_name(True, phase == "fwd", False, phase)
             for phase in ("fwd", "dq", "dkv")}
    assert names["fwd"].startswith(afmoe.ATTN_FWD)
    assert names["dq"].startswith(afmoe.ATTN_DQ)
    assert names["dkv"].startswith(afmoe.ATTN_DKV)
    assert len({afmoe.ATTN_FWD, afmoe.ATTN_DQ, afmoe.ATTN_DKV}) == 3


def test_the_model_picks_the_kernels_on_a_tpu_only(monkeypatch):
    """On the CPU the XLA blocks run; where the backend is a TPU (steered
    here) and the sequence is whole kernel blocks, the kernels do."""
    calls = []
    monkeypatch.setattr(afmoe, "kernel_attention", lambda q, k, v, w, **kw: (
        calls.append(w), afmoe.blocked_attention(q, k, v, w, 8, 16))[1])
    _, model, _ = build(seq_len=128)
    x = jax.random.randint(jax.random.PRNGKey(0), (1, 129), 0, VOCAB)
    params = model.init({"params": jax.random.PRNGKey(0)}, x, train=True)["params"]
    model.apply({"params": params}, x)
    assert calls == []
    monkeypatch.setattr(afmoe, "_auto_pallas", lambda: True)
    model.apply({"params": params}, x)
    assert calls == [WINDOW] * 4 + [None]


# -- what a block's checkpoint keeps -------------------------------------------

TWO_BLOCKS = dict(num_hidden_layers=2, num_dense_layers=1,
                  layer_types=(SLIDING, FULL))
KERNEL_TOY = dict(seq_len=128, **TWO_BLOCKS)  # 128 rows = one kernel block


def _kernel_calls(jaxpr) -> list[str]:
    """Names of the Pallas calls in ``jaxpr``, sub-programs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
        for sub in _sub_jaxprs(eqn.params):
            found += _kernel_calls(sub)
    return found


def _loss_gradient(model, x):
    return jax.grad(lambda p: model.task_loss(
        {"params": p}, x, jnp.ones(x.shape[0])))


def test_the_forward_kernel_runs_once_a_layer(monkeypatch):
    """The mechanism's engagement counter: in the gradient of ``task_loss``
    (one sliding and one full layer, the kernel path steered on) every layer
    has ONE forward kernel, one dq and one dkv. A checkpoint that does not
    keep the forward's output and log-sum-exp runs it twice a layer."""
    monkeypatch.setattr(afmoe, "_auto_pallas", lambda: True)
    _, model, _ = build(**KERNEL_TOY)
    x = jax.random.randint(jax.random.PRNGKey(0), (1, 129), 0, VOCAB)
    params = model.init({"params": jax.random.PRNGKey(0)}, x, train=True)["params"]

    def counts():
        calls = _kernel_calls(jax.make_jaxpr(_loss_gradient(model, x))(params).jaxpr)
        assert all(c.startswith(kernels) for c in calls), calls
        return {name: sum(c.startswith(name) for c in calls) for name in kernels}

    kernels = (afmoe.ATTN_FWD, afmoe.ATTN_DQ, afmoe.ATTN_DKV)
    layers = len(KERNEL_TOY["layer_types"])
    assert counts() == dict.fromkeys(kernels, layers)
    # the counter counts: with only the routed experts' output kept, the
    # block's recomputation runs the forward kernel again
    monkeypatch.setattr(afmoe, "BLOCK_KEEPS", jax.checkpoint_policies
                        .save_only_these_names(afmoe.ROUTED_OUT))
    assert counts()[afmoe.ATTN_FWD] == 2 * layers


@pytest.mark.parametrize("path", ["kernel", "xla"])
def test_what_the_checkpoint_keeps_moves_no_gradient(monkeypatch, path):
    """Gradients of ``task_loss`` under ``BLOCK_KEEPS`` against a checkpoint
    that keeps nothing, on the kernel path (interpret mode) and on the XLA
    path (which has no named attention value). Float32 round-off is the
    limit; on this CPU backend both paths read bit-equal."""
    over = TWO_BLOCKS
    if path == "kernel":
        monkeypatch.setattr(afmoe, "_auto_pallas", lambda: True)
        over = KERNEL_TOY
    _, model, _ = build(**over)
    x = jax.random.randint(jax.random.PRNGKey(1),
                           (1, over.get("seq_len", T) + 1), 0, VOCAB)
    params = init_params(model)
    kept = _loss_gradient(model, x)(params)
    monkeypatch.setattr(afmoe, "BLOCK_KEEPS",
                        jax.checkpoint_policies.nothing_saveable)
    recomputed = _loss_gradient(model, x)(params)
    for (where, a), b in zip(jax.tree_util.tree_leaves_with_path(kept),
                             jax.tree.leaves(recomputed)):
        assert float(jnp.abs(a - b).max()) <= 1e-6 * max(
            float(jnp.abs(b).max()), 1e-3), jax.tree_util.keystr(where)


# -- the task through the trainer ---------------------------------------------


def _sites(seed: int, n_sites: int = 2, rows: int = 4):
    rng = np.random.default_rng(seed)
    return [SiteArrays(rng.integers(0, VOCAB, (rows, T + 1)).astype(np.int32),
                       np.zeros((rows,), np.int32),
                       np.arange(rows, dtype=np.int32))
            for _ in range(n_sites)]


def test_one_trainer_round_matches_the_reference_round():
    """2 sites, dSGD, Adam, the device pipeline, bfloat16 compute: the
    parameters after one epoch of one round against the reference's round."""
    cfg, model, dims = build(num_sites=2, batch_size=1, learning_rate=1e-3,
                             compute_dtype="bfloat16")
    sites = _sites(0, rows=1)
    trainer = FederatedTrainer(cfg, model, None)
    assert trainer._pipeline == "device" and trainer._donate
    state = trainer.init_state(jnp.ones((1, T + 1), jnp.int32), num_sites=2)
    before = jax.device_get(state.params)
    state, losses = trainer.run_epoch(state, sites, 1, batch_size=1)
    after = jax.device_get(state.params)
    with jax.default_matmul_precision("highest"):
        outs = [jax.value_and_grad(lambda p: ref.loss(
            p, jnp.asarray(s.inputs[0]), dims))(before) for s in sites]
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


def test_fit_validates_on_the_token_loss(tmp_path):
    cfg, model, _ = build(num_sites=2, batch_size=1, epochs=2,
                          monitor_metric="loss", metric_direction="minimize",
                          validation_epochs=1, patience=5)
    trainer = FederatedTrainer(cfg, model, None, out_dir=str(tmp_path))
    out = trainer.fit(_sites(1), _sites(2, rows=2), _sites(3, rows=2),
                      verbose=False)
    loss, monitored = out["test_metrics"][0]
    assert monitored == loss and 3.0 < loss < 6.0  # ln 96 = 4.56
    assert out["test_scores"] == {}  # no classes, no class metrics


def test_token_ids_survive_the_resident_inventory():
    """Ids above 256 come out of the gather as they went in: integer samples
    are stacked, uploaded (compute dtype bfloat16) and gathered as int32."""
    sites = [SiteArrays(
        np.asarray([[257, 25023, 4097, 300, 65535], [1, 2, 3, 4, 5]], np.int32)
        + s, np.zeros((2,), np.int32), np.arange(2, dtype=np.int32))
        for s in range(2)]
    inv = stack_site_inventory(sites)
    assert inv.inputs.dtype == np.int32
    inv_x, inv_y = put_site_inventory(None, inv, jnp.bfloat16)
    assert inv_x.dtype == jnp.int32
    xb, _, wb = _gather_batch(inv_x[1], inv_y[1], jnp.asarray([[0, -1]]),
                              sample_shape=(5,))
    assert xb.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(xb[0, 0]), sites[1].inputs[0])
    np.testing.assert_array_equal(np.asarray(xb[0, 1]), 0)
    np.testing.assert_array_equal(np.asarray(wb), [[1.0, 0.0]])


def test_float_inventories_are_still_cast_at_upload():
    sites = [SiteArrays(np.ones((2, 5), np.float32), np.zeros((2,), np.int32),
                        np.arange(2, dtype=np.int32))]
    inv_x, _ = put_site_inventory(None, stack_site_inventory(sites), jnp.bfloat16)
    assert inv_x.dtype == jnp.bfloat16


# -- scopes ---------------------------------------------------------------------

MODEL_SCOPES = ("ATTENTION_WINDOW", "ATTENTION_FULL", "MOE_ROUTE",
                "MOE_EXPERTS", "MOE_SHARED", "LM_HEAD")


@pytest.fixture(scope="module")
def lowered_epoch():
    cfg, model, _ = build(num_sites=2, batch_size=1)
    trainer = FederatedTrainer(cfg, model, None)
    state = trainer.init_state(jnp.ones((1, T + 1), jnp.int32), num_sites=2)
    inv_x = jnp.zeros((2, 3, T + 1), jnp.int32)
    inv_y = jnp.zeros((2, 3), jnp.int32)
    idx = jnp.zeros((2, 2, 1), jnp.int32)
    return trainer.epoch_fn.lower(
        state, inv_x, inv_y, idx, None, None, None, None
    ).as_text(debug_info=True)


@pytest.mark.parametrize("scope", MODEL_SCOPES)
def test_model_scope_is_in_the_lowered_epoch_program(lowered_epoch, scope):
    name = getattr(scopes, scope)
    assert name.startswith("model/")
    assert re.search(r"(?<![A-Za-z0-9_])" + re.escape(scopes.MODEL) + r"\)*/.*"
                     + re.escape(name) + r"(?![A-Za-z0-9_])", lowered_epoch), name


# -- the comparison notices a missing term --------------------------------------


def _dropped(term: str, monkeypatch):
    """The system with one term of the block taken away; the reference keeps
    the published block."""
    over, edit = {}, (lambda p: p)
    if term == "route_scale":
        over = {"route_scale": 1.0}
    elif term == "window":
        over = {"sliding_window": T}
    elif term == "shared_expert":
        def edit(p):
            return jax.tree_util.tree_map_with_path(
                lambda path, a: a * 0 if "shared" in jax.tree_util.keystr(path)
                else a, p)
    elif term == "output_gate":
        # a constant gate: the post-norm takes the constant out again
        def edit(p):
            return jax.tree_util.tree_map_with_path(
                lambda path, a: a * 0 if "wg" in jax.tree_util.keystr(path)
                else a, p)
    elif term == "qk_norm":
        plain = afmoe.rms_norm
        monkeypatch.setattr(afmoe, "rms_norm", lambda x, scale, eps: (
            x.astype(jnp.float32) if x.ndim == 4 else plain(x, scale, eps)))
    return over, edit


@pytest.mark.parametrize("term", ["none", "output_gate", "qk_norm",
                                  "shared_expert", "window", "route_scale"])
def test_the_comparison_notices_a_dropped_term(monkeypatch, term):
    """``logit_rel_rms`` (benchmarks/lib/refcheck_lm.py) of the bfloat16
    system against the float32 reference stays inside the configuration's
    limit, and leaves it when a term of the block goes missing."""
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "trinity-mini-ep16.json")) as fh:
        limit = json.load(fh)["check"]["logit_rel_rms_max"]
    _, reference_model, dims = build()
    params, x = init_params(reference_model), tokens(8)
    want = ref_logits(params, x, dims)
    over, edit = _dropped(term, monkeypatch)
    _, model, _ = build(compute_dtype="bfloat16", **over)
    err = rel_rms(model.apply({"params": edit(params)}, x), want)
    if term == "none":
        assert err < limit, err
    else:
        assert err > 2 * limit, (term, err)
