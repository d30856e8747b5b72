"""The ``glm4_moe_lite`` decoder (models/afmoe.py: latent attention, two
pre-norms, one multi-token-prediction module) against its plain reference
(benchmarks/reference/glm4_moe_lite.py), at toy widths on the CPU.

Held: logits, loss and gradients group by group, with and without the second
prediction depth and with every share of the experts; the second depth's term
alone (weight 0 is the next-token loss to the bit; it reads the next token's
embedding and predicts the one after); the expert shares of a latent-attention
layer add up to the uncut layer; the attention kernels at head width 256 with
one query head a key-value head and a rotary slice the heads share; one
forward kernel a layer; what the checkpoint keeps moves no gradient; one
``FederatedTrainer`` round equals the reference round; the comparison notices
each term that goes missing; Trinity's arguments resolve as before.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax._src.ad_checkpoint import saved_residuals
from test_afmoe import _kernel_calls, unequal_blocks

from benchmarks.lib.refcheck_lm import GROUPS, group_cosines, group_of
from benchmarks.reference import federated as fed
from benchmarks.reference import glm4_moe_lite as ref
from dinunet_implementations_tpu.core.config import (
    AFMoEArgs,
    NNComputation,
    TrainConfig,
)
from dinunet_implementations_tpu.data.api import SiteArrays
from dinunet_implementations_tpu.models import afmoe
from dinunet_implementations_tpu.models.afmoe import FULL, GLM4_MOE_LITE
from dinunet_implementations_tpu.runner.registry import (
    afmoe_layer_types,
    get_task,
)
from dinunet_implementations_tpu.trainer.loop import FederatedTrainer

T, VOCAB, EXPERTS, HELD, TOP_K = 32, 96, 16, 2, 4
TOY = dict(
    model_type=GLM4_MOE_LITE, seq_len=T, vocab_size=VOCAB, vocab_rows=VOCAB,
    hidden_size=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, num_experts=EXPERTS,
    num_experts_per_tok=TOP_K, experts_held=HELD, first_expert=0,
    num_hidden_layers=2, num_dense_layers=1, route_scale=1.8,
    rope_theta=1000000.0, q_block=8, kv_chunk=16, loss_block=8,
)
CONFIG = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "configs",
                      "glm-4.7-flash-ep8.json")


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


def init_params(model, scale: float = 5.0, t: int = T):
    """Seeded random weights, the matrices scaled up so that every term of
    the block moves the result (at std 0.02 the norms hide most of them)."""
    params = model.init({"params": jax.random.PRNGKey(0)}, tokens(9, t=t),
                        train=True)["params"]
    return jax.tree.map(lambda a: a * scale if a.ndim >= 2 else a, params)


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


def test_trinitys_arguments_resolve_as_before():
    """Every new key defaults to "off": the defaults still build Trinity's
    block, and its Dims carry no latent width and no second depth."""
    a = AFMoEArgs()
    assert (a.model_type, a.q_lora_rank, a.kv_lora_rank, a.qk_nope_head_dim,
            a.qk_rope_head_dim, a.v_head_dim, a.num_nextn_predict_layers) == (
        "afmoe", 0, 0, 0, 0, 0, 0)
    assert afmoe.Dims().model_type == afmoe.AFMOE
    cfg = TrainConfig(task_id=NNComputation.TASK_LM).with_overrides(
        {"lm_args": {"num_hidden_layers": 4}})
    assert afmoe_layer_types(cfg.lm_args)[-1] == FULL
    assert afmoe_layer_types(toy_cfg().lm_args) == (FULL,) * 2


@pytest.mark.parametrize("bad", [
    {"model_type": "other"}, {"q_lora_rank": 0}, {"v_head_dim": 12},
    {"num_nextn_predict_layers": 2},
    {"layer_types": ("sliding_attention", FULL)},
    {"model_type": "afmoe", "num_nextn_predict_layers": 1,
     "num_key_value_heads": 2, "head_dim": 16},
])
def test_the_registry_refuses_what_the_type_cannot_be(bad):
    with pytest.raises(ValueError):
        build(**bad)


def test_the_parameter_tree_sorts_into_the_checks_groups():
    _, model, _ = build(num_nextn_predict_layers=1)
    params = init_params(model)
    paths = [tuple(k.key for k in path) for path, _ in
             jax.tree_util.tree_leaves_with_path(params)]
    by_group = {g: [p for p in paths if group_of(p) == g] for g in GROUPS}
    assert all(by_group.values()), by_group
    attn = {p[-1] for p in by_group["attention"]}
    assert attn == {"wq_a", "wq_b", "wkv_a", "wkv_b", "wo"}
    # the latent norms are norms; a tree without the second depth has no mtp
    assert ("layer_0", "attn", "kv_a_norm", "scale") in by_group["norms"]
    _, plain, _ = build()
    assert "mtp" not in init_params(plain) and "mtp" in params


@pytest.mark.parametrize("depths", [0, 1])
@pytest.mark.parametrize("first", [0, EXPERTS - HELD])
def test_logits_loss_and_gradients_match_the_reference(first, depths):
    _, model, dims = build(first_expert=first, num_nextn_predict_layers=depths)
    params, x = init_params(model), tokens(1)
    got, inter = model.apply({"params": params}, x, mutable=["intermediates"])
    assert float(jnp.abs(got - ref_logits(params, x, dims)).max()) < 5e-5
    # the routing counter, as MoE sows it; the dense layer sows nothing
    assert sorted(inter["intermediates"]) == ["layer_1"]
    assert all(set(v) == {"moe"} for v in inter["intermediates"].values())
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(task_loss(model, x[:1]))(params)
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, x[0], dims)))(params)
        chain_loss, chain = ref.grads(params, x[0], dims)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert abs(float(chain_loss) - float(want_loss)) < 1e-5
    assert jax.tree.structure(grads) == jax.tree.structure(chain)
    for (path, g), w, c in zip(jax.tree_util.tree_leaves_with_path(grads),
                               jax.tree.leaves(want), jax.tree.leaves(chain)):
        scale = max(float(jnp.abs(w).max()), 1e-3)
        assert float(jnp.abs(g - w).max()) < 2e-4 * scale, jax.tree_util.keystr(path)
        assert float(jnp.abs(c - w).max()) < 2e-4 * scale, jax.tree_util.keystr(path)
    cosines = group_cosines(grads, want)
    assert set(cosines) == set(GROUPS) | {"all"}
    assert min(cosines.values()) > 1 - 1e-6, cosines


def test_vmap_over_sites_folds_the_latent_layers_too():
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


# -- the second prediction depth ------------------------------------------------


def test_weight_zero_gives_the_next_token_loss_to_the_bit():
    _, deeper, _ = build(num_nextn_predict_layers=1, mtp_loss_weight=0.0)
    _, plain, _ = build()
    params, x = init_params(deeper), tokens(2)
    main = {k: v for k, v in params.items() if k != "mtp"}
    a = deeper.apply({"params": params}, x, method=afmoe.AFMoE.token_losses)
    b = plain.apply({"params": main}, x, method=afmoe.AFMoE.token_losses)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the main logits do not read the module
    np.testing.assert_array_equal(
        np.asarray(deeper.apply({"params": params}, x)),
        np.asarray(plain.apply({"params": main}, x)))


def test_the_second_depth_is_the_references_term():
    _, deeper, dims = build(num_nextn_predict_layers=1, mtp_loss_weight=1.0)
    _, plain, _ = build()
    params, x = init_params(deeper), tokens(3, rows=1)
    main = {k: v for k, v in params.items() if k != "mtp"}
    term = float(task_loss(deeper, x)(params)) - float(task_loss(plain, x)(main))
    with jax.default_matmul_precision("highest"):
        want_main, want = jax.jit(lambda p: ref.losses(p, x[0], dims))(params)
    assert abs(float(task_loss(plain, x)(main)) - float(want_main)) < 1e-5
    assert abs(term - float(want)) < 2e-5 and float(want) > 1.0


@pytest.mark.parametrize("position,moves", [
    (0, "nothing"),  # t_0 is read by the main model alone: only through h
    (1, "first"),  # t_1 is position 0's input and nobody's target
    (T, "last"),  # t_T is the last target of both depths and nobody's input
])
def test_the_second_depth_reads_the_next_token_and_predicts_the_one_after(
        position, moves):
    """A shifted-target probe. With the module's hidden-state half cut off
    (``hnorm`` scale 0) the second depth sees the sample only through
    ``E[t_{i+1}]``: changing ``t_0`` then moves no second-depth loss, and
    changing ``t_T`` moves position ``T - 2``'s alone (its target ``t_{i+2}``).
    """
    _, model, dims = build(num_nextn_predict_layers=1, mtp_loss_weight=1.0)
    params, x = init_params(model), tokens(4, rows=1)
    params["mtp"]["hnorm"]["scale"] = jnp.zeros_like(params["mtp"]["hnorm"]["scale"])

    @jax.jit
    def per_position(sample):
        with jax.default_matmul_precision("highest"):
            h = ref.hidden(params, sample[:-1], dims)
            h2 = ref.mtp_hidden(params, h, sample, dims)
            logp = jax.nn.log_softmax(
                ref.head_logits(ref._mtp_head(params), h2, dims), -1)
        return -jnp.take_along_axis(logp, sample[2:, None], -1)[:, 0]

    other = x.at[0, position].set((x[0, position] + 1) % VOCAB)
    delta = np.abs(np.asarray(per_position(x[0]) - per_position(other[0])))
    assert delta.shape == (T - 1,)
    if moves == "nothing":
        assert delta.max() == 0.0
    elif moves == "first":
        assert delta[0] > 1e-3
    else:
        assert delta[-1] > 1e-3 and delta[:-1].max() == 0.0
    # and the system's second-depth term is the mean of exactly those
    _, plain, _ = build()
    main = {k: v for k, v in params.items() if k != "mtp"}
    term = float(task_loss(model, other)(params)) - float(
        task_loss(plain, other)(main))
    assert abs(term - float(per_position(other[0]).mean())) < 2e-5


# -- the shares ----------------------------------------------------------------


def test_the_expert_shares_of_a_latent_layer_add_up_to_the_uncut_layer():
    """One latent-attention expert layer, its 16 experts held 2 a share by 8
    shares: what the shares compute alike (the residual stream, attention,
    the shared expert) counted once plus every share's routed part equals the
    uncut reference layer."""
    dims = ref.Dims.of({**TOY, "first_expert": 0}, q_block=8)
    whole = build(experts_held=EXPERTS)[1]
    params = init_params(whole)["layer_1"]
    h = jax.random.normal(jax.random.PRNGKey(5), (1, T, 64))
    stacks = ("w1", "w3", "w2")

    def cut(first, held):
        moe = {k: (v[first: first + held] if k in stacks else v)
               for k, v in params["moe"].items()}
        return {**params, "moe": moe}

    with jax.default_matmul_precision("highest"):
        layer = jax.jit(lambda p: ref.layer_forward(p, h[0], dims))
        uncut, alike = layer(params), layer(cut(0, 0))  # alike: no routed expert
        total = alike
        for first in range(0, EXPERTS, HELD):
            share = build(first_expert=first)[1]
            block = jax.jit(afmoe.Block(share.dims, 1).apply)
            total = total + block({"params": cut(first, HELD)}, h)[0] - alike
    routed = float(jnp.abs(uncut - alike).max())
    assert routed > 0.1  # the experts did something
    assert float(jnp.abs(total - uncut).max()) < 1e-4 * float(jnp.abs(uncut).max())


# -- attention -----------------------------------------------------------------


def _latent_qkv(t=256, heads=4, nope=192, rope=64):
    """Queries, keys and values as latent attention hands them over: width
    256, one query head a key-value head, the keys' last ``rope`` columns one
    vector a position for all heads."""
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(k[0], (1, t, heads, nope + rope))
    k_r = jnp.broadcast_to(jax.random.normal(k[1], (1, t, 1, rope)),
                           (1, t, heads, rope))
    key = jnp.concatenate([jax.random.normal(k[2], (1, t, heads, nope)), k_r], -1)
    return q, key, jax.random.normal(k[3], (1, t, heads, nope + rope))


@pytest.mark.parametrize("t,blocks", [(256, 128), (512, "unequal")])
def test_kernel_attention_is_blocked_attention_at_latent_attentions_shape(t, blocks):
    """Equal blocks, and a geometry in which query, key and compute block of
    every kernel differ."""
    if blocks == "unequal":
        blocks = unequal_blocks()
    q, k, v = _latent_qkv(t=t)
    plain = lambda q, k, v: afmoe.blocked_attention(q, k, v, None, 64, 128)
    kernel = lambda q, k, v: afmoe.kernel_attention(q, k, v, None, blocks=blocks)
    assert float(jnp.abs(plain(q, k, v) - kernel(q, k, v)).max()) < 1e-5
    grad = lambda f: jax.grad(lambda *a: (f(*a) ** 2).sum(), argnums=(0, 1, 2))
    for a, b in zip(grad(plain)(q, k, v), grad(kernel)(q, k, v)):
        assert float(jnp.abs(a - b).max()) < 2e-4


def test_attention_blocks_at_latent_attentions_shape():
    """One query head a key-value head at width 256 over a causal 8,192:
    1,024-row query and key blocks in all three kernels, 512 columns at a
    time (step 0, PERF.md §6, PR 33); within the VMEM budget where 2,048-row
    blocks and whole-block compute are not (the compiler refused them)."""
    sizes = afmoe.attention_blocks(8192, 1, 256, None, jnp.bfloat16)
    assert (sizes.block_q, sizes.block_kv, sizes.block_kv_compute) == (1024, 1024, 512)
    assert (sizes.block_q_dq, sizes.block_kv_dq) == (1024, 1024)
    assert (sizes.block_q_dkv, sizes.block_kv_dkv,
            sizes.block_kv_dkv_compute) == (1024, 1024, 512)
    for refused in (("fwd", 2048, 512, 512), ("fwd", 1024, 2048, 512),
                    ("dq", 2048, 512, 512), ("dq", 1024, 2048, 2048),
                    ("dkv", 512, 2048, 512), ("dkv", 1024, 1024, 1024)):
        assert afmoe.attention_vmem_bytes(
            *refused, 256, jnp.bfloat16) > afmoe.VMEM_BUDGET, refused
    # the toy the model tests run: one granule of 128 rows
    toy = afmoe.attention_blocks(128, 1, 16, None, jnp.float32)
    assert (toy.block_q, toy.block_kv_dkv, toy.block_kv_dq) == (128, 128, 128)


def test_blocked_attention_at_latent_attentions_shape_is_plain_attention():
    q, k, v = _latent_qkv(t=T, nope=12, rope=4)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    s = jnp.einsum("btnd,bsnd->bnts", q, k) / 4.0
    p = jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), axis=-1)
    want = jnp.einsum("bnts,bsnd->btnd", p, v)
    got = afmoe.blocked_attention(q, k, v, None, q_block=8, kv_chunk=16)
    assert float(jnp.abs(got - want).max()) < 1e-5


KERNEL_TOY = dict(seq_len=128)  # 128 rows = one kernel block


def test_the_model_picks_the_kernels_on_a_tpu_only(monkeypatch):
    calls = []
    monkeypatch.setattr(afmoe, "kernel_attention", lambda q, k, v, w, **kw: (
        calls.append((w, q.shape[2:], k.shape[2:])),
        afmoe.blocked_attention(q, k, v, w, 8, 16))[1])
    _, model, _ = build(**KERNEL_TOY)
    x = tokens(0, rows=1, t=128)
    params = model.init({"params": jax.random.PRNGKey(0)}, x, train=True)["params"]
    jax.eval_shape(lambda p: model.apply({"params": p}, x), params)
    assert calls == []
    monkeypatch.setattr(afmoe, "_auto_pallas", lambda: True)
    jax.eval_shape(lambda p: model.apply({"params": p}, x), params)
    assert calls == [(None, (4, 16), (4, 16))] * 2  # full; a head a head


@pytest.mark.parametrize("depths", [0, 1])
def test_the_forward_kernel_runs_once_a_layer(monkeypatch, depths):
    """In the gradient of ``task_loss`` (kernel path steered on) every latent
    layer, the second depth's block included, has ONE forward kernel, one dq
    and one dkv: the checkpoint keeps the kernel's output and log-sum-exp."""
    monkeypatch.setattr(afmoe, "_auto_pallas", lambda: True)
    _, model, _ = build(num_nextn_predict_layers=depths, **KERNEL_TOY)
    x = tokens(0, rows=1, t=128)
    params = model.init({"params": jax.random.PRNGKey(0)}, x, train=True)["params"]
    kernels = (afmoe.ATTN_FWD, afmoe.ATTN_DQ, afmoe.ATTN_DKV)

    def counts():
        calls = _kernel_calls(
            jax.make_jaxpr(jax.grad(task_loss(model, x)))(params).jaxpr)
        assert all(c.startswith(kernels) for c in calls), calls
        return {name: sum(c.startswith(name) for c in calls) for name in kernels}

    layers = TOY["num_hidden_layers"] + depths
    assert counts() == dict.fromkeys(kernels, layers)
    monkeypatch.setattr(afmoe, "BLOCK_KEEPS", jax.checkpoint_policies
                        .save_only_these_names(afmoe.ROUTED_OUT))
    assert counts()[afmoe.ATTN_FWD] == 2 * layers


def test_the_checkpoint_keeps_the_latent_and_not_the_keys():
    """What a block's checkpoint saves of its own computing (besides, on the
    kernel path, the kernel's result) is the raw latent, ``kv_lora_rank +
    qk_rope_head_dim`` wide a position: nothing of the heads' width."""
    _, model, _ = build(**KERNEL_TOY)
    x = tokens(0, rows=1, t=128)
    params = model.init({"params": jax.random.PRNGKey(0)}, x, train=True)["params"]
    block = afmoe.Block(model.dims, 1)
    h = jnp.zeros((1, 128, 64))
    saved = saved_residuals(
        jax.checkpoint(lambda p, h: block.apply({"params": p}, h).sum(),
                       policy=afmoe.BLOCK_KEEPS), params["layer_1"], h)
    computed = [a.shape for a, why in saved
                if "argument" not in why and "constant" not in why]
    # (the XLA attention path names no value, and nothing after the routed
    # experts' output reads it in this block: a residual add)
    assert computed == [(1, 128, 16 + 4)]


@pytest.mark.parametrize("path", ["kernel", "xla"])
def test_what_the_checkpoint_keeps_moves_no_gradient(monkeypatch, path):
    over, t = dict(num_nextn_predict_layers=1), T
    if path == "kernel":
        monkeypatch.setattr(afmoe, "_auto_pallas", lambda: True)
        over, t = {**over, **KERNEL_TOY}, 128
    x = tokens(1, rows=1, t=t)
    _, model, _ = build(**over)
    params = init_params(model, t=t)
    kept = jax.jit(jax.grad(task_loss(model, x)))(params)
    monkeypatch.setattr(afmoe, "BLOCK_KEEPS",
                        jax.checkpoint_policies.nothing_saveable)
    _, model, _ = build(**over)
    recomputed = jax.jit(jax.grad(task_loss(model, x)))(params)
    for (where, a), b in zip(jax.tree_util.tree_leaves_with_path(kept),
                             jax.tree.leaves(recomputed)):
        assert float(jnp.abs(a - b).max()) <= 1e-6 * max(
            float(jnp.abs(b).max()), 1e-3), jax.tree_util.keystr(where)


# -- the task through the trainer ------------------------------------------------


@pytest.mark.parametrize("depths", [0, 1])
def test_one_trainer_round_matches_the_reference_round(depths):
    """2 sites, dSGD, Adam, the device pipeline, bfloat16 compute: the
    parameters after one epoch of one round against the reference's round."""
    cfg, model, dims = build(num_sites=2, batch_size=1, learning_rate=1e-3,
                             compute_dtype="bfloat16",
                             num_nextn_predict_layers=depths)
    rng = np.random.default_rng(0)
    sites = [SiteArrays(rng.integers(0, VOCAB, (1, T + 1)).astype(np.int32),
                        np.zeros((1,), np.int32), np.arange(1, dtype=np.int32))
             for _ in range(2)]
    trainer = FederatedTrainer(cfg, model, None)
    state = trainer.init_state(jnp.ones((1, T + 1), jnp.int32), num_sites=2)
    before = jax.device_get(state.params)
    assert ("mtp" in before) == bool(depths)
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
    over = {}
    if term == "routed_scaling_factor":
        over = {"route_scale": 1.0}
    elif term == "mtp_term":
        over = {"mtp_loss_weight": 0.0}
    elif term == "rotary_on_k_r":
        plain = afmoe.rotary
        monkeypatch.setattr(afmoe, "rotary", lambda x, pos, theta: (
            x if x.shape[-2] == 1 else plain(x, pos, theta)))
    elif term == "latent_norm":
        plain = afmoe.rms_norm
        monkeypatch.setattr(afmoe, "rms_norm", lambda x, scale, eps: (
            x.astype(jnp.float32) if x.shape[-1] in (24, 16)
            else plain(x, scale, eps)))
    return over


@pytest.mark.parametrize("term", ["none", "rotary_on_k_r", "latent_norm",
                                  "routed_scaling_factor", "mtp_term"])
def test_the_comparison_notices_a_dropped_term(monkeypatch, term):
    """``logit_rel_rms`` and ``loss_abs_err`` (benchmarks/lib/refcheck_lm.py)
    of the bfloat16 system against the float32 reference stay inside the
    configuration's limits, and one of them leaves when a term goes missing
    (the second depth's term shows in the loss alone: the logits are the next
    token's)."""
    with open(CONFIG) as fh:
        limits = json.load(fh)["check"]
    # every expert held, so that the routed part is a large share of a layer
    whole = dict(num_nextn_predict_layers=1, experts_held=EXPERTS)
    _, reference_model, dims = build(**whole)
    params, x = init_params(reference_model), tokens(8)
    want = ref_logits(params, x, dims)
    with jax.default_matmul_precision("highest"):
        ref_loss = jax.jit(lambda row: ref.loss(params, row, dims))
        want_loss = float(np.mean([ref_loss(row) for row in x]))
    over = _dropped(term, monkeypatch)
    _, model, _ = build(compute_dtype="bfloat16", **{**whole, **over})
    err = rel_rms(model.apply({"params": params}, x), want)
    loss_err = abs(float(task_loss(model, x)(params)) - want_loss)
    if term == "none":
        assert err < limits["logit_rel_rms_max"], err
    elif term == "mtp_term":
        assert err < limits["logit_rel_rms_max"]
        assert loss_err > 20 * limits["loss_atol"], loss_err
    else:
        assert err > 2 * limits["logit_rel_rms_max"], (term, err)
