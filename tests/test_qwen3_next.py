"""The ``qwen3_next`` decoder (models/afmoe.py: Gated DeltaNet on
``linear_attention`` layers, gated attention over partial rotary on the full
ones, a softmax router over all experts, a sigmoid-gated shared expert)
against its plain reference (benchmarks/reference/qwen3_next.py), at toy
widths on the CPU.

Held: the chunked delta rule against the reference's position-by-position
recurrence (chunks of 8 and 16, whole and ragged lengths, several segments),
its gradients, log-gates of -21 a position; the block-wise triangular inverse;
the whole linear layer; gated attention with partial rotary on both attention
paths; the router against the published softmax-top-k-renormalise; the
shared expert's gate; the four shares of an expert layer against the uncut
layer; logits, loss and gradients; one ``FederatedTrainer`` round through the
site vmap; what the registry refuses; the comparison notices each term that goes
missing; the accepted types keep their expert layers' trees.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib.refcheck_lm import group_cosines, group_of
from benchmarks.reference import federated as fed
from benchmarks.reference import qwen3_next as ref
from dinunet_implementations_tpu.core.config import NNComputation, TrainConfig
from dinunet_implementations_tpu.data.api import SiteArrays
from dinunet_implementations_tpu.models import afmoe
from dinunet_implementations_tpu.models.afmoe import (
    CONV,
    FULL,
    LINEAR,
    QWEN3_NEXT,
    SLIDING,
)
from dinunet_implementations_tpu.ops import delta_pallas
from dinunet_implementations_tpu.runner.registry import (
    afmoe_layer_types,
    get_task,
)
from dinunet_implementations_tpu.trainer.loop import FederatedTrainer

T, VOCAB, EXPERTS, HELD, TOP_K, HIDDEN = 32, 96, 16, 4, 4, 64
KINDS = (LINEAR, FULL)  # a layer of each kind (the period is four)
TOY = dict(
    model_type=QWEN3_NEXT, seq_len=T, vocab_size=VOCAB, vocab_rows=VOCAB,
    hidden_size=HIDDEN, num_attention_heads=4, num_key_value_heads=2,
    head_dim=32, partial_rotary_factor=0.25, moe_intermediate_size=32,
    num_experts=EXPERTS, num_experts_per_tok=TOP_K, num_shared_experts=1,
    experts_held=HELD, first_expert=0, num_hidden_layers=2, layer_types=KINDS,
    num_dense_layers=0, global_attn_every_n_layers=4,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4, rope_theta=1e7,
    rms_norm_eps=1e-6, route_norm=True, q_block=8, kv_chunk=16, loss_block=8,
)
CONFIG = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "configs",
                      "qwen3-next-80b-a3b-ep32.json")
with open(CONFIG) as _fh:
    CHECK = json.load(_fh)["check"]
#: the configuration's own groups of the check (Gated DeltaNet apart)
NAMED = [tuple(pair) for pair in CHECK["groups"]]


@pytest.fixture(scope="module", autouse=True)
def small_chunks():
    """The model's chunks at 8 positions: four chunks of a 32-token toy
    sequence in one segment (the rule's own tests take other sizes)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(afmoe, "DELTA_CHUNK", 8)
        yield


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
                       q_block=8, head_block=8, recurrence_block=8)
    return cfg, model, dims


def tokens(seed: int, rows: int = 2, t: int = T):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, t + 1), 0, VOCAB)


def init_params(model, scale: float = 5.0):
    """Seeded random weights, the matrices scaled up so that every term of
    the block moves the result, the norms' scales drawn too (``A_log`` and
    ``dt_bias`` as initialised)."""
    params = model.init({"params": jax.random.PRNGKey(0)}, tokens(9),
                        train=True)["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))

    def draw(path, a):
        if path[-1].key in ("A_log", "dt_bias"):
            return a
        if a.ndim >= 2:
            return a * scale
        return a * (1.0 + 0.5 * jax.random.normal(next(keys), a.shape))

    return jax.tree_util.tree_map_with_path(draw, params)


def task_loss(model, x):
    return jax.jit(
        lambda p: model.task_loss({"params": p}, x, jnp.ones(x.shape[0])))


def ref_logits(params, x, dims):
    with jax.default_matmul_precision("highest"):
        forward = jax.jit(lambda row: ref.forward(params, row[:-1], dims))
        return jnp.stack([forward(row) for row in x])


# -- the delta rule --------------------------------------------------------------


def _rule_inputs(t: int, seed: int = 0, gates=None, heads: int = 3):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    b, dk, dv = 2, 8, 6
    q = afmoe.l2_normalize(jax.random.normal(keys[0], (b, t, heads, dk))) * dk ** -0.5
    k = afmoe.l2_normalize(jax.random.normal(keys[1], (b, t, heads, dk)))
    v = jax.random.normal(keys[2], (b, t, heads, dv))
    beta = jax.nn.sigmoid(jax.random.normal(keys[3], (b, t, heads)))
    g = (-jnp.exp(jax.random.uniform(keys[4], (b, t, heads), minval=-4, maxval=3))
         if gates is None else jnp.broadcast_to(gates, (b, t, heads)))
    return q, k, v, beta, g


@jax.jit
def _recurrence(q, k, v, beta, g):
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda *a: ref.delta_rule(*a, 8))(q, k, v, beta, g)


@pytest.mark.parametrize("chunk,t,segment", [
    (8, 37, 2),  # a ragged tail, five chunks padded to three segments of two
    (16, 48, 2),  # whole chunks, two segments
    (16, 37, afmoe.DELTA_SEGMENT),  # a ragged tail, one segment
])
def test_the_chunked_rule_is_the_recurrence(monkeypatch, chunk, t, segment):
    monkeypatch.setattr(afmoe, "DELTA_SEGMENT", segment)
    args = _rule_inputs(t)
    got = jax.jit(lambda *a: afmoe.chunked_delta_rule(*a, chunk=chunk))(*args)
    want = _recurrence(*args)
    assert got.shape == want.shape == (2, t, 3, 6)
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())
    # causal: the last position changes nothing before it
    moved = afmoe.chunked_delta_rule(
        *(a.at[:, -1].add(0.7) for a in args[:3]), *args[3:], chunk=chunk)
    assert float(jnp.abs(moved[:, :-1] - got[:, :-1]).max()) == 0.0


@pytest.mark.parametrize("chunk", [8, 16])
def test_the_chunked_rules_gradients_are_the_recurrences(monkeypatch, chunk):
    monkeypatch.setattr(afmoe, "DELTA_SEGMENT", 2)
    args = _rule_inputs(40, seed=1)
    w = jax.random.normal(jax.random.PRNGKey(7), (2, 40, 3, 6))

    def loss(rule):
        return lambda *a: jnp.sum(rule(*a) * w)

    got = jax.jit(jax.grad(loss(lambda *a: afmoe.chunked_delta_rule(
        *a, chunk=chunk)), argnums=range(5)))(*args)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss(lambda *a: _recurrence(*a)), argnums=range(5))(*args)
    for name, a, b in zip("q k v beta g".split(), got, want):
        assert float(jnp.abs(a - b).max()) < 5e-5 * float(jnp.abs(b).max()), name


def test_log_gates_of_minus_21_stay_finite_and_agree():
    """The published initialisation reaches log-gates of about -21 a
    position: ``exp`` of a chunk's cumulative sum underflows within five
    positions, and nothing divides one decay by another. Mixed with gates
    of 0 (a head that keeps everything) and with -21 everywhere."""
    for gates in (jnp.float32(-21.0),
                  jnp.where(jnp.arange(3) == 1, -21.0, 0.0)):
        args = _rule_inputs(40, seed=2, gates=gates)
        got, grads = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(afmoe.chunked_delta_rule(*a, chunk=16) ** 2),
            argnums=range(5)))(*args)
        want, wgrads = jax.value_and_grad(
            lambda *a: jnp.sum(_recurrence(*a) ** 2), argnums=range(5))(*args)
        assert np.isfinite(float(got))
        assert abs(float(got) - float(want)) < 1e-4 * float(want)
        for a, b in zip(grads, wgrads):
            assert bool(jnp.isfinite(a).all())
            assert float(jnp.abs(a - b).max()) < 1e-4 * max(float(jnp.abs(b).max()), 1e-6)


@pytest.mark.parametrize("n,alike", [(64, False), (64, True), (16, False)])
def test_the_block_inverse_is_the_inverse(n, alike):
    """``(I + a)^-1`` by substitution on 16-row blocks and merges, against
    float64; ``alike`` is a chunk of one key repeated at beta 1 (every entry
    of ``a`` 1), where a power series of ``a`` would sum terms of 1e17."""
    if alike:
        a = jnp.tril(jnp.ones((2, n, n)), -1)
    else:
        a = 0.3 * jnp.tril(jax.random.normal(jax.random.PRNGKey(3), (2, n, n)), -1)
    got = np.asarray(afmoe.unit_lower_inverse(a), np.float64)
    want = np.linalg.inv(np.eye(n) + np.asarray(a, np.float64))
    assert np.abs(got - want).max() < 1e-5 * max(np.abs(want).max(), 1.0)


# -- the delta rule's kernels (ops/delta_pallas.py), in interpret mode -----------


def _wide_inputs(t: int, rows: int = 1, gates=None, alike: bool = False,
                 norm: float = 1.0):
    """The raw columns ``[q | k | v] [rows, t, 2 * 256 + 4 * 128]`` as the
    convolution leaves them (``q`` and ``k`` not normed, entries of about
    ``norm``), ``beta`` and ``g``: heads of 128 (whole lane tiles), two value
    heads a key head (the cell's grouping); ``alike``: the second chunk holds
    one key repeated at beta 1, where a power series of the chunk's matrix
    sums terms of 1e17."""
    keys = jax.random.split(jax.random.PRNGKey(4), 6)
    d, heads = 128, 4
    q = norm * jax.random.normal(keys[0], (rows, t, 2 * d))
    k = norm * jax.random.normal(keys[1], (rows, t, 2 * d))
    v = jax.random.normal(keys[2], (rows, t, heads * d))
    beta = jax.nn.sigmoid(jax.random.normal(keys[3], (rows, t, heads)))
    g = (-jnp.exp(jax.random.uniform(keys[4], (rows, t, heads), minval=-4, maxval=3))
         if gates is None else jnp.broadcast_to(gates, (rows, t, heads)))
    if alike:
        k = k.at[:, 64:128].set(k[:, 64:65])
        beta = beta.at[:, 64:128].set(1.0)
    return jnp.concatenate([q, k, v], axis=-1), beta, g


def _rule_and_grads(kernels: bool, args, w):
    """``afmoe.gated_delta_rule`` on raw columns, the kernels taken or not
    (in interpret mode on this backend): the output and the cotangents of
    ``sum(o * w)`` for the raw columns, ``beta`` and ``g``."""
    def rule(*a):
        return afmoe.gated_delta_rule(*a, 2, 128, chunk=64)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(afmoe, "_auto_pallas", lambda: kernels)
        assert (delta_pallas.DELTA_FWD in str(jax.make_jaxpr(rule)(*args))) == kernels
        return jax.jit(lambda *a: (rule(*a), jax.grad(
            lambda *b: jnp.sum(rule(*b) * w), argnums=range(3))(*a)))(*args)


def _normed_recurrence(qkv, beta, g):
    """The reference on raw columns: ``q`` and ``k`` l2-normed (``q`` scaled
    by ``128^-1/2``), each key head read by its two value heads, the rule
    position by position."""
    B, T = beta.shape[:2]
    q, k = (afmoe.l2_normalize(qkv[..., i * 256:(i + 1) * 256].reshape(B, T, 2, 128))
            for i in (0, 1))
    return _recurrence(jnp.repeat(q * 128 ** -0.5, 2, axis=2),
                       jnp.repeat(k, 2, axis=2),
                       qkv[..., 512:].reshape(B, T, 4, 128), beta, g)


def _the_kernels_against_xla_and_the_recurrence(args, w):
    """The output and the five cotangents (raw ``q``, ``k``, ``v``, ``beta``
    and ``g``) of the kernels against XLA's norm and chunked rule and
    against the position-by-position reference."""
    out, grads = _rule_and_grads(True, args, w)
    xla_out, xla_grads = _rule_and_grads(False, args, w)
    want = _normed_recurrence(*args)
    with jax.default_matmul_precision("highest"):
        ref_grads = jax.grad(lambda *a: jnp.sum(_normed_recurrence(*a) * w),
                             argnums=range(3))(*args)
    assert bool(jnp.isfinite(out).all())
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(out - xla_out).max()) < 1e-5 * scale
    assert float(jnp.abs(out - want).max()) < 5e-5 * scale

    def five(cotangents):  # the raw columns' cotangent split into q | k | v
        qkv, beta, g = cotangents
        return (qkv[..., :256], qkv[..., 256:512], qkv[..., 512:], beta, g)

    for name, a, x, r in zip("q k v beta g".split(), five(grads), five(xla_grads),
                             five(ref_grads)):
        assert bool(jnp.isfinite(a).all()), name
        top = max(float(jnp.abs(r).max()), 1e-6)
        assert float(jnp.abs(a - x).max()) < 1e-5 * top, name
        assert float(jnp.abs(a - r).max()) < 1e-4 * top, name


@pytest.mark.parametrize("case", ["ragged-segments", "gates", "alike"])
def test_the_delta_kernels_are_the_chunked_rule_and_the_recurrence(
        monkeypatch, case):
    """Both kernels, fed the raw columns, against XLA's norm and chunked
    rule and the position-by-position reference: the output and the five
    cotangents. ``ragged-segments``: two sequences of three chunks and a tail
    of 17 positions, two segments of two chunks, two head blocks of the
    grid; ``gates``: log-gates of -21 mixed with 0 (a head that keeps
    everything); ``alike``: a chunk of one repeated key at beta 1."""
    monkeypatch.setattr(afmoe, "DELTA_SEGMENT", 2)
    t, rows, gates = 3 * 64 + 17, 1, None
    if case == "ragged-segments":
        rows = 2
        monkeypatch.setattr(delta_pallas, "HEAD_BLOCKS", (2,))
    elif case == "gates":
        gates = jnp.where(jnp.arange(4) % 2 == 1, -21.0, 0.0)
    args = _wide_inputs(t, rows, gates, alike=case == "alike")
    w = jax.random.normal(jax.random.PRNGKey(8), (rows, t, 4, 128))
    _the_kernels_against_xla_and_the_recurrence(args, w)


@pytest.mark.parametrize("case", ["large-norm", "tiny-norm", "alike"])
def test_the_kernels_norm_the_raw_columns_as_l2_normalize_does(monkeypatch, case):
    """The norm inside the kernels (``x rsqrt(sum x^2 + 1e-6)``, the query's
    scale in its factor, and its transpose to the raw columns' cotangents)
    against ``l2_normalize`` in XLA and the reference, over a chunk and a
    tail: ``large-norm``, rows of norm about 11,000; ``tiny-norm``, rows
    whose squared norm, about 1.3e-6, is of the order of ``eps``;
    ``alike``, a chunk of one repeated raw key at beta 1."""
    monkeypatch.setattr(afmoe, "DELTA_SEGMENT", 2)
    norm = {"large-norm": 1e3, "tiny-norm": 1e-4}.get(case, 1.0)
    args = _wide_inputs(64 + 9, norm=norm, alike=case == "alike")
    w = jax.random.normal(jax.random.PRNGKey(9), (1, 64 + 9, 4, 128))
    _the_kernels_against_xla_and_the_recurrence(args, w)


def test_delta_blocks_at_the_cells_shapes():
    """``delta_block`` is the chooser and the dispatch predicate: four value
    heads a grid step at the Qwen3-Next cell (eight do not fit beside
    sixteen chunk states and inverses a head), eight where a sequence is one
    segment of four chunks; None at 64-wide heads, where a block cannot hold
    whole key groups, where value heads are no whole groups of the key
    heads, or where no block that holds whole groups starts ``v``'s columns
    at a whole block of the raw ``[q | k | v]`` array (one key head of 128
    and four value heads: ``v`` starts at column 256, a block of four value
    heads is 512 wide)."""
    cell = (32, 16, 128, 128, 16)
    assert delta_pallas.delta_block(8192, *cell) == 4
    assert (delta_pallas.block_vmem_bytes(8, *cell) > delta_pallas.VMEM_BUDGET
            >= delta_pallas.block_vmem_bytes(4, *cell))
    assert delta_pallas.delta_block(256, *cell) == 8
    assert delta_pallas.delta_block(8192, 32, 16, 64, 64, 16) is None
    assert delta_pallas.delta_block(8192, 32, 2, 128, 128, 16) is None
    assert delta_pallas.delta_block(8192, 3, 2, 128, 128, 16) is None
    assert delta_pallas.delta_block(8192, 4, 1, 128, 128, 16) is None


def test_the_kernels_are_taken_by_shape_alone():
    """On a TPU backend ``gated_delta_rule`` hands the raw columns to the
    kernels where ``delta_block`` takes the shape and chunks are 64; the toy
    model's 16-wide heads and chunks of 8 stay XLA's (``l2_normalize``, then
    ``chunked_delta_rule``, which never calls a kernel)."""
    wide = _wide_inputs(64)
    q, k, v, beta, g = _rule_inputs(64)
    narrow = (jnp.concatenate([x.reshape(2, 64, -1) for x in (q, k, v)], -1),
              beta, g)

    def calls(args, heads, width, chunk):
        return str(jax.make_jaxpr(lambda *a: afmoe.gated_delta_rule(
            *a, heads, width, chunk=chunk))(*args)).count("pallas_call")

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(afmoe, "_auto_pallas", lambda: True)
        assert calls(wide, 2, 128, 64) == 1
        assert calls(wide, 2, 128, 32) == calls(narrow, 3, 8, 64) == 0
        qkv, beta, g = wide
        heads = [qkv[..., i:i + n].reshape(1, 64, n // 128, 128)
                 for i, n in ((0, 256), (256, 256), (512, 512))]
        assert "pallas_call" not in str(jax.make_jaxpr(
            lambda *a: afmoe.chunked_delta_rule(*a, chunk=64))(*heads, beta, g))
    assert calls(wide, 2, 128, 64) == 0  # not a TPU


def test_the_linear_layer_against_the_reference():
    """Gated DeltaNet as the block builds it (projections, convolution, the
    gates, the chunked rule, the gated norm, the output projection) against
    the reference's, position by position."""
    layer = afmoe.GatedDeltaNet(2, 4, 16, 16, 4, 1e-6)
    a = jax.random.normal(jax.random.PRNGKey(4), (2, 40, HIDDEN))
    params = layer.init(jax.random.PRNGKey(5), a)["params"]
    assert {k: jnp.shape(v) for k, v in params.items() if k != "norm"} == {
        "in_proj_qkvz": (HIDDEN, 2 * 32 + 2 * 64), "in_proj_ba": (HIDDEN, 8),
        "conv1d": (128, 4), "A_log": (4,), "dt_bias": (4,),
        "out_proj": (64, HIDDEN)}
    assert params["norm"]["scale"].shape == (16,)
    a_of_init = np.exp(np.asarray(params["A_log"]))
    assert ((a_of_init > 0) & (a_of_init < 16)).all()
    assert (np.asarray(params["dt_bias"]) == 1).all()
    params = {**jax.tree.map(lambda w: w * 10.0 if w.ndim == 2 else w, params),
              "norm": {"scale": 1.0 + 0.3 * jax.random.normal(
                  jax.random.PRNGKey(6), (16,))}}
    got = jax.jit(layer.apply)({"params": params}, a)
    dims = ref.Dims(linear_num_key_heads=2, linear_num_value_heads=4,
                    linear_key_head_dim=16, linear_value_head_dim=16,
                    recurrence_block=8)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(lambda row: ref.linear_attention(params, row, dims)))(a)
    assert float(jnp.abs(got - want).max()) < 5e-5 * float(jnp.abs(want).max())


def _layer_before(params, a, cdt, nk=2, nv=4, dk=16, dv=16, eps=1e-6):
    """``GatedDeltaNet`` as it was before the kernels took the raw columns:
    ``q`` and ``k`` l2-normed in XLA beside the convolution and the gates,
    under their checkpoint, then ``chunked_delta_rule`` and the gated norm."""
    B, T, _ = a.shape
    keys, values = nk * dk, nv * dv
    qkvz = afmoe._mm(a, params["in_proj_qkvz"], cdt)
    ba = afmoe._mm(a, params["in_proj_ba"], cdt)

    @jax.checkpoint
    def rule_inputs(qkv, ba, filt, a_log, dt_bias):
        mixed = jax.nn.silu(afmoe.causal_conv(qkv, filt.astype(jnp.float32)))
        beta = jax.nn.sigmoid(ba[..., :nv])
        g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
            ba[..., nv:] + dt_bias.astype(jnp.float32))
        q, k = (afmoe.l2_normalize(mixed[..., i * keys: (i + 1) * keys]
                                   .reshape(B, T, nk, dk)) for i in (0, 1))
        v = mixed[..., 2 * keys:].reshape(B, T, nv, dv)
        return q * dk ** -0.5, k, v, beta, g

    q, k, v, beta, g = rule_inputs(qkvz[..., : 2 * keys + values], ba,
                                   params["conv1d"], params["A_log"],
                                   params["dt_bias"])
    o = afmoe.chunked_delta_rule(q, k, v, beta, g, chunk=afmoe.DELTA_CHUNK,
                                 cdt=cdt)

    @jax.checkpoint
    def gated(o, z, norm):
        return afmoe.rms_norm(o, norm, eps) * jax.nn.silu(z)

    o = gated(o, qkvz[..., 2 * keys + values:].reshape(B, T, nv, dv),
              params["norm"]["scale"])
    return afmoe._mm(o.reshape(B, T, values), params["out_proj"], cdt)


@pytest.mark.parametrize("compute_dtype", [None, "bfloat16"])
def test_off_the_kernels_the_layer_is_what_it_was_to_the_digit(compute_dtype):
    """Where the kernels do not take the shape (here: 16-wide heads on the
    CPU) the layer hands its raw columns to ``gated_delta_rule``, which norms
    ``q`` and ``k`` in XLA: the output is the one the layer gave when it
    normed them itself, to the digit."""
    layer = afmoe.GatedDeltaNet(2, 4, 16, 16, 4, 1e-6, compute_dtype=compute_dtype)
    a = jax.random.normal(jax.random.PRNGKey(4), (2, 40, HIDDEN))
    params = jax.tree.map(lambda w: w * 10.0 if w.ndim == 2 else w,
                          layer.init(jax.random.PRNGKey(5), a)["params"])
    cdt = afmoe.compute_dtype_of(compute_dtype)
    got = jax.jit(layer.apply)({"params": params}, a)
    want = jax.jit(lambda p, x: _layer_before(p, x, cdt))(params, a)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- gated attention over partial rotary -----------------------------------------


@pytest.mark.parametrize("path,t", [("xla", T), ("kernel", 256)])
def test_gated_attention_with_partial_rotary_against_the_reference(
        monkeypatch, path, t):
    """QK-norm, then rotary over the first quarter of each head only, causal,
    the output gate, four query heads on two key-value heads of width 64: the
    layer as the block builds it against the reference's ``pre`` / ``core`` /
    gate / ``W_o``, on the XLA blocks and on the splash kernels (interpret
    mode; the rotary hand-over kernel rotates whole heads, so XLA's rotary
    feeds them)."""
    if path == "kernel":
        monkeypatch.setattr(afmoe, "_auto_pallas", lambda: True)
        afmoe._splash.cache_clear()
    n, g, d = 4, 2, 64
    layer = afmoe.Attention(n, g, d, None, 1e7, 1e-6, 8, 16, qk_norm=True,
                            gate=True, rope=True, rotary_dim=16)
    raw = jax.random.normal(jax.random.PRNGKey(6), (t, HIDDEN))
    a = ref.rms(raw, jnp.ones((HIDDEN,)), 1e-6)[None]
    params = layer.init(jax.random.PRNGKey(7), a)["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(8), 8))
    params = jax.tree.map(
        lambda w: w * 5.0 if w.ndim >= 2
        else w * (1.0 + 0.5 * jax.random.normal(next(keys), w.shape)), params)
    assert sorted(params) == ["k_norm", "q_norm", "wg", "wk", "wo", "wq", "wv"]
    assert afmoe.takes_kernels(t) == (path == "kernel")
    try:
        got = jax.jit(layer.apply)({"params": params}, a)[0]
    finally:
        afmoe._splash.cache_clear()
    dims = ref.Dims(num_attention_heads=n, num_key_value_heads=g, head_dim=d,
                    partial_rotary_factor=0.25, q_block=8)
    p = {"attn": params, "input_norm": {"scale": jnp.ones((HIDDEN,))}}

    @jax.jit
    def reference(p, raw):
        q, k, v, gate = ref.pre(p, raw, dims)
        o = jnp.concatenate([ref.core(q[s:e], k, v, s, dims)
                             for s, e in ref._query_blocks(t, dims)])
        return (o * gate) @ p["attn"]["wo"]

    with jax.default_matmul_precision("highest"):
        want = reference(p, raw)
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())


def test_partial_rotary_moves_the_first_dimensions_only():
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 12, 2, 32))
    pos = jnp.arange(12)
    got, part, whole, full = jax.jit(lambda x: (
        afmoe.partial_rotary(x, pos, 1e7, 8), afmoe.rotary(x[..., :8], pos, 1e7),
        afmoe.partial_rotary(x, pos, 1e7, 32), afmoe.rotary(x, pos, 1e7)))(x)
    assert float(jnp.abs(got[..., 8:] - x[..., 8:]).max()) == 0.0
    assert float(jnp.abs(got[..., :8] - part).max()) == 0.0
    assert float(jnp.abs(got[:, 1:, :, :8] - x[:, 1:, :, :8]).max()) > 1e-3
    assert float(jnp.abs(whole - full).max()) == 0.0


# -- the expert layer ----------------------------------------------------------------


def test_the_router_is_top_k_of_the_softmax_renormalised():
    """``route_chosen`` (a softmax over the chosen logits) against the
    published rule (a softmax over all 512, the top 10, renormalised): the
    same experts, the same weights and the same gradient."""
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(10), (64, 512))
    ct = jax.random.normal(jax.random.PRNGKey(11), (64, 10))
    published = lambda l: ref.routing(  # noqa: E731  the router the identity
        {"router": jnp.eye(512)}, l, ref.Dims(num_experts_per_tok=10))

    @jax.jit
    def both(l):
        with jax.default_matmul_precision("highest"):
            grads = [jax.grad(lambda l: jnp.sum(rule(l)[1] * ct))(l)
                     for rule in (lambda l: afmoe.route_chosen(l, 10), published)]
            return afmoe.route_chosen(l, 10), published(l), grads

    (sel, w), (want_sel, want_w), (got, want) = both(logits)
    assert (np.asarray(sel) == np.asarray(want_sel)).all()
    assert float(jnp.abs(w - want_w).max()) < 1e-6
    assert float(jnp.abs(got - want).max()) < 1e-6


def _moe(shared_gate=True, first=0, held=HELD):
    return afmoe.MoE(HIDDEN, EXPERTS, TOP_K, held, first, 32, 32, True, 1.0,
                     softmax_routing=True, shared_gate=shared_gate)


def test_the_shared_expert_is_scaled_by_its_gate():
    m = jax.random.normal(jax.random.PRNGKey(12), (1, T, HIDDEN))
    module = _moe()
    params = jax.tree.map(lambda w: w * 5.0,
                          module.init(jax.random.PRNGKey(13), m)["params"])
    assert sorted(params) == ["router", "shared", "shared_expert_gate", "w1",
                              "w2", "w3"]  # no expert_bias: softmax routing
    assert params["shared_expert_gate"].shape == (HIDDEN, 1)
    silent = {**params, "w2": jnp.zeros_like(params["w2"])}  # routed part 0
    got = jax.jit(module.apply)({"params": silent}, m)
    shared = jax.jit(afmoe.SwiGLU(32).apply)({"params": params["shared"]}, m)
    gate = jax.nn.sigmoid(m @ params["shared_expert_gate"])
    assert float(jnp.abs(got - gate * shared).max()) < 1e-5 * float(jnp.abs(shared).max())
    assert float(jnp.abs(gate - 0.5).max()) > 0.1  # the gate does something
    ungated = _moe(shared_gate=False)
    assert "shared_expert_gate" not in ungated.init(jax.random.PRNGKey(13), m)["params"]


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """One expert layer, its 16 experts held 4 a share by 4 shares: what the
    shares compute alike (the residual stream, the token mixer, the router
    and the gated shared expert) counted once plus every share's routed part
    equals the uncut reference layer (a linear layer: the expert layer is the
    same after either token mixer)."""
    layer = 0
    _, whole, dims = build(experts_held=EXPERTS)
    params = init_params(whole)[f"layer_{layer}"]

    def cut(first, held):
        moe = {k: (v[first: first + held] if k in ("w1", "w3", "w2") else v)
               for k, v in params["moe"].items()}
        return {**params, "moe": moe}

    h = jax.random.normal(jax.random.PRNGKey(14), (1, T, HIDDEN))
    with jax.default_matmul_precision("highest"):
        run = jax.jit(lambda p: ref.layer_forward(p, h[0], dims, layer))
        uncut, alike = run(params), run(cut(0, 0))  # no routed expert
        total = alike
        for first in range(0, EXPERTS, HELD):
            share = build(first_expert=first)[1]
            block = jax.jit(afmoe.Block(share.dims, layer).apply)
            total = total + block({"params": cut(first, HELD)}, h)[0] - alike
    assert float(jnp.abs(uncut - alike).max()) > 0.1  # the experts did something
    assert float(jnp.abs(total - uncut).max()) < 1e-4 * float(jnp.abs(uncut).max())


# -- the model against the reference -------------------------------------------


@pytest.fixture(scope="module")
def toy():
    """The toy model holding experts 0-3, its weights, two rows of tokens, its
    logits and the reference's, computed once for the tests below."""
    cfg, model, dims = build()
    params, x = init_params(model), tokens(1)
    got, inter = jax.jit(lambda p, x: model.apply(
        {"params": p}, x, mutable=["intermediates"]))(params, x)
    return dict(cfg=cfg, model=model, dims=dims, params=params, x=x,
                logits=got, intermediates=inter,
                want=ref_logits(params, x, dims))


def test_the_tree_holds_what_the_type_has_and_nothing_else(toy):
    params, model = toy["params"], toy["model"]
    assert sorted(params) == ["embed", "final_norm", "layer_0", "layer_1",
                              "lm_head"]
    for i, kind in enumerate(KINDS):
        layer = params[f"layer_{i}"]
        mixer = "linear_attn" if kind == LINEAR else "attn"
        assert sorted(layer) == sorted(["input_norm", mixer, "moe", "pre_mlp_norm"])
    paths = [tuple(k.key for k in path) for path, _ in
             jax.tree_util.tree_leaves_with_path(params)]
    groups = {p: group_of(p, NAMED) for p in paths}
    assert {p[-1] for p, g in groups.items() if g == "delta_gates"} == {
        "A_log", "dt_bias"}
    assert {p[1] for p, g in groups.items() if g == "delta_rule"} == {"linear_attn"}
    assert groups[("layer_1", "attn", "wg")] == "attention"
    assert groups[("layer_0", "moe", "shared_expert_gate")] == "shared"
    assert not model.mup_enabled and not model.tie_word_embeddings


def test_logits_loss_and_gradients_match_the_reference(toy):
    model, dims, params, x = toy["model"], toy["dims"], toy["params"], toy["x"]
    want_logits = toy["want"]
    assert float(jnp.abs(toy["logits"] - want_logits).max()) < 5e-5 * float(
        jnp.abs(want_logits).max())
    # the routing counter, as MoE sows it: every layer routes
    assert sorted(toy["intermediates"]["intermediates"]) == ["layer_0", "layer_1"]
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
    cosines = group_cosines(grads, want, NAMED)
    assert {"delta_rule", "delta_gates", "attention", "shared"} <= set(cosines)
    assert min(cosines.values()) > 1 - 1e-6, cosines
    assert float(jnp.abs(want["layer_0"]["linear_attn"]["A_log"]).max()) > 1e-6


def test_the_last_share_of_the_experts_matches_the_reference(toy):
    """The share that holds experts 12-15: its logits and loss against the
    reference given the same share."""
    _, model, dims = build(first_expert=EXPERTS - HELD)
    params, x = toy["params"], toy["x"]
    got = jax.jit(model.apply)({"params": params}, x)
    want = ref_logits(params, x, dims)
    assert float(jnp.abs(got - want).max()) < 5e-5 * float(jnp.abs(want).max())
    assert float(jnp.abs(got - toy["logits"]).max()) > 1e-3  # other experts


def test_one_trainer_round_matches_the_reference_round():
    """2 sites, dSGD, Adam, the device pipeline, bfloat16 compute: the
    parameters after one epoch of one round against the reference's round.
    The trainer maps the sites' gradients by ``vmap``: the expert layer folds
    it into its groups and the delta rule's scans take it as a batch axis."""
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
        grad = jax.jit(jax.value_and_grad(lambda p, x: ref.loss(p, x, dims)))
        outs = [grad(before, jnp.asarray(s.inputs[0])) for s in sites]
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


# -- the registry ----------------------------------------------------------------


@pytest.mark.parametrize("bad", [
    {"model_type": "qwen3-next"},
    {"model_type": "afmoe", "layer_types": KINDS},  # a linear layer elsewhere
    {"layer_types": (LINEAR, CONV)},  # lfm2_moe's mixer
    {"layer_types": (LINEAR, SLIDING)},  # no window in this type
    {"layer_types": (LINEAR,)},  # names one layer of two
    {"linear_num_key_heads": 0},
    {"linear_num_value_heads": 3},  # not a whole number of key heads' share
    {"num_shared_experts": 0},
    {"num_dense_layers": 1},
    {"partial_rotary_factor": 0.3},  # 9.6 of 32 dimensions
    {"model_type": "lfm2_moe", "layer_types": (CONV, FULL),
     "num_shared_experts": 0, "head_dim": 16},  # partial rotary elsewhere
    {"num_nextn_predict_layers": 1},
    {"first_expert": EXPERTS - HELD + 1},
])
def test_the_registry_refuses_what_the_type_cannot_be(bad):
    with pytest.raises(ValueError):
        build(**bad)


def test_the_layers_follow_the_period():
    """Without ``layer_types`` the published period: a full layer every
    ``global_attn_every_n_layers``-th (``full_attention_interval``), the
    linear ones before it; a list, where given, is taken as it is."""
    period = (LINEAR, LINEAR, LINEAR, FULL)
    for n in (4, 8):
        a = toy_cfg(num_hidden_layers=n, layer_types=()).lm_args
        assert afmoe_layer_types(a) == period * (n // 4)
    cfg, model, _ = build()
    assert afmoe_layer_types(cfg.lm_args) == KINDS == model.dims.layer_types
    assert QWEN3_NEXT in afmoe.MODEL_TYPES and len(afmoe.MODEL_TYPES) == 5


#: the expert layer's tree of each accepted type, as it was before ``MoE``'s
#: one flag became three (routing rule, activation, shared gate)
ACCEPTED = {
    "afmoe": dict(),
    "lfm2_moe": dict(layer_types=(CONV, FULL), num_shared_experts=0,
                     head_dim=32, num_attention_heads=2, tie_word_embeddings=True),
    "smallthinker": dict(num_shared_experts=0, rope_layout=(1, 0),
                         sliding_window_layout=(1, 0)),
    "glm4_moe_lite": dict(q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
                          qk_rope_head_dim=8, v_head_dim=16,
                          layer_types=(FULL, FULL)),
}


def _accepted_model(model_type):
    cfg = TrainConfig(task_id=NNComputation.TASK_LM).with_overrides({"lm_args": {
        **{k: v for k, v in TOY.items() if not k.startswith(("linear_", "partial_"))
           and k != "global_attn_every_n_layers"},
        "model_type": model_type, "num_hidden_layers": 2, "sliding_window": 8,
        "head_dim": 16, "layer_types": (), **ACCEPTED[model_type]}})
    model = get_task(cfg.task_id).build_model(cfg)
    return model, jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, tokens(9), train=True))["params"]


@pytest.mark.parametrize("model_type", sorted(ACCEPTED))
def test_the_accepted_types_keep_their_expert_layers(model_type):
    model, params = _accepted_model(model_type)
    moe = sorted(params["layer_1"]["moe"])
    want = ["router", "w1", "w2", "w3"]
    if model_type != "smallthinker":
        want.append("expert_bias")
    if model_type in ("afmoe", "glm4_moe_lite"):
        want.append("shared")
    assert moe == sorted(want)


@pytest.mark.parametrize("model_type", sorted(ACCEPTED))
def test_the_accepted_types_never_reach_the_delta_rule(monkeypatch, model_type):
    """The four accepted types' loss and its gradient trace with Gated
    DeltaNet, the delta rule, its norm and its kernels taken away: nothing of
    them is in those programs, so a change to them leaves the programs as
    they were."""
    def refuse(*args, **kwargs):
        raise AssertionError("reached the delta rule")

    for name in ("GatedDeltaNet", "gated_delta_rule", "chunked_delta_rule",
                 "l2_normalize"):
        monkeypatch.setattr(afmoe, name, refuse)
    monkeypatch.setattr(delta_pallas, "delta_rule", refuse)
    model, params = _accepted_model(model_type)
    x = tokens(9)
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda p: model.task_loss(
        {"params": p}, x, jnp.ones(x.shape[0]))))(params))
    assert delta_pallas.DELTA_FWD not in jaxpr and delta_pallas.DELTA_BWD not in jaxpr


# -- the comparison notices a missing term ---------------------------------------


def _dropped(term: str, monkeypatch):
    """The system with one term taken away; the reference keeps them all."""
    if term == "the_convolution":  # the last tap alone: no mixing across positions
        plain = afmoe.causal_conv
        monkeypatch.setattr(afmoe, "causal_conv", lambda u, filt: plain(
            u, filt.at[:, :-1].set(0.0)))
    elif term == "the_decay":
        plain = afmoe.chunked_delta_rule
        monkeypatch.setattr(afmoe, "chunked_delta_rule", lambda q, k, v, beta, g, **kw:
                            plain(q, k, v, beta, jnp.zeros_like(g), **kw))
    elif term == "partial_rotary":  # the whole head rotated
        monkeypatch.setattr(afmoe, "partial_rotary", lambda x, pos, theta, dims:
                            afmoe.rotary(x, pos, theta))


@pytest.mark.parametrize("term", ["the_convolution", "the_decay",
                                  "partial_rotary"])
def test_the_comparison_notices_a_dropped_term(monkeypatch, toy, term):
    """``logit_rel_rms`` (benchmarks/lib/refcheck_lm.py) of the bfloat16
    system against the float32 reference stays inside the configuration's
    limit, and leaves it by far when a term goes missing."""
    params, x, want = toy["params"], toy["x"], toy["want"]

    def rel_rms(model):
        got = jax.jit(model.apply)({"params": params}, x)
        return float(jnp.sqrt(jnp.mean((got - want) ** 2) / jnp.mean(want ** 2)))

    whole = rel_rms(build(compute_dtype="bfloat16")[1])
    _dropped(term, monkeypatch)
    err = rel_rms(build(compute_dtype="bfloat16")[1])
    assert whole < CHECK["logit_rel_rms_max"] / 2, whole
    assert err > CHECK["logit_rel_rms_max"] and err > 10 * whole, (term, err, whole)
