"""The resident inventory against the TPU's own compiler (ISSUE 27).

Nothing runs and no chip is needed: the TPU compiler installed here compiles
for a DESCRIBED v5e. Held: the device's default layout for the stored
inventory is row-major (why ``stored_sample_shape`` pads 98 windows to 104),
and the dSGD epoch program at HCP widths consumes the inventory argument as it
arrives (no copy of it), gathers whole stored rows, and passes neither a
select nor a copy over the gathered batch.

The topology is described inside a fixture, never at import, and every test
that needs it lives in this one file: only one process may hold libtpu.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dinunet_implementations_tpu.data.api import (
    merged_sample_shape,
    stored_sample_shape,
)

HCP_SAMPLE = (98, 100, 10)  # 98 windows of 100 components x 10 timepoints
SITES, ROWS, BATCH, STEPS = 32, 40, 16, 2


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _parameter_layout(text: str, index: int = 0) -> tuple:
    """Minor-to-major order of entry parameter ``index`` in optimized HLO."""
    entry = text[text.index("ENTRY"):]
    m = re.search(r"= \w+\[[0-9,]*\]\{([0-9,]*)[:}][^\n]*? parameter\(%d\)"
                  % index, entry)
    assert m, "parameter not found"
    return tuple(int(d) for d in m.group(1).split(","))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_default_layout_of_the_stored_inventory_is_row_major(
        one_chip, no_compile_cache, dtype):
    """Rows padded to whole tiles: row-major, a sample contiguous. The
    sample's own 98 rows: the device tiles the SITE axis with the features,
    and a row gather would first relayout the whole inventory."""
    def layout_of(sample):
        x = jax.ShapeDtypeStruct((SITES, ROWS + 1) + sample, dtype,
                                 sharding=one_chip)
        compiled = jax.jit(
            lambda a: a.astype(jnp.float32).sum()).lower(x).compile()
        return _parameter_layout(compiled.as_text())

    assert stored_sample_shape(HCP_SAMPLE) == (104, 1000)
    assert layout_of(stored_sample_shape(HCP_SAMPLE)) == (3, 2, 1, 0)
    assert layout_of(merged_sample_shape(HCP_SAMPLE)) != (3, 2, 1, 0)


def test_epoch_program_gathers_rows_and_nothing_else(
        one_chip, no_compile_cache, monkeypatch):
    from dinunet_implementations_tpu.engines import make_engine
    from dinunet_implementations_tpu.models import ICALstm
    from dinunet_implementations_tpu.ops import lstm_pallas
    from dinunet_implementations_tpu.trainer import (
        FederatedTask,
        init_train_state,
        make_optimizer,
        make_train_epoch_fn,
    )

    # the program asks jax.default_backend() and would interpret its kernels
    monkeypatch.setattr(lstm_pallas, "_interpret", lambda: False)
    task = FederatedTask(ICALstm(
        input_size=256, hidden_size=348, num_comps=100, window_size=10,
        use_pallas=True, compute_dtype="bfloat16"))
    engine, opt = make_engine("dSGD"), make_optimizer("adam", 1e-3)
    state = jax.eval_shape(lambda: init_train_state(
        task, engine, opt, jax.random.PRNGKey(0),
        jnp.ones((BATCH,) + HCP_SAMPLE, jnp.float32), num_sites=SITES))

    def put(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    state = jax.tree.map(lambda a: put(a.shape, a.dtype), state)
    stored = stored_sample_shape(HCP_SAMPLE)
    fn = make_train_epoch_fn(task, engine, opt, None, 1, pipeline="device",
                             donate_state=True)
    text = fn.lower(
        state, put((SITES, ROWS + 1) + stored, jnp.bfloat16),
        put((SITES, ROWS + 1), jnp.int32),
        put((SITES, STEPS, BATCH), jnp.int32),
    ).compile().as_text()
    lines = text.splitlines()
    assert text.count("tpu_custom_call") >= 4  # the kernels are in it
    inv = "bf16[%d,%d,%d,%d]" % ((SITES, ROWS + 1) + stored)
    assert inv + "{3,2,1,0" in text  # the argument, row-major
    copies = [ln for ln in lines
              if re.search(r"= %s\{[^}]*\} copy\(" % re.escape(inv), ln)]
    assert not copies, copies[0][:300]
    # the gathered batch, as [sites * batch, ...] or [sites, batch, ...],
    # with or without the pad rows
    batch = r"bf16\[(%d,%d|%d),(98|104),1000\]" % (SITES, BATCH, SITES * BATCH)
    moved = [ln for ln in lines
             if re.search(r"= %s\{[^}]*\} (select|copy)\(" % batch, ln)]
    assert not moved, moved[0][:300]
    assert any(re.search(r"= %s\{[^}]*\} gather\(" % batch, ln)
               for ln in lines)


# -- the next-token model's kernels at the cell's sizes (ISSUE 28) -------------


def test_attention_kernels_compile_for_the_chip_under_the_site_fold(
        one_chip, no_compile_cache, monkeypatch):
    """The splash-attention kernels at Trinity-Mini's widths (32 query / 4
    key-value heads of 128, 8,192 positions, window 2,048 and full), in the
    blocks ``afmoe.attention_blocks`` chooses there (512 under the window,
    1,024 x 1,024 under the causal mask), under the trainer's vmap over two
    sites and its gradient: three Mosaic calls a mask, named after the
    model's constants."""
    from dinunet_implementations_tpu.models import afmoe

    monkeypatch.setattr(afmoe, "_interpret", lambda: False)
    afmoe._splash.cache_clear()
    t, n, g, d = 8192, 32, 4, 128

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    try:
        for window, edge in ((2048, 512), (None, 1024)):
            sizes = afmoe.attention_blocks(t, n // g, d, window, jnp.bfloat16)
            assert (sizes.block_q, sizes.block_kv_dq, sizes.block_q_dkv) == (edge,) * 3

            def loss(q, k, v):
                return afmoe.kernel_attention(q, k, v, window).sum()

            text = jax.jit(jax.vmap(jax.grad(loss, argnums=(0, 1, 2)))).lower(
                sds(2, 1, t, n, d), sds(2, 1, t, g, d), sds(2, 1, t, g, d)
            ).compile().as_text()
            for name in (afmoe.ATTN_FWD, afmoe.ATTN_DQ, afmoe.ATTN_DKV):
                assert re.search(r"%[\w.]*" + name + r"[\w.]* = .*tpu_custom_call",
                                 text), name
    finally:
        afmoe._splash.cache_clear()


def test_attention_kernels_compile_at_latent_attentions_shape(
        one_chip, no_compile_cache, monkeypatch):
    """The same kernels as GLM-4.7-Flash's latent attention calls them (ISSUE
    32): 20 key-value heads with ONE query head each at head width 256, full
    causal attention over 8,192 positions in the blocks
    ``afmoe.attention_blocks`` chooses there (1,024 x 1,024, the largest the
    16 MiB scope takes at this width), under the trainer's vmap over two
    sites and its gradient."""
    from dinunet_implementations_tpu.models import afmoe

    monkeypatch.setattr(afmoe, "_interpret", lambda: False)
    afmoe._splash.cache_clear()
    t, n, d = 8192, 20, 256
    x = jax.ShapeDtypeStruct((2, 1, t, n, d), jnp.bfloat16, sharding=one_chip)
    sizes = afmoe.attention_blocks(t, 1, d, None, jnp.bfloat16)
    assert (sizes.block_q, sizes.block_kv_dq, sizes.block_q_dkv) == (1024,) * 3
    try:
        text = jax.jit(jax.vmap(jax.grad(
            lambda q, k, v: afmoe.kernel_attention(q, k, v, None).sum(),
            argnums=(0, 1, 2)))).lower(x, x, x).compile().as_text()
        for name in (afmoe.ATTN_FWD, afmoe.ATTN_DQ, afmoe.ATTN_DKV):
            assert re.search(r"%[\w.]*" + name + r"[\w.]* = .*tpu_custom_call",
                             text), name
    finally:
        afmoe._splash.cache_clear()


def test_attention_kernels_compile_at_smallthinkers_shapes(
        one_chip, no_compile_cache, monkeypatch):
    """The same kernels as the smallthinker block calls them (ISSUE 34): seven
    query heads a key-value head at width 128 over 16,384 positions, a 4,096
    window and full causal, in the blocks ``afmoe.attention_blocks`` chooses
    there (1,024 x 1,024 under both masks: a quarter of the window), under
    the trainer's vmap over two sites and its gradient."""
    from dinunet_implementations_tpu.models import afmoe

    monkeypatch.setattr(afmoe, "_interpret", lambda: False)
    afmoe._splash.cache_clear()
    t, n, g, d = 16384, 28, 4, 128

    def sds(heads):
        return jax.ShapeDtypeStruct((2, 1, t, heads, d), jnp.bfloat16,
                                    sharding=one_chip)

    try:
        for window in (4096, None):
            sizes = afmoe.attention_blocks(t, n // g, d, window, jnp.bfloat16)
            assert (sizes.block_q, sizes.block_kv_dq, sizes.block_q_dkv) == (1024,) * 3
            text = jax.jit(jax.vmap(jax.grad(
                lambda q, k, v: afmoe.kernel_attention(q, k, v, window).sum(),
                argnums=(0, 1, 2)))).lower(sds(n), sds(g), sds(g)).compile().as_text()
            for name in (afmoe.ATTN_FWD, afmoe.ATTN_DQ, afmoe.ATTN_DKV):
                assert re.search(r"%[\w.]*" + name + r"[\w.]* = .*tpu_custom_call",
                                 text), name
    finally:
        afmoe._splash.cache_clear()


def test_attention_kernels_compile_at_lfm2s_half_tile_heads(
        one_chip, no_compile_cache, monkeypatch):
    """The same kernels as the lfm2_moe block's one attention layer calls them
    (ISSUE 38): four query heads a key-value head at head width 64, HALF a
    lane tile, causal over 8,192 positions in the blocks
    ``afmoe.attention_blocks`` chooses there (1,024 x 1,024), under the
    trainer's vmap over two sites and its gradient. All three are Mosaic
    calls on 64-wide operands: nothing is padded on the way in."""
    from dinunet_implementations_tpu.models import afmoe

    monkeypatch.setattr(afmoe, "_interpret", lambda: False)
    afmoe._splash.cache_clear()
    t, n, g, d = 8192, 32, 8, 64

    def sds(heads):
        return jax.ShapeDtypeStruct((2, 1, t, heads, d), jnp.bfloat16,
                                    sharding=one_chip)

    sizes = afmoe.attention_blocks(t, n // g, d, None, jnp.bfloat16)
    assert (sizes.block_q, sizes.block_kv_dq, sizes.block_q_dkv) == (1024,) * 3
    try:
        text = jax.jit(jax.vmap(jax.grad(
            lambda q, k, v: afmoe.kernel_attention(q, k, v, None).sum(),
            argnums=(0, 1, 2)))).lower(sds(n), sds(g), sds(g)).compile().as_text()
        for name in (afmoe.ATTN_FWD, afmoe.ATTN_DQ, afmoe.ATTN_DKV):
            call = re.search(r"%[\w.]*" + name + r"[\w.]* = .*tpu_custom_call",
                             text)
            assert call, name
            assert re.search(r"bf16\[2,8,(4,)?8192,64\]", call.group(0)), name
    finally:
        afmoe._splash.cache_clear()


def _attention_layer_text(one_chip, monkeypatch, t, hidden, heads, window,
                          gated, rope=True) -> str:
    """Compiled text of one ``afmoe.Attention`` layer, forward and backward,
    under a block's checkpoint and the trainer's fold of two sites, with the
    kernel paths steered on as they are on the chip."""
    from dinunet_implementations_tpu.models import afmoe
    from dinunet_implementations_tpu.ops import rope_pallas

    monkeypatch.setattr(afmoe, "_auto_pallas", lambda: True)
    monkeypatch.setattr(afmoe, "_interpret", lambda: False)
    monkeypatch.setattr(rope_pallas, "_interpret", lambda: False)
    if not rope:
        monkeypatch.setattr(rope_pallas, "rope_block", lambda *a: None)
    afmoe._splash.cache_clear()
    n, g, d = heads
    layer = afmoe.Attention(n, g, d, window, 1e4, 1e-5, 512, 2048,
                            compute_dtype="bfloat16", qk_norm=gated, gate=gated)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 128, hidden)))

    def put(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def loss(p, a):
        run = jax.checkpoint(layer.apply, policy=afmoe.BLOCK_KEEPS)
        return jnp.sum(jax.vmap(run, in_axes=(None, 0))(p, a))

    try:
        return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            jax.tree.map(put, params),
            put(jnp.zeros((2, 1, t, hidden), jnp.float32))).compile().as_text()
    finally:
        afmoe._splash.cache_clear()


def _plain_copy_bytes(text: str) -> int:
    """Bytes the program's plain ``copy`` instructions write (the metric
    ``layout_copy_ms_per_round`` sums their time)."""
    sizes = {"f32": 4, "bf16": 2, "s32": 4}
    total = 0
    for dtype, dims in re.findall(
            r"^ +%copy(?:\.\d+)? = (\w+)\[([\d,]*)\]", text, re.M):
        count = 1
        for n in dims.split(","):
            count *= int(n)
        total += count * sizes[dtype]
    return total


@pytest.mark.parametrize("cell,t,hidden,heads,window,gated", [
    ("smallthinker-21b-ep8", 16384, 2560, (28, 4, 128), 4096, False),
    ("trinity-mini-ep16", 8192, 2048, (32, 4, 128), 2048, True),
], ids=["smallthinker", "trinity"])
def test_a_rotary_layer_hands_over_through_one_kernel_each_way(
        one_chip, no_compile_cache, monkeypatch, cell, t, hidden, heads,
        window, gated):
    """A sliding layer at the two cells' shapes (ISSUE 37): between the
    projections and ``splash_mqa_*`` stand the new kernels' calls and nothing
    else — the forward call's operands ARE the projections' own outputs,
    float32 and row-major; no fusion named ``slice_negate*`` (XLA's rotary);
    one forward call in the forward pass, one in the recomputation, one
    transposed call, whose bfloat16 cotangents the projections' backward
    matmuls read as they are; and the plain copies write no more bytes than
    the full layer's beside it (which has no positions and never took the
    new path: its text holds neither kernel, with or without it)."""
    from dinunet_implementations_tpu.ops import rope_pallas

    n, g, d = heads
    text = _attention_layer_text(one_chip, monkeypatch, t, hidden, heads,
                                 window, gated)
    assert "slice_negate" not in text
    fwd = re.findall(r"^ +%[\w.]*rope_fwd[\w.]* = .*custom-call\(%([\w.\-]+), "
                     r"%([\w.\-]+),.*tpu_custom_call", text, re.M)
    bwd = re.findall(r"^ +%%([\w.]*rope_bwd[\w.]*) = \(bf16\[2,%d,%d\]\S*, "
                     r"bf16\[2,%d,%d\].*tpu_custom_call"
                     % (t, n * d, t, g * d), text, re.M)
    assert len(fwd) == 2 and len(bwd) == 1, (fwd, bwd)
    for q_operand, _ in fwd:  # written by the projection's own fusion
        assert re.search(r"^ +%%%s = f32\[2,%d,%d\]\{2,1,0[:}].* fusion\("
                         % (re.escape(q_operand), t, n * d), text, re.M), q_operand
    for name in ("splash_mqa_fwd", "splash_mqa_dq", "splash_mqa_dkv"):
        assert len(re.findall(r"^ +%%[\w.]*%s[\w.]* = .*tpu_custom_call" % name,
                              text, re.M)) == 1, name
    assert not re.search(r"= f32\[2,(1,)?%d,(%d|%d,%d)\]\S* copy\("
                         % (t, n * d, n, d), text)  # the queries, whole, float32
    full = _attention_layer_text(one_chip, monkeypatch, t, hidden, heads, None,
                                 gated)
    assert not any(k in full for k in rope_pallas.KERNEL_NAMES + ("slice_negate",))
    assert _plain_copy_bytes(text) <= _plain_copy_bytes(full)
    old = _attention_layer_text(one_chip, monkeypatch, t, hidden, heads, window,
                                gated, rope=False)
    assert "slice_negate" in old  # the witness, where rotary() stays
    assert _plain_copy_bytes(old) > 3 * _plain_copy_bytes(text)


def test_grouped_products_lower_to_the_compilers_kernel(one_chip,
                                                         no_compile_cache):
    """``jax.lax.ragged_dot`` (rows by group) and its row-contracting form
    (the stacks' cotangent) become the compiler's own ragged-dot kernels, whose
    work follows the group sizes; the batched form has no lowering on the
    chip, which is why the expert layer folds the sites into the groups."""
    from dinunet_implementations_tpu.models import afmoe

    rows, h, f, e = 8192, 2048, 1024, 8

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    by_rows = jax.jit(lambda x, w, s: jax.lax.ragged_dot(
        x, w, s, preferred_element_type=jnp.float32))
    text = by_rows.lower(sds((rows, h)), sds((e, h, f)),
                         sds((e,), jnp.int32)).compile().as_text()
    assert re.search(r"%ragged-dot[\w.-]* = .*tpu_custom_call", text)
    contracting = jax.jit(lambda x, y, s: jax.lax.ragged_dot_general(
        x, y, s, afmoe._CONTRACT_ROWS, preferred_element_type=jnp.float32))
    text = contracting.lower(sds((rows, h)), sds((rows, f)),
                             sds((2 * e,), jnp.int32)).compile().as_text()
    assert re.search(r"%ragged-dot[\w.-]* = .*tpu_custom_call", text)
    with pytest.raises(Exception, match="batch dimensions"):
        jax.jit(jax.vmap(lambda x, w, s: jax.lax.ragged_dot(x, w, s))).lower(
            sds((2, rows, h)), sds((2, e, h, f)), sds((2, e), jnp.int32)
        ).compile()


def test_experts_backward_adds_into_a_window_of_the_stacks(one_chip,
                                                          no_compile_cache):
    """The expert layer's backward pass at ``smallthinker-21b-ep8.dsgd-fold2-
    long``'s shapes (2 folds x 16,384 tokens, top-6, 8 held ReGLU experts of
    2,560 x 768): the chunk's stack gradients have a grouped product of the
    WINDOW's shape (two experts' four groups) beside the whole stacks' sixteen,
    no whole stack is ever copied (the window's update is in place in the
    carried stack), and the program's temporaries are no more than they were
    before the window (1.759 GiB, ISSUE 35)."""
    from dinunet_implementations_tpu.models import afmoe

    folds, t, h, k, held, f = 2, 16384, 2560, 6, 8, 768
    groups, window = folds * held, afmoe.WINDOW_EXPERTS * folds

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(lambda m, sel, w, w1, w3, w2, dy: afmoe._experts_backward(
        m, sel, w, w1, w3, w2, dy, 0, jnp.bfloat16, True)).lower(
        sds((folds, t, h)), sds((folds, t, k), jnp.int32), sds((folds, t, k)),
        sds((held, h, f)), sds((held, h, f)), sds((held, f, h)),
        sds((folds, t, h))).compile()
    text = compiled.as_text()
    for rows, cols in ((h, f), (f, h)):
        for n in (window, groups):
            assert re.search(r"%%ragged-dot[\w.-]* = f32\[%d,%d,%d\]"
                             % (n, rows, cols), text), (n, rows, cols)
        assert not re.search(r"= f32\[%d,%d,%d\]\S* copy\(" % (groups, rows, cols),
                             text)
    assert compiled.memory_analysis().temp_size_in_bytes <= 1.759 * 2 ** 30


def test_experts_backward_has_a_window_of_each_rung(one_chip, no_compile_cache):
    """The same at ``lfm2-8b-a1b-ep4.dsgd-fold2``'s shapes (2 folds x 8,192
    tokens, top-4, 8 held SwiGLU experts of 2,048 x 1,792): a grouped
    product of every rung's shape (two to seven experts' groups) beside the
    whole stacks' sixteen, no whole stack copied, and temporaries no more
    than the parent's single window left (1,454,147,072 bytes, ISSUE 39)."""
    from dinunet_implementations_tpu.models import afmoe

    folds, t, h, k, held, f = 2, 8192, 2048, 4, 8, 1792
    groups = folds * held

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(lambda m, sel, w, w1, w3, w2, dy: afmoe._experts_backward(
        m, sel, w, w1, w3, w2, dy, 0, jnp.bfloat16)).lower(
        sds((folds, t, h)), sds((folds, t, k), jnp.int32), sds((folds, t, k)),
        sds((held, h, f)), sds((held, h, f)), sds((held, f, h)),
        sds((folds, t, h))).compile()
    text = compiled.as_text()
    rungs = afmoe.window_rungs(folds, held)
    assert rungs == (4, 6, 8, 10, 12, 14)
    for rows, cols in ((h, f), (f, h)):
        for n in rungs + (groups,):
            assert re.search(r"%%ragged-dot[\w.-]* = f32\[%d,%d,%d\]"
                             % (n, rows, cols), text), (n, rows, cols)
        assert not re.search(r"= f32\[%d,%d,%d\]\S* copy\(" % (groups, rows, cols),
                             text)
    assert compiled.memory_analysis().temp_size_in_bytes <= 1_454_147_072


# -- the LSTM kernels' blocks against the chip's own limits (ISSUE 31) ---------


@pytest.mark.parametrize(
    "T,rows,dtype",
    [
        (98, 512, jnp.bfloat16),  # the benchmark's cells
        (97, 512, jnp.bfloat16),  # a tail: one step past T in the last block
        (98, 384, jnp.bfloat16),  # three row granules in one forward tile
        (49, 16, jnp.bfloat16),  # a ring chunk of one site's rows
        (98, 512, jnp.float32),  # float32 streams: twice the bytes a block
        (98, 3, jnp.float32),  # rows that are no whole sublane tile
    ],
    ids=["cell", "tail", "three-granules", "ring-chunk", "float32", "odd-rows"],
)
def test_lstm_kernels_compile_with_the_block_lstm_block_chooses(
        one_chip, no_compile_cache, monkeypatch, T, rows, dtype):
    """What interpret mode cannot show: the chosen ``(Tb, R)`` fits the 16 MiB
    the compiler scopes a kernel to (no ``vmem_limit_bytes`` is asked for) and
    Mosaic takes the partial last time block and the one-timestep halo."""
    from dinunet_implementations_tpu.ops import lstm_pallas

    monkeypatch.setattr(lstm_pallas, "_interpret", lambda: False)
    D, H = 256, 174
    cdt = None if dtype == jnp.float32 else dtype
    f32 = jnp.float32

    def sds(shape, dt=f32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    fwd = jax.jit(lambda *a: lstm_pallas._fwd_fused_call(*a, cdt)).lower(
        sds((T, rows, D), dtype), sds((4, D, H)), sds((4, H)), sds((4, H, H)),
        sds((rows, H)), sds((rows, H))).compile().as_text()
    assert re.search(r"%[\w.]*lstm_fwd[\w.]* = .*tpu_custom_call", fwd)
    s, c = sds((T, rows, H), dtype), sds((rows, H))
    bwd = jax.jit(
        lambda i, f, o, g, cs, w, c0, dhs, dhT, dcT: lstm_pallas._bwd_call(
            (i, f, o, g), cs, w, c0, dhs, dhT, dcT, cdt)
    ).lower(s, s, s, s, s, sds((4, H, H)), c, s, c, c).compile().as_text()
    assert re.search(r"%[\w.]*lstm_bwd[\w.]* = .*tpu_custom_call", bwd)


# -- the gated delta rule's kernels at the Qwen3-Next cell's shapes ------------


def test_the_delta_rule_runs_as_one_kernel_each_way(one_chip, no_compile_cache,
                                                    monkeypatch):
    """One Gated DeltaNet layer of ``qwen3-next-80b-a3b-ep32.dsgd-fold2``
    (2 sites under the site ``vmap``, T 8,192, 16 key heads and 32 value
    heads of 128), forward and backward under the block's checkpoint, with
    the kernel path steered on as it is on the chip: both kernels compile
    (their blocks fit the 16 MiB the compiler scopes a kernel to), the rule
    is Mosaic calls named ``delta_fwd`` / ``delta_bwd`` and no ``while``
    over chunks, and the compiler's plan for the layer is below the XLA
    scan's (4,141,142,016 bytes, the same layer before the kernels)."""
    from dinunet_implementations_tpu.models import afmoe
    from dinunet_implementations_tpu.ops import delta_pallas

    monkeypatch.setattr(afmoe, "_auto_pallas", lambda: True)
    monkeypatch.setattr(delta_pallas, "_interpret", lambda: False)
    layer = afmoe.GatedDeltaNet(16, 32, 128, 128, 4, 1e-6,
                                compute_dtype="bfloat16")
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 128, 2048)))

    def put(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def loss(p, a):
        run = jax.checkpoint(layer.apply, policy=afmoe.BLOCK_KEEPS)
        return jnp.sum(jax.vmap(run, in_axes=(None, 0))(p, a))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        jax.tree.map(put, params),
        put(jnp.zeros((2, 1, 8192, 2048), jnp.float32))).compile()
    text = compiled.as_text()
    for name in delta_pallas.KERNEL_NAMES:
        assert re.search(r"^ +%%[\w.]*%s[\w.]* = .*tpu_custom_call" % name,
                         text, re.M), name
    assert not re.search(r"^ +%[\w.\-]+ = .* while\(", text, re.M)
    assert compiled.memory_analysis().peak_memory_in_bytes < 4_141_142_016
