"""Mesh + collectives tests on the 8-device virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from dinunet_implementations_tpu.parallel import (
    SITE_AXIS,
    host_mesh,
    make_site_mesh,
    payload_cast,
    payload_uncast,
    site_mean,
    site_sum,
    site_weighted_mean,
)


def test_device_count():
    assert len(jax.devices()) == 8


def test_make_site_mesh_shapes():
    mesh = host_mesh(8)
    assert mesh.shape[SITE_AXIS] == 8
    mesh2 = make_site_mesh(4, model_axis_size=2)
    assert mesh2.shape[SITE_AXIS] == 4
    assert mesh2.shape["model"] == 2
    with pytest.raises(ValueError):
        make_site_mesh(16)


def _run_sharded(mesh, fn, x, in_spec=P(SITE_AXIS), out_spec=P(SITE_AXIS)):
    return shard_map(fn, mesh=mesh, in_specs=in_spec, out_specs=out_spec)(x)


def test_site_sum_and_mean():
    mesh = host_mesh(8)
    x = jnp.arange(8.0).reshape(8, 1)
    out = _run_sharded(mesh, lambda v: site_sum({"g": v})["g"], x)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 28.0))
    out = _run_sharded(mesh, lambda v: site_mean({"g": v})["g"], x)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 3.5))


def test_site_weighted_mean_matches_pooled():
    """Weighted site mean == pooled mean over all examples (dSGD invariant)."""
    mesh = host_mesh(4)
    rng = np.random.default_rng(0)
    # 4 sites with heterogeneous example counts (like FS fixture 73-120 subjects)
    counts = np.array([3.0, 5.0, 2.0, 7.0])
    grads = rng.normal(size=(4, 6)).astype(np.float32)  # per-site mean gradient
    pooled = (grads * counts[:, None]).sum(0) / counts.sum()

    def fn(g, w):
        return site_weighted_mean({"g": g}, w[0])["g"]

    out = shard_map(fn, mesh=mesh, in_specs=(P(SITE_AXIS), P(SITE_AXIS)), out_specs=P(SITE_AXIS))(
        jnp.asarray(grads), jnp.asarray(counts)
    )
    for i in range(4):
        np.testing.assert_allclose(np.asarray(out)[i], pooled, rtol=1e-5)


def test_payload_cast_roundtrip():
    tree = {"w": jnp.ones((4, 4), jnp.float32)}
    cast = payload_cast(tree, "16")
    assert cast["w"].dtype == jnp.bfloat16
    back = payload_uncast(cast, tree)
    assert back["w"].dtype == jnp.float32
    same = payload_cast(tree, "32")
    assert same["w"].dtype == jnp.float32
    # compat mode: the reference's literal IEEE fp16 payload
    # (compspec.json:161-176) — "16" is bf16 on TPU, "16-ieee" opts into fp16
    ieee = payload_cast(tree, "16-ieee")
    assert ieee["w"].dtype == jnp.float16


def test_weighted_mean_accumulates_fp32():
    """Review finding: bf16 payloads must still reduce in fp32."""
    mesh = host_mesh(4)
    g = jnp.array([300.0, 0.5, 0.5, 0.5], jnp.bfloat16).reshape(4, 1)
    w = jnp.ones((4,))
    out = shard_map(
        lambda gv, wv: site_weighted_mean({"g": gv}, wv[0])["g"],
        mesh=mesh, in_specs=(P(SITE_AXIS), P(SITE_AXIS)), out_specs=P(SITE_AXIS),
    )(g, w)
    assert out.dtype == jnp.bfloat16
    # true mean 75.375; bf16(75.375)=75.5 but naive bf16 accumulation drifts to 75.0
    np.testing.assert_allclose(np.asarray(out, np.float32), 75.5)


# ---------------------------------------------------------------------------
# wire codecs (r14 — parallel/collectives.py WireCodec)
# ---------------------------------------------------------------------------


def test_wire_codec_none_is_legacy_roundtrip():
    from dinunet_implementations_tpu.parallel.collectives import (
        resolve_wire_codec,
        wire_compress,
    )

    x = jnp.linspace(-2.0, 2.0, 32)
    for bits in ("32", "16", "16-ieee"):
        c = resolve_wire_codec(bits, "none")
        np.testing.assert_array_equal(
            np.asarray(c.compress(x)), np.asarray(wire_compress(x, c.dtype))
        )


def test_wire_codec_int8_error_bound_and_grid():
    """Scale-per-payload symmetric int8: relative error bounded by half a
    grid step of the payload's amax, grid values round-trip exactly."""
    from dinunet_implementations_tpu.parallel.collectives import (
        resolve_wire_codec,
    )

    c = resolve_wire_codec("32", "int8")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(scale=3e-4, size=(64, 32)).astype(np.float32))
    y = c.compress(x)
    amax = float(jnp.abs(x).max())
    assert float(jnp.abs(y - x).max()) <= 0.5 * amax / 127 + 1e-12
    # exact grid points survive the round trip bit-for-bit
    grid = jnp.asarray([0.0, 127.0, -127.0, 64.0])
    np.testing.assert_array_equal(np.asarray(c.compress(grid)),
                                  np.asarray(grid))


def test_wire_codec_fp8_scales_small_gradients():
    """Raw-cast fp8 flushes ~1e-4 gradients to zero; the scale-per-payload
    codec must preserve them to e4m3 relative precision (~6%)."""
    from dinunet_implementations_tpu.parallel.collectives import (
        resolve_wire_codec,
    )

    c = resolve_wire_codec("32", "fp8")
    x = jnp.asarray(
        np.random.default_rng(1).normal(scale=1e-4, size=(128,))
        .astype(np.float32)
    )
    y = c.compress(x)
    assert float(jnp.abs(y).max()) > 0
    rel = float(jnp.abs(y - x).max() / jnp.abs(x).max())
    assert rel < 0.07, rel
    # a raw cast (no scaling) really does lose these values — the scale is
    # doing the work
    raw = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    assert float(jnp.abs(raw).max()) == 0.0


def test_wire_codec_zero_and_batched_scales():
    from dinunet_implementations_tpu.parallel.collectives import (
        resolve_wire_codec,
    )

    c = resolve_wire_codec("32", "int8")
    # an all-zero (dead-site-masked) payload stays exactly zero, no NaN
    z = c.compress(jnp.zeros((4, 4)))
    np.testing.assert_array_equal(np.asarray(z), 0.0)
    # batched=True: one scale per leading (virtual-site) row — rows at
    # wildly different magnitudes each keep their own relative precision
    rows = jnp.stack([
        jnp.linspace(-1e-4, 1e-4, 16), jnp.linspace(-1e3, 1e3, 16)
    ])
    y = c.compress(rows, batched=True)
    for i in range(2):
        rel = float(jnp.abs(y[i] - rows[i]).max() / jnp.abs(rows[i]).max())
        assert rel <= 0.5 / 127 + 1e-9, (i, rel)


def test_wire_codec_stochastic_rounding_unbiased():
    """Stochastic int8 rounding: deterministic (value-hashed dither) yet
    unbiased in expectation — the mean quantization error over many values
    must be far below half a grid step (RNE on a one-sided distribution
    would not be)."""
    from dinunet_implementations_tpu.parallel.collectives import (
        resolve_wire_codec,
    )

    sr = resolve_wire_codec("32", "int8", stochastic=True)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.uniform(-1.0, 1.0, size=(200_000,))
                    .astype(np.float32))
    y = sr.compress(x)
    step = 1.0 / 127
    assert abs(float(jnp.mean(y - x))) < 0.02 * step
    # deterministic: same input, same output
    np.testing.assert_array_equal(np.asarray(sr.compress(x)), np.asarray(y))
    # stochastic only applies to int8
    assert resolve_wire_codec("32", "fp8", stochastic=True).stochastic is False


def test_two_level_psum_accepts_codec():
    """The packed partial re-quantizes through the codec before the
    cross-device hop — values equal the codec round-trip of the local sum."""
    from dinunet_implementations_tpu.parallel.collectives import (
        PackedAxis,
        resolve_wire_codec,
        two_level_psum,
    )

    c = resolve_wire_codec("32", "int8")
    x = jnp.asarray(
        np.random.default_rng(3).normal(size=(4, 8)).astype(np.float32)
    )
    out = two_level_psum(x, PackedAxis(None, 4), wire_dtype=c)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(c.compress(jnp.sum(x, axis=0)))
    )


def test_wire_codec_rejects_unknown_quant():
    from dinunet_implementations_tpu.parallel.collectives import (
        resolve_wire_codec,
    )

    with pytest.raises(ValueError, match="wire_quant"):
        resolve_wire_codec("32", "int4")


def test_quantized_engines_approximate_f32_aggregate():
    """dSGD/rankDAD/powerSGD under int8 and fp8 wires: the aggregate stays
    within the codec's error envelope of the f32 aggregate — quantization
    compresses the wire, it does not change the math."""
    from dinunet_implementations_tpu.engines import make_engine

    rng = np.random.default_rng(4)
    S = 3
    grads = {
        "k": jnp.asarray(rng.normal(size=(S, 6, 4)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(S, 4)).astype(np.float32)),
    }
    row = jax.tree.map(lambda g: g[0], grads)
    w = jnp.ones((S,))

    def run(eng):
        st = jax.tree.map(lambda a: jnp.stack([a] * S), eng.init(row))
        agg, _ = jax.vmap(
            lambda g, s, ww: eng.aggregate(g, s, ww, "site"),
            axis_name="site",
        )(grads, st, w)
        return agg

    for name in ("dSGD", "rankDAD", "powerSGD"):
        ref = run(make_engine(name, dad_reduction_rank=2))
        for quant, tol in (("int8", 0.02), ("fp8", 0.1)):
            got = run(make_engine(name, dad_reduction_rank=2,
                                  wire_quant=quant))
            for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
                err = float(jnp.abs(a - b).max())
                assert err < tol, (name, quant, err)
