"""Cross-site collectives — the aggregation transport.

The reference ships JSON-serialized gradients from every site container to the
remote container, which reduces them on an ``mp.Pool`` of ``num_reducers``
processes and broadcasts the result back (reference ``local.py:26-27,49``,
``remote.py:20-21,37``; payloads optionally cast to fp16 via ``precision_bits``,
``compspec.json:161-176``). Here each of those becomes a single XLA collective
over the ``site`` mesh axis: reduction rides ICI, the "broadcast back" is simply
the collective's replicated result. ~97% of reference wall-clock was this
transport (SURVEY.md §3.1); these primitives delete that cost class.

All functions are designed for use *inside* ``shard_map``/``pjit`` with a bound
axis name.

Axis forms (r12 — site packing). ``axis_name`` may be:

- a ``str`` mesh/vmap axis name — the classic one-site-per-collective-member
  form (one site per device, or all sites vmapped onto one device);
- a ``(mesh_axis, vmap_axis)`` tuple — the legacy folded form, kept for
  compatibility: collectives resolve the vmapped half through jax's batching
  rules, which ships the whole ``[K, ...]`` batched block over the mesh axis
  (K× wire inflation — the reason PackedAxis exists);
- a :class:`PackedAxis` — the packed two-level form: every payload leaf
  carries a LEADING ``[K]`` virtual-site axis, reductions run **local
  in-register sum over the packed axis first**, the partial is (optionally)
  quantized to the wire dtype, and ONE cross-device collective ships the
  unbatched partial over the mesh axis. Per-device wire bytes are then
  independent of K for every psum-shaped exchange; only genuine per-site
  payloads (the low-rank factor all-gather) scale with K.

Three-tier form (r18 multi-slice, ``PackedAxis.slice_name`` set): the mesh
carries an OUTER ``slice`` axis whose collectives cross DCN, the slow
inter-slice fabric (parallel/mesh.py ``sliced_site_mesh``). Reductions grow
a tier: the in-register pack sum (tier 0) and the intra-slice psum over ICI
(tier 1) as before, then an inter-slice hop (tier 2) that ships only the
already-reduced per-slice partial. The tier-2 payload treatment is the
``dcn_wire`` argument, independent of the intra-slice codec:

- ``dcn_wire=None`` (``dcn_wire_quant`` resolves to "none") — the FUSED
  form: tiers 1+2 are ONE collective naming ``(slice, site)`` together.
  Value-wise this is exactly the flat single-mesh reduce (same members,
  same reduction order — sliced==unsliced trajectories stay bit-exact
  site-for-site), and it is what XLA/the TPU runtime hierarchically
  decomposes over ICI+DCN on real multi-slice hardware. Bookkeeping
  reductions (losses, weight totals, sync-BN) always take this form —
  they must never be re-quantized at a slice boundary.
- ``dcn_wire=WireCodec`` — the SPLIT form: psum over ``site`` completes the
  per-slice partial, the partial re-quantizes through the DCN codec (scale
  per payload), and ONE psum naming only ``slice`` ships it across DCN.
  int8/fp8 then land their 4x shrink exactly where bandwidth is scarcest:
  the expensive hop carries one codec-grid payload per slice per round
  instead of one dense payload per device.

Gathers are always hierarchical under a sliced axis (gather over ``site``,
optionally DCN-re-quantize the per-slice block, gather over ``slice``) —
gathering is exact, so the site order and values match the flat form
bit-for-bit when no DCN codec is set.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from .mesh import SITE_AXIS


@dataclasses.dataclass(frozen=True)
class PackedAxis:
    """The packed (K-sites-per-device) site axis: payload pytree leaves carry
    a leading ``[pack]`` virtual-site axis; reductions are two-level (local
    sum over that axis, then one cross-device collective over ``name``).
    ``name=None`` means no mesh half (every virtual site on one device — the
    cross-device collective degenerates to the identity); trace-time static,
    safe to close over in jitted code.

    ``slice_name`` (r18 multi-slice) names the OUTER inter-slice mesh axis
    when the mesh has one — reductions then grow the DCN tier (module
    docstring: fused vs split forms, picked per call by ``dcn_wire``).
    ``slice_name=None`` keeps the exact legacy two-level program."""

    name: str | None  # the mesh axis (from parallel/mesh.py constants)
    pack: int  # K — virtual sites per device (the leading payload axis)
    slice_name: str | None = None  # the DCN mesh axis (sliced meshes only)

    def reduce_axes(self):
        """The axis names a FUSED (bookkeeping / dcn_wire=None) reduction
        spans: ``(slice, site)`` on the sliced form — one collective over
        both tiers, bit-identical to the flat single-mesh reduce — else
        just ``name``."""
        if self.slice_name is not None:
            return (self.slice_name, self.name)
        return self.name


def _bcast(scale, like):
    """Reshape a per-virtual-site ``[K]`` vector to broadcast against a
    ``[K, ...]``-leading payload leaf."""
    return scale.reshape(scale.shape + (1,) * (like.ndim - scale.ndim))

# precision_bits payload casting (compspec.json:161-176). On TPU, "16" means
# bfloat16 (the native 16-bit type; same byte count on the wire, wider
# exponent); "16-ieee" opts into the reference's literal IEEE fp16 payload for
# bit-level compat runs. The reduction itself always accumulates in fp32.
_PAYLOAD_DTYPES = {
    "32": jnp.float32, 32: jnp.float32,
    "16": jnp.bfloat16, 16: jnp.bfloat16,
    "16-ieee": jnp.float16,
}


def payload_dtype(precision_bits="32"):
    """Resolve the ``precision_bits`` flag to the payload dtype."""
    return _PAYLOAD_DTYPES[precision_bits]


def site_weight_scale(weight, axis_name=SITE_AXIS):
    """Per-site normalized weight ``w_s / Σ w`` with a zero-total guard (an
    all-masked round yields scale 0, keeping updates finite). Packed form:
    ``weight`` is the ``[K]`` virtual-site vector and the total spans the
    local pack AND the mesh axis; the returned scale is ``[K]``."""
    w = jnp.asarray(weight, jnp.float32)
    if isinstance(axis_name, PackedAxis):
        total = jnp.sum(w)
        if axis_name.name is not None:
            # bookkeeping reduce: spans the slice tier FUSED when present
            # (never re-quantized at a slice boundary — module docstring)
            total = jax.lax.psum(total, axis_name.reduce_axes())
    else:
        total = jax.lax.psum(w, axis_name)
    return jnp.where(total > 0, w / jnp.maximum(total, 1e-12), 0.0)


def payload_cast(tree, precision_bits="32"):
    """Cast a gradient pytree to the configured payload dtype before the
    collective — the TPU equivalent of the reference's fp16 payload compression."""
    dtype = _PAYLOAD_DTYPES[precision_bits]
    return jax.tree.map(lambda g: g.astype(dtype), tree)


def payload_uncast(tree, like):
    """Restore original dtypes after the collective."""
    return jax.tree.map(lambda g, l: g.astype(l.dtype), tree, like)


def _pack_partial(x, wire_dtype):
    """Tier 0 + intra-slice wire quantization: in-register sum over the
    leading ``[K]`` virtual-site axis, the partial optionally quantized to
    ``wire_dtype`` (plain dtype round-trip or a :class:`WireCodec`)."""
    part = jnp.sum(x, axis=0)
    if isinstance(wire_dtype, WireCodec):
        part = wire_dtype.compress(part)
    elif wire_dtype is not None:
        part = wire_compress(part, wire_dtype)
    return part


def _dcn_hop(partial, axes: PackedAxis, dcn_wire):
    """Tier 2 (the SPLIT form): re-quantize the completed per-slice partial
    through the DCN codec and ship it in ONE psum naming only the slice
    axis — the only collective form that crosses DCN alone, which is what
    checks/semantic.py's DCN-tier rules key on."""
    return jax.lax.psum(dcn_wire.compress(partial), axes.slice_name)


def three_level_psum(x, axes: PackedAxis, wire_dtype=None, dcn_wire=None,
                     slice_live=None):
    """The hierarchical reduction primitive (module docstring): tier 0 is
    the in-register pack sum, tier 1 the intra-slice psum of the UNBATCHED
    partial (quantized to ``wire_dtype`` — what the device ships over ICI;
    f32 accumulation resumes after the collective), tier 2 the inter-slice
    DCN hop. With ``axes.slice_name=None`` this IS the legacy two-level
    reduction, op for op. With a slice axis, ``dcn_wire=None`` fuses tiers
    1+2 into one ``(slice, site)`` collective (bit-identical values to the
    flat reduce); a :class:`WireCodec` splits them, re-quantizing the
    per-slice partial before the slice-only psum. The ICI wire cost is
    K-independent and the DCN hop ships one partial per slice per round.

    ``slice_live`` (r19 slice elasticity) is this member's OWN slice's
    per-round liveness gate — a traced 0/1 scalar. The local partial is
    zeroed before any cross-member tier, so a dead slice contributes
    EXACTLY nothing to the DCN reduce and the surviving slices' sum equals
    the reduce that excluded the dead slice's members outright (``×1.0`` is
    bit-exact, ``×0`` is exclusion). The epoch's production rounds route
    slice death through the site-level contribute gate (trainer/steps.py —
    value-equivalent, proven by tests/test_multislice.py); this explicit
    form is the primitive-level contract the slice-fault unit tests pin."""
    part = _pack_partial(x, wire_dtype)
    if slice_live is not None and axes.slice_name is not None:
        part = part * slice_live
    if axes.name is None:
        return part
    if axes.slice_name is None:
        return jax.lax.psum(part, axes.name)
    if dcn_wire is None:
        return jax.lax.psum(part, axes.reduce_axes())
    return _dcn_hop(jax.lax.psum(part, axes.name), axes, dcn_wire)


def two_level_psum(x, axes: PackedAxis, wire_dtype=None, dcn_wire=None):
    """The r12 name for :func:`three_level_psum` — kept because every packed
    call site reads naturally as "two-level" on single-slice meshes, where
    the lowering is unchanged op for op; sliced axes route the same call
    through the DCN tier."""
    return three_level_psum(x, axes, wire_dtype, dcn_wire)


def weighted_site_sum(g, scale, axis_name, wire_dtype=None, dcn_wire=None,
                      slice_live=None):
    """One dense payload leaf of a weighted exchange: ``Σ_s scale_s · g_s``
    accumulated in f32. Classic axes psum the per-site scaled value; a
    :class:`PackedAxis` takes the two-level route (``scale`` is then the
    ``[K]`` vector and ``g`` carries the leading pack axis), growing the
    DCN tier on sliced axes (``dcn_wire`` — :func:`three_level_psum`).
    ``wire_dtype`` quantizes the packed partial only — on the classic path
    the per-member payload is whatever the caller already cast it to.
    ``slice_live`` gates this member's slice out of the reduce
    (:func:`three_level_psum` — sliced axes only)."""
    gf = g.astype(jnp.float32)
    if isinstance(axis_name, PackedAxis):
        return three_level_psum(
            gf * _bcast(scale, gf), axis_name, wire_dtype, dcn_wire,
            slice_live,
        )
    return jax.lax.psum(gf * scale, axis_name)


def weighted_tree_sum(tree, scale, axes: PackedAxis, wire_dtype=None,
                      dcn_wire=None, slice_live=None):
    """A whole pytree's weighted exchange with ONE inter-slice collective.

    Per leaf, tiers 0+1 run exactly like :func:`weighted_site_sum`; the DCN
    tier then ships the ENTIRE tree of per-slice partials in a single
    slice-only psum — every leaf DCN-re-quantized (scale per payload),
    raveled and concatenated, so the expensive hop pays one collective
    launch per round instead of one per leaf. Single-slice axes (or
    ``dcn_wire=None``) reduce per leaf exactly like the mapped
    :func:`weighted_site_sum` — same ops, so the legacy program is
    untouched. dSGD's whole dense exchange rides this (engines/dsgd.py).
    ``slice_live`` gates the per-slice partial out of the DCN reduce like
    :func:`three_level_psum` — the reduce then renormalizes over surviving
    slices only (the weights of a dead slice's members carry zero through
    ``scale``, so the denominator excludes them too)."""
    if not isinstance(axes, PackedAxis):
        return jax.tree.map(
            lambda g: weighted_site_sum(g, scale, axes, wire_dtype), tree
        )
    if axes.slice_name is None or dcn_wire is None or axes.name is None:
        return jax.tree.map(
            lambda g: weighted_site_sum(
                g, scale, axes, wire_dtype, dcn_wire, slice_live
            ),
            tree,
        )
    partials = jax.tree.map(
        lambda g: jax.lax.psum(
            _pack_partial(
                g.astype(jnp.float32) * _bcast(scale, g), wire_dtype
            ),
            axes.name,
        ),
        tree,
    )
    if slice_live is not None:
        partials = jax.tree.map(lambda p: p * slice_live, partials)
    leaves, treedef = jax.tree.flatten(partials)
    comp = [dcn_wire.compress(leaf).reshape(-1) for leaf in leaves]
    flat = comp[0] if len(comp) == 1 else jnp.concatenate(comp)
    tot = jax.lax.psum(flat, axes.slice_name)
    outs, off = [], 0
    for leaf in leaves:
        n = leaf.size
        outs.append(tot[off:off + n].reshape(leaf.shape))
        off += n
    return jax.tree.unflatten(treedef, outs)


def site_sum(tree, axis_name=SITE_AXIS):
    """Sum a pytree across sites (the remote's reduce)."""
    if isinstance(axis_name, PackedAxis):
        return jax.tree.map(lambda g: two_level_psum(g, axis_name), tree)
    return jax.tree.map(lambda g: jax.lax.psum(g, axis_name), tree)


def site_mean(tree, axis_name=SITE_AXIS):
    """Unweighted mean across sites."""
    if isinstance(axis_name, PackedAxis):
        n = axis_name.pack
        if axis_name.name is not None:
            n = n * jax.lax.axis_size(axis_name.name)
        if axis_name.slice_name is not None:
            n = n * jax.lax.axis_size(axis_name.slice_name)
        return jax.tree.map(
            lambda g: two_level_psum(g, axis_name) / n, tree
        )
    return jax.tree.map(lambda g: jax.lax.pmean(g, axis_name), tree)


def site_weighted_mean(tree, weight, axis_name=SITE_AXIS, wire_dtype=None,
                       dcn_wire=None):
    """Example-count-weighted mean across sites.

    dSGD semantics: each site contributes its gradient weighted by how many
    examples produced it (sites hold 73–120 subjects in the FS fixture —
    heterogeneous), so the aggregate equals the pooled-data gradient. ``weight``
    is a scalar per site (e.g. this round's example count) — the ``[K]``
    vector under a :class:`PackedAxis`, where the local weighted partial is
    reduced in-register and quantized to ``wire_dtype`` before the single
    cross-device psum (the two-level form; per-device wire bytes do not scale
    with K). On a sliced axis with a DCN codec, the whole tree's per-slice
    partials ship across DCN in ONE fused slice-only collective
    (:func:`weighted_tree_sum`) — one payload per slice per round.
    """
    scale = site_weight_scale(weight, axis_name)
    # Accumulate in fp32 even for bf16 payloads; cast back only after the psum.
    agg = weighted_tree_sum(tree, scale, axis_name, wire_dtype, dcn_wire)
    return jax.tree.map(lambda a, g: a.astype(g.dtype), agg, tree)


def site_all_gather(x, axis_name=SITE_AXIS, axis: int = 0, tiled: bool = False,
                    dcn_wire=None):
    """Gather per-site values to every site (used by the low-rank engines to
    share rank-r factors instead of full gradients).

    ``axis_name`` may be a (mesh_axis, vmap_axis) tuple — the folded-sites
    case, where several simulated sites ride one device as a vmapped block.
    ``jax.lax.all_gather`` rejects mixed mesh/vmap axis tuples (unlike
    ``psum``), so gather each axis in turn, innermost first, and flatten: the
    leading dim comes out in global site order (outer*fold_size + inner),
    matching ``jax.lax.axis_index(axes)``.

    A :class:`PackedAxis` gathers the device's whole ``[K, ...]`` virtual-site
    block in ONE collective and flattens to the same global (device-major)
    site order — this is the one exchange whose wire bytes genuinely scale
    with K (every virtual site's factors must reach every device).

    Sliced axes (``slice_name`` set) gather hierarchically: the intra-slice
    gather assembles the slice's ``[S/slices, ...]`` block over ICI, then ONE
    inter-slice gather ships that block across DCN — re-quantized per
    virtual-site row through ``dcn_wire`` when a DCN codec is set (payload
    gathers only; bookkeeping gathers pass ``dcn_wire=None`` and cross
    exact). The flattened result is the same slice-major global site order
    as the data layout — gathering is exact, so without a DCN codec the
    values match the flat single-mesh gather bit-for-bit."""
    if isinstance(axis_name, str):
        return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)
    if isinstance(axis_name, PackedAxis):
        assert axis == 0 and not tiled, "packed gather stacks the leading dim only"
        if axis_name.name is None:
            return x  # every virtual site already local: [S, ...] as-is
        out = jax.lax.all_gather(x, axis_name.name, axis=0)
        out = out.reshape((-1,) + x.shape[1:])
        if axis_name.slice_name is not None:
            if dcn_wire is not None:
                # per-virtual-site-row DCN re-quantization of the slice's
                # block before the expensive hop (batched: scale per row)
                out = dcn_wire.compress(out, batched=True)
            out = jax.lax.all_gather(out, axis_name.slice_name, axis=0)
            out = out.reshape((-1,) + x.shape[1:])
        return out
    assert axis == 0 and not tiled, "tuple-axis gather supports leading-dim stacking only"
    out = x
    for ax in reversed(tuple(axis_name)):
        out = jax.lax.all_gather(out, ax, axis=0)
    return out.reshape((-1,) + x.shape)


def site_all_gather_packed(parts, axis_name=SITE_AXIS, dcn_wire=None):
    """ONE ``all_gather`` for a list of same-dtype ``[k_i, ...]`` arrays
    (matching trailing dims): concatenate along axis 0, gather, re-split into
    ``[S, k_i, ...]`` views.

    The low-rank engines otherwise issue two gathers per compressible leaf
    (P and Q); packing turns a whole rank group's factor exchange into a
    single collective launch — comm volume unchanged (``r·Σ(m_i+n_i)`` per
    site), launch count divided by ``2·|group|`` (the flagship ICA-LSTM's
    r=10 group goes from 12 gathers per round to 1).

    Under a :class:`PackedAxis` the parts carry a leading ``[K]`` virtual-site
    axis (``[K, k_i, ...]``); they concatenate on axis 1, the device's whole
    ``[K, Σk_i, ...]`` block ships in one gather, and the splits come back in
    the same global-site-order ``[S, k_i, ...]`` views as the classic form —
    downstream reconstruction code is identical either way."""
    packed = isinstance(axis_name, PackedAxis)
    cat_axis = 1 if packed else 0
    if len(parts) == 1:
        return [site_all_gather(parts[0], axis_name, dcn_wire=dcn_wire)]
    sizes = [p.shape[cat_axis] for p in parts]
    gathered = site_all_gather(
        jnp.concatenate(parts, axis=cat_axis), axis_name, dcn_wire=dcn_wire
    )
    outs, off = [], 0
    for k in sizes:
        outs.append(gathered[:, off:off + k])
        off += k
    return outs


def wire_compress(x, pdtype):
    """Round-trip ``x`` through the wire payload dtype (``precision_bits``):
    the value a collective actually transports, restored to f32 so the
    reduction itself accumulates at full precision (policy above: psum never
    runs in bf16)."""
    return x.astype(pdtype).astype(jnp.float32)


# ---------------------------------------------------------------------------
# quantized wire codecs (r14)
# ---------------------------------------------------------------------------

#: accepted TrainConfig.wire_quant values. "none" keeps the legacy
#: precision_bits wire byte-for-byte (program-identical, S005-gated);
#: "bf16" forces a bf16 wire regardless of precision_bits; "int8"/"fp8"
#: are the scale-per-payload quantized codecs below.
WIRE_QUANTS = ("none", "bf16", "int8", "fp8")

#: largest finite float8_e4m3fn magnitude — the fp8 codec maps each
#: payload's amax onto it so small-gradient tensors don't flush to zero
#: (e4m3's min normal is ~1.6e-2; raw-cast gradients of ~1e-4 would vanish)
FP8_E4M3_MAX = 448.0


def _dither_uniform(v):
    """Deterministic per-element uniform in [0, 1) for stochastic rounding,
    derived by hashing the value's own float bits (splitmix/murmur-style
    integer finalizer) — no RNG key to thread through the engines, identical
    across topologies and replays, and decorrelated across elements/rounds
    because the hashed bits change with the value. 24-bit mantissa-exact."""
    bits = jax.lax.bitcast_convert_type(v.astype(jnp.float32), jnp.uint32)
    h = bits * jnp.uint32(0x9E3779B9)
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    return (h >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def _payload_amax_scale(xf, batched: bool, grid_max: float):
    """Per-payload symmetric scale mapping ``amax`` onto the codec grid's
    largest representable magnitude. ``batched=True`` treats the LEADING axis
    as the virtual-site axis (one scale per packed row — each virtual site
    quantizes its own payload, matching the per-member semantics of the
    classic one-site-per-device form). All-zero (a masked dead site's
    where-zeroed payload) and non-finite amax fall back to scale 1.0, so the
    codec never manufactures NaN out of a 0/0."""
    axes = tuple(range(1, xf.ndim)) if batched else None
    amax = jnp.max(jnp.abs(xf), axis=axes, keepdims=batched)
    ok = jnp.isfinite(amax) & (amax > 0)
    return jnp.where(ok, amax / jnp.float32(grid_max), 1.0)


@dataclasses.dataclass(frozen=True)
class WireCodec:
    """One wire-quantization codec: what a collective payload is QUANTIZED to
    before it ships, and how it is restored after.

    ``compress`` follows the repo's established bf16-wire pattern
    (:func:`wire_compress`): the payload round-trips through the wire grid
    and the collective itself accumulates in f32 — reductions never run in a
    narrow dtype, and dequantization distributes over the sum exactly
    (``Σ_s scale_s·q_s`` is the same value whether each member dequantizes
    before the reduce or a transport dequantizes after it; the traced
    program carries the quantize→collective chain, which checks/semantic.py
    S002/S004 resolve to the wire dtype to PROVE the byte shrink). ``dtype``
    is what crosses the wire per element — int8/fp8 are 1 byte, a 4× shrink
    over f32; a physical transport adds one f32 scale scalar per payload
    (modeled as negligible, not counted in ``Engine.wire_bytes``).

    ``quant="none"`` reproduces the legacy ``precision_bits`` round-trip
    bit-for-bit — engines keep their historical code path there, so the
    disabled codec is program-identical (S005-gated).

    ``stochastic=True`` (int8 only) rounds stochastically on the quant grid
    — ``floor(v + u)``, ``u ~ U[0,1)`` from :func:`_dither_uniform` — making
    the quantizer unbiased in expectation; fp8 keeps round-to-nearest-even
    (hardware cast semantics)."""

    quant: str  # "none" | "bf16" | "int8" | "fp8"
    dtype: Any  # numpy dtype on the wire (what Engine.wire_dtype reports)
    stochastic: bool = False

    def compress(self, x, batched: bool = False):
        """Round-trip one payload leaf through the wire grid (f32 in/out).
        ``batched=True``: leading axis is the packed virtual-site axis —
        scale per row (see :func:`_payload_amax_scale`)."""
        xf = x.astype(jnp.float32)
        if self.quant == "none":
            return wire_compress(xf, self.dtype)
        if self.quant == "bf16":
            return wire_compress(xf, jnp.bfloat16)
        if self.quant == "int8":
            scale = _payload_amax_scale(xf, batched, 127.0)
            v = xf / scale
            if self.stochastic:
                q = jnp.floor(v + _dither_uniform(v))
            else:
                q = jnp.round(v)
            q = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)
            return q.astype(jnp.float32) * scale
        if self.quant == "fp8":
            scale = _payload_amax_scale(xf, batched, FP8_E4M3_MAX)
            q = (xf / scale).astype(jnp.float8_e4m3fn)
            return q.astype(jnp.float32) * scale
        raise ValueError(f"unknown wire codec {self.quant!r}")


def resolve_wire_codec(precision_bits="32", wire_quant: str = "none",
                       stochastic: bool = False) -> WireCodec:
    """Resolve ``(precision_bits, TrainConfig.wire_quant)`` to the engine's
    wire codec. ``wire_quant="none"`` defers entirely to ``precision_bits``
    (the legacy wire); any other value overrides the WIRE dtype only — the
    power-iteration matmul precision stays governed by ``precision_bits``
    (engines/rankdad.py ``mm_dtype``), the two knobs compose."""
    import numpy as np

    if wire_quant not in WIRE_QUANTS:
        raise ValueError(
            f"wire_quant must be one of {WIRE_QUANTS}, got {wire_quant!r}"
        )
    if wire_quant == "none":
        dtype = np.dtype(_PAYLOAD_DTYPES[precision_bits])
    elif wire_quant == "bf16":
        dtype = np.dtype(jnp.bfloat16)
    elif wire_quant == "int8":
        dtype = np.dtype(np.int8)
    else:  # fp8
        if not hasattr(jnp, "float8_e4m3fn"):  # pragma: no cover - old jax
            raise ValueError(
                "wire_quant='fp8' needs jnp.float8_e4m3fn (ml_dtypes); "
                "this jax build lacks it — use 'int8' or 'bf16'"
            )
        dtype = np.dtype(jnp.float8_e4m3fn)
    # factory kwarg, never a tracer: TrainConfig.wire_stochastic is static
    return WireCodec(
        quant=wire_quant, dtype=dtype,
        stochastic=bool(stochastic) and wire_quant == "int8",  # jaxlint: disable=R005
    )


def resolve_dcn_codec(precision_bits="32", wire_quant: str = "none",
                      dcn_wire_quant: str = "", stochastic: bool = False):
    """Resolve ``TrainConfig.dcn_wire_quant`` to the inter-slice codec, or
    ``None`` — the FUSED form (no re-quantization at the slice boundary;
    tiers 1+2 are one collective, sliced==unsliced stays bit-exact).

    ``""`` (the config default) follows ``wire_quant``, so quantized wires
    land their shrink on BOTH tiers unless the operator splits them;
    ``"none"`` explicitly opts the DCN tier out while the ICI wire stays
    quantized. Single-slice meshes never consult this — there is no DCN
    tier to codec."""
    eff = dcn_wire_quant or wire_quant
    if eff == "none":
        return None
    return resolve_wire_codec(precision_bits, eff, stochastic)


# ---------------------------------------------------------------------------
# byzantine-robust site-axis reducers (r17)
# ---------------------------------------------------------------------------

#: accepted TrainConfig.robust_agg / engine robust_agg values. "none" keeps
#: the legacy renormalizing weighted mean program-identically (S005-gated);
#: "norm_clip" clips each site's gradient norm to a robust (weighted-median)
#: threshold before the SAME weighted-mean wire (composes with quantized
#: wires); "trimmed_mean" / "coordinate_median" replace the psum-shaped
#: exchange with a cross-site gather and reduce per coordinate over the
#: global site axis — the classic byzantine-robust estimators, at a
#: genuinely larger wire (every site's payload must reach every device).
ROBUST_AGGS = ("none", "norm_clip", "trimmed_mean", "coordinate_median")


def _sorted_site_axis(vals, weight):
    """Sort ``vals [S, ...]`` along the site axis per coordinate and carry
    the per-site weights with each coordinate's permutation. Returns
    ``(v_sorted, w_sorted, cum, total)`` where ``cum`` is the inclusive
    cumulative weight in sorted order and ``total`` the (broadcastable)
    weight total."""
    order = jnp.argsort(vals, axis=0)
    v_sorted = jnp.take_along_axis(vals, order, axis=0)
    w = jnp.asarray(weight, jnp.float32).reshape(
        (vals.shape[0],) + (1,) * (vals.ndim - 1)
    )
    w_sorted = jnp.take_along_axis(
        jnp.broadcast_to(w, vals.shape), order, axis=0
    )
    cum = jnp.cumsum(w_sorted, axis=0)
    return v_sorted, w_sorted, cum, cum[-1:]


def weighted_trimmed_mean(vals, weight, trim_frac: float):
    """Per-coordinate WEIGHTED trimmed mean over the leading site axis:
    sort each coordinate's S values, drop ``trim_frac`` of the total live
    weight from each tail, average what remains (each sorted entry
    contributes the overlap of its weight interval with the kept band —
    exact for fractional trims and for dead sites, whose weight is 0 and
    who therefore never shift the band). ``trim_frac`` is a trace-time
    static in [0, 0.5); an all-dead coordinate (total weight 0) reduces to
    0, matching the weighted mean's zero-total guard."""
    # factory kwarg, never a tracer: TrainConfig.robust_trim_frac is static
    if not 0.0 <= float(trim_frac) < 0.5:  # jaxlint: disable=R005
        raise ValueError(
            f"trim_frac must be in [0, 0.5), got {trim_frac}"
        )
    v_sorted, w_sorted, cum, total = _sorted_site_axis(vals, weight)
    lo = jnp.float32(trim_frac) * total
    hi = (1.0 - jnp.float32(trim_frac)) * total
    keep = jnp.clip(
        jnp.minimum(cum, hi) - jnp.maximum(cum - w_sorted, lo), 0.0, None
    )
    denom = jnp.sum(keep, axis=0)
    out = jnp.sum(keep * v_sorted, axis=0) / jnp.maximum(denom, 1e-12)
    return jnp.where(total[0] > 0, out, jnp.zeros_like(out))


def weighted_coordinate_median(vals, weight):
    """Per-coordinate WEIGHTED (lower) median over the leading site axis:
    the sorted value whose cumulative weight interval contains half the
    total live weight. Dead sites (weight 0) never get selected; an
    all-dead coordinate reduces to 0 like the weighted mean's zero-total
    guard. Breakdown point 1/2 — the strongest of the robust reducers, at
    the same gathered wire as the trimmed mean."""
    v_sorted, w_sorted, cum, total = _sorted_site_axis(vals, weight)
    mid = 0.5 * total
    keep = (
        (cum - w_sorted < mid) & (cum >= mid) & (w_sorted > 0)
    ).astype(jnp.float32)
    out = jnp.sum(keep * v_sorted, axis=0) / jnp.maximum(
        jnp.sum(keep, axis=0), 1.0
    )
    return jnp.where(total[0] > 0, out, jnp.zeros_like(out))


def robust_site_reduce(vals, weight, mode: str, trim_frac: float = 0.2):
    """Dispatch one gathered ``[S, ...]`` payload through the configured
    robust reducer (``mode`` is a trace-time static)."""
    if mode == "trimmed_mean":
        return weighted_trimmed_mean(vals, weight, trim_frac)
    if mode == "coordinate_median":
        return weighted_coordinate_median(vals, weight)
    raise ValueError(f"unknown robust site reducer {mode!r}")


def robust_clip_scales(nsq, weight, axis_name, clip_mult: float):
    """Norm-clip defense: per-site multiplicative clip scales from a ROBUST
    norm threshold.

    ``nsq`` is each site's squared gradient norm (a scalar under the
    classic vmapped axes, the ``[K]`` virtual-site vector under a
    :class:`PackedAxis`); the threshold is ``clip_mult ×`` the live-weighted
    MEDIAN site norm across the global site axis — an attacker scaling its
    gradient cannot move a median it does not own, so the clip threshold
    stays anchored to the honest cohort. The cross-site exchange is two
    tiny gathers (the per-site norm and weight vectors — modeled in the
    engines' robust-mode ``wire_shapes``); the gradient payload itself then
    rides the engine's UNCHANGED weighted-mean wire, which is why norm_clip
    composes with the quantized wire codecs.
    """
    ns_all = site_all_gather(jnp.asarray(nsq, jnp.float32), axis_name)
    w_all = site_all_gather(jnp.asarray(weight, jnp.float32), axis_name)
    med = weighted_coordinate_median(jnp.sqrt(ns_all), w_all)
    tau = jnp.float32(clip_mult) * med
    norm = jnp.sqrt(jnp.asarray(nsq, jnp.float32))
    return jnp.where(norm > tau, tau / jnp.maximum(norm, 1e-30), 1.0)


def clip_site_gradients(grads, weight, axis_name, clip_mult: float):
    """Apply the norm-clip defense to a per-site gradient pytree (leaves
    carry the leading ``[K]`` pack axis under a :class:`PackedAxis`,
    are unbatched per vmapped member otherwise). Returns the clipped tree;
    weights are untouched — clipping bounds a hostile site's INFLUENCE,
    the weighted mean still renormalizes as usual."""
    packed = isinstance(axis_name, PackedAxis)
    if packed:
        k = axis_name.pack
        nsq = jnp.zeros((k,), jnp.float32)
        for leaf in jax.tree.leaves(grads):
            nsq = nsq + jnp.sum(
                jnp.square(leaf.astype(jnp.float32)).reshape(k, -1), axis=1
            )
    else:
        nsq = jnp.zeros((), jnp.float32)
        for leaf in jax.tree.leaves(grads):
            nsq = nsq + jnp.sum(jnp.square(leaf.astype(jnp.float32)))
    scale = robust_clip_scales(nsq, weight, axis_name, clip_mult)
    return jax.tree.map(
        lambda g: (
            g.astype(jnp.float32)
            * scale.reshape(scale.shape + (1,) * (g.ndim - scale.ndim))
        ).astype(g.dtype),
        grads,
    )


def site_index(axis_name=SITE_AXIS):
    if isinstance(axis_name, PackedAxis):
        # per-device block start: virtual site d*K + j lives at row j of the
        # packed leaf on mesh member d (device-major global order; sliced
        # meshes linearize slice-major over the (slice, site) pair — the
        # same order the P((slice, site)) data layout shards to)
        if axis_name.name is None:
            base = 0
        else:
            base = jax.lax.axis_index(axis_name.reduce_axes())
        return base * axis_name.pack
    return jax.lax.axis_index(axis_name)


def site_count(axis_name=SITE_AXIS):
    if isinstance(axis_name, PackedAxis):
        n = 1 if axis_name.name is None else jax.lax.axis_size(axis_name.name)
        if axis_name.slice_name is not None:
            n = n * jax.lax.axis_size(axis_name.slice_name)
        return n * axis_name.pack
    return jax.lax.axis_size(axis_name)
