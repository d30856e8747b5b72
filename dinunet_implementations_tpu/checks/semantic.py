"""jaxprlint — semantic SPMD verification over TRACED programs (tier 2).

The AST tier (rules.py, R001-R007) checks what the source text promises; the
properties the repo actually stakes correctness and perf claims on live in
the traced/lowered/compiled program: "every collective runs over a declared
mesh axis", "`wire_bytes` models what really goes over the wire", "donated
buffers really alias", "the bf16 wire is really bf16", "off == compiled
out". This module traces the REAL fit programs for a small
engine × topology × pipeline matrix on CPU virtual devices and verifies
them semantically:

- **S001** — collective/mesh audit: walking every ClosedJaxpr (recursing
  into scan/while/pjit/shard_map sub-jaxprs), each collective primitive
  (``psum``, ``all_gather``, ``reduce_scatter``, …) may name only the
  declared mesh-axis constants (``parallel/mesh.py``; vmap-resolved fold
  axes appear as positional ints and are fine), and no cross-site
  communication may sit outside the rounds scan — at 512+ packed sites a
  per-round stray collective is a silent synchronization cliff.
- **S002** — wire-byte proof: the per-round PER-DEVICE collective payload,
  computed from the TRACED operand shapes/dtypes, must match the engine's
  static ``wire_bytes`` model exactly — at the cell's site-packing factor
  (r12: packed cells verify that psum-shaped exchanges reduce over the
  packed virtual-site axis in-register BEFORE the wire and stay
  K-invariant, while the factor gather's ``[K, Σ(m+n), r]`` block is
  modeled as genuinely K-scaling). Matching is structural: every entry of
  the engine's ``wire_shapes`` introspection hook (engines/base.py) must
  appear as a traced collective operand literally, every traced
  payload-sized operand must be covered by the model, and the byte totals
  must agree. The telemetry layer's ``payload_bytes`` figures
  (telemetry/metrics.py) become verified, not modeled.
- **S003** — donation proof: for ``donate_epoch_state`` builds, the compiled
  executable's input-output aliasing must actually contain every donated
  TrainState buffer. A donated-but-unaliased arg is a silent HBM/perf bug —
  jax warns once to stderr and the epoch quietly doubles its params+opt
  residency.
- **S004** — precision-flow lint on the aggregation path: each payload
  operand's wire dtype (resolved through its producer chain, so the
  ``wire_compress`` bf16→f32 round-trip counts as bf16) must not be wider
  than the engine's modeled payload dtype, and a ``precision_bits="16"``
  compression engine must actually lower low-precision ``dot_general`` ops
  for its power-iteration products (engines/lowrank.py ``lp_matmul``).
- **S005** — program-identity gate over the normalized-lowering differ
  (checks/lowering.py): telemetry-off, faults-off(-by-default), and the
  sanitizer's observation modes must be lowering-identical to the baseline
  program, and the static opt-outs (``quarantine_rounds=-1``,
  ``telemetry=True``) must genuinely diverge — if the "compiled out"
  machinery stops being compiled out, this gate fails.

Run with ``python -m dinunet_implementations_tpu.checks --semantic`` (CPU;
the CLI provisions virtual devices). Findings ride the same
:class:`~.core.Finding`/baseline machinery as the AST tier, keyed on
``(rule, trace://<cell>, snippet)`` — grandfathering goes through
``checks/baseline_semantic.json`` (shipped EMPTY); there is no inline
suppression for traced programs.
"""

from __future__ import annotations

import dataclasses
import math
import os
import re

from .core import Finding
from .rules import COLLECTIVE_AXIS_ARG

#: the semantic tier's grandfather list (empty == every traced program clean)
SEMANTIC_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "baseline_semantic.json"
)

# -- collective tables ------------------------------------------------------
# Derived from the AST tier's COLLECTIVE_AXIS_ARG so the two tiers agree on
# what counts as a collective (tests/test_semantic.py asserts the mapping is
# total). Some lax APIs trace to differently-named primitives:
API_TO_PRIM = {
    "psum_scatter": "reduce_scatter",
    "pmean": "psum",  # pmean is psum / axis_size sugar
    "axis_size": "psum",  # old-jax spelling: psum(1, axis)
}

#: traced primitives that move data across the site/model axes
COMM_PRIMS = frozenset({
    "psum", "pmax", "pmin", "ppermute", "all_gather", "all_to_all",
    "reduce_scatter", "pbroadcast",
})
#: traced primitives that only QUERY the axis (no payload; exempt from the
#: in-scan and wire-byte rules, still axis-name audited)
QUERY_PRIMS = frozenset({"axis_index"})


def prim_for(api_name: str) -> str:
    """Traced-primitive name for a lax collective API name."""
    return API_TO_PRIM.get(api_name, api_name)


# tier agreement, enforced at import (a hard raise, not an assert — it must
# survive python -O): every collective the AST tier knows must trace to a
# primitive this tier audits
_unmapped = [
    n for n in COLLECTIVE_AXIS_ARG
    if prim_for(n) not in COMM_PRIMS | QUERY_PRIMS
]
if _unmapped:
    raise RuntimeError(
        f"rules.COLLECTIVE_AXIS_ARG and the semantic tier's COMM/QUERY "
        f"primitive tables have drifted: {_unmapped} have no traced-"
        f"primitive mapping (extend API_TO_PRIM/COMM_PRIMS)"
    )


def ensure_cpu_devices(min_devices: int = 2, want: int = 8) -> None:
    """Provision virtual CPU devices for the trace matrix.

    Must run before the jax backend initializes (the CLI path — jax is
    imported by the package but uninitialized until first device use); in an
    already-initialized process (pytest under tests/conftest.py) it is a
    no-op and the session's device count is used.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={want}"
        ).strip()
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except (RuntimeError, ValueError):
        pass  # backend already initialized; run on what the session has
    cpus = [d for d in jax.devices() if d.platform == "cpu"]
    if len(cpus) < min_devices:
        raise RuntimeError(
            f"the semantic tier traces mesh programs and needs >= "
            f"{min_devices} CPU devices, have {len(cpus)}; run via `python "
            f"-m dinunet_implementations_tpu.checks --semantic` (which sets "
            f"XLA_FLAGS before jax initializes) or export XLA_FLAGS="
            f"--xla_force_host_platform_device_count={want}"
        )


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CollectiveSite:
    """One collective primitive found in a traced program."""

    prim: str
    named_axes: tuple  # str axis names only (vmap-resolved folds are ints)
    operands: tuple  # operand avals
    scan_depth: int  # 0 == outside every scan/while
    wire_itemsizes: tuple  # per operand: effective float itemsize of the
    # payload it carries (_payload_itemsize; None for non-float operands)


@dataclasses.dataclass
class ProgramAudit:
    """Everything the S-rules need from one traced program."""

    collectives: list
    dots: list  # (lhs_itemsize, rhs_itemsize, scan_depth) per dot_general


#: value-preserving / scaling ops the wire-dtype walk may look through: the
#: payload chain between "quantized to the wire dtype" and "handed to the
#: collective" is casts, scale multiplies, liveness selects and layout
#: moves — plus the r14 quantized-wire codec's grid ops (round/floor to the
#: int8 grid, clamp to its range): parallel/collectives.py WireCodec
_PASSTHROUGH = frozenset({
    "convert_element_type", "mul", "div", "add", "sub", "neg", "select_n",
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "expand_dims",
    "concatenate", "slice", "stop_gradient", "copy",
    "round", "floor", "clamp", "max", "min",
})

#: the elementwise/broadcasting subset of _PASSTHROUGH: only here may an
#: operand smaller than the output be dismissed as a broadcasting scale
_ELEMENTWISE = frozenset({
    "mul", "div", "add", "sub", "select_n", "max", "min", "clamp",
})


def _sub_jaxprs(params: dict):
    """All jaxprs nested in one eqn's params (scan/while/pjit/shard_map/
    custom_* — any param that is a Jaxpr, a ClosedJaxpr, or a sequence of
    them)."""
    from jax.extend.core import ClosedJaxpr as closed
    from jax.extend.core import Jaxpr as plain

    for v in params.values():
        if isinstance(v, closed):
            yield v.jaxpr
        elif isinstance(v, plain):
            yield v
        elif isinstance(v, (list, tuple)):
            for vv in v:
                if isinstance(vv, closed):
                    yield vv.jaxpr
                elif isinstance(vv, plain):
                    yield vv


def _float_itemsize(dtype):
    """Itemsize when ``dtype`` is a float (incl. the ml_dtypes extension
    floats — bfloat16/float8 have numpy kind 'V', not 'f'), else None."""
    import jax.numpy as jnp
    import numpy as np

    d = np.dtype(dtype)
    if d.kind == "f" or jnp.issubdtype(d, jnp.floating):
        return d.itemsize
    return None


def _wire_itemsize_of(dtype):
    """Effective wire itemsize of one dtype in a payload chain: floats carry
    their own width; a SUB-WORD integer is a quantization grid (the r14 int8
    wire codec's ``convert→int8→convert`` round-trip — the value the chain
    carries from there on fits in that many bytes); word-size-and-up
    integers (indices, counters) are not payloads at all → None."""
    import numpy as np

    f = _float_itemsize(dtype)
    if f is not None:
        return f
    d = np.dtype(dtype)
    if d.kind in "iu" and d.itemsize < 4:
        return d.itemsize
    return None


def _is_scale_operand(var, producers: dict) -> bool:
    """True when ``var`` enters an arithmetic op as a scale/mask rather than
    as the payload itself: a literal, a scalar, or a broadcast of something
    smaller than itself. A narrow float there perturbs the payload but does
    not quantize it, so the wire-dtype walk must not let it narrow the
    result."""
    aval = getattr(var, "aval", None)
    if aval is None:  # jaxpr Literal
        return True
    shape = tuple(getattr(aval, "shape", ()))
    if math.prod(shape) <= 1:
        return True
    eqn = producers.get(id(var))
    if eqn is not None and eqn.primitive.name == "broadcast_in_dim":
        src = getattr(eqn.invars[0], "aval", None)
        if src is not None and (
            math.prod(tuple(getattr(src, "shape", ()))) < math.prod(shape)
        ):
            return True
    return False


def _payload_itemsize(var, producers: dict, max_depth: int = 10):
    """Effective float itemsize of the value ``var`` carries onto the wire —
    the dtype the payload was QUANTIZED to, even when an f32-accumulating
    collective consumes the f32 round-trip of a bf16 value
    (``parallel/collectives.py wire_compress``).

    The walk follows the payload's own dataflow, not every contributor: a
    cast chain can only narrow (min with the input), an n-ary arithmetic op
    is only as narrow as its WIDEST data-carrying operand (combining a
    quantized tensor with a full-precision one leaves the quantized grid —
    an f32 payload multiplied by a mask that touched bf16 must still read
    f32), and scale/mask operands (:func:`_is_scale_operand`) are skipped
    entirely (an f32 grad scaled by a shared bf16 scalar is not a bf16
    wire, and a bf16 payload scaled by an f32 weight still is one)."""

    def eff(v, depth):
        aval = getattr(v, "aval", None)
        if aval is None:
            return None
        # sub-word integers count as quantization grids (_wire_itemsize_of):
        # the int8 wire codec's round-trip passes through an int8 value, and
        # everything downstream of that cast carries ≤ 1 byte of payload
        storage = _wire_itemsize_of(aval.dtype)
        if storage is None:
            return None
        eqn = producers.get(id(v))
        if eqn is None or depth >= max_depth:
            return storage
        if eqn.primitive.name not in _PASSTHROUGH:
            return storage
        out_elems = math.prod(tuple(getattr(aval, "shape", ())))
        elementwise = eqn.primitive.name in _ELEMENTWISE

        def _scale_like(iv):
            # a scale/mask never carries the payload: literals, scalars,
            # explicit broadcasts (_is_scale_operand) — and, for
            # ELEMENTWISE ops only, any operand STRICTLY SMALLER than the
            # output, i.e. one that broadcasts against the payload (the
            # r14 packed per-row [K, 1, 1] quant scale reaches the mul at
            # its own rank-kept shape, no broadcast_in_dim in the jaxpr).
            # Shape-composing ops (concatenate, slice) keep every operand
            # as data: their inputs are legitimately smaller than the
            # output without being scales.
            if _is_scale_operand(iv, producers):
                return True
            if not elementwise:
                return False
            a = getattr(iv, "aval", None)
            if a is None:
                return True
            return math.prod(tuple(getattr(a, "shape", ()))) < out_elems

        data = [
            iv for iv in eqn.invars
            if len(eqn.invars) == 1 or not _scale_like(iv)
        ]
        subs = [s for s in (eff(iv, depth + 1) for iv in data) if s is not None]
        if not subs:
            return storage
        return min(storage, max(subs))

    return eff(var, 0)


def _named_axes(params: dict) -> tuple:
    ax = params.get("axes", params.get("axis_name"))
    if ax is None:
        return ()
    if not isinstance(ax, (tuple, list)):
        ax = (ax,)
    return tuple(a for a in ax if isinstance(a, str))


def audit_jaxpr(closed_jaxpr) -> ProgramAudit:
    """Walk a ClosedJaxpr (recursing into every sub-jaxpr) and collect all
    collective sites + dot_general precision info."""
    collectives: list = []
    dots: list = []

    def walk(jaxpr, scan_depth: int):
        producers: dict = {}
        for eqn in jaxpr.eqns:
            for ov in eqn.outvars:
                producers[id(ov)] = eqn
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in COMM_PRIMS or name in QUERY_PRIMS:
                ops = tuple(getattr(v, "aval", None) for v in eqn.invars)
                wis = tuple(
                    _payload_itemsize(v, producers) for v in eqn.invars
                )
                collectives.append(CollectiveSite(
                    prim=name,
                    named_axes=_named_axes(eqn.params),
                    operands=ops,
                    scan_depth=scan_depth,
                    wire_itemsizes=wis,
                ))
            elif name == "dot_general":
                sizes = [
                    _float_itemsize(v.aval.dtype)
                    if getattr(v, "aval", None) is not None else None
                    for v in eqn.invars[:2]
                ]
                dots.append((sizes[0], sizes[1], scan_depth))
            inner_depth = scan_depth + (1 if name in ("scan", "while") else 0)
            for sub in _sub_jaxprs(eqn.params):
                walk(sub, inner_depth)

    walk(closed_jaxpr.jaxpr, 0)
    return ProgramAudit(collectives=collectives, dots=dots)


# ---------------------------------------------------------------------------
# S001 — collective/mesh audit
# ---------------------------------------------------------------------------


def check_collective_axes(
    collectives: list, path: str, allowed_axes=None,
    require_in_scan: bool = True,
) -> list:
    """S001: every collective names only declared mesh-axis constants, and
    cross-site communication lives inside the rounds scan."""
    if allowed_axes is None:
        from ..parallel.mesh import MODEL_AXIS, SITE_AXIS

        allowed_axes = {SITE_AXIS, MODEL_AXIS}
    findings = []
    for site in collectives:
        rogue = [a for a in site.named_axes if a not in allowed_axes]
        if rogue:
            findings.append(Finding(
                rule="S001", path=path, line=0, col=0,
                message=(
                    f"collective '{site.prim}' runs over undeclared axis "
                    f"name(s) {rogue} (declared mesh axes: "
                    f"{sorted(allowed_axes)}) — it reduces over something "
                    f"other than the site/model mesh"
                ),
                snippet=f"{site.prim} axes={rogue}",
                fixit="bind collectives to the parallel/mesh.py axis "
                      "constants (SITE_AXIS/MODEL_AXIS; folded sites ride "
                      "vmap and resolve positionally)",
            ))
        if require_in_scan and site.prim in COMM_PRIMS and site.scan_depth == 0:
            findings.append(Finding(
                rule="S001", path=path, line=0, col=0,
                message=(
                    f"cross-site collective '{site.prim}' appears OUTSIDE "
                    f"the rounds scan — per-epoch stray communication that "
                    f"the round loop cannot overlap or amortize"
                ),
                snippet=f"{site.prim} outside-scan",
                fixit="move cross-site communication inside the rounds scan "
                      "(trainer/steps.py one_round) so it ships once per "
                      "round with the aggregation traffic",
            ))
    return findings


# ---------------------------------------------------------------------------
# S002 / S004 — wire-byte proof + precision flow
# ---------------------------------------------------------------------------


def _match_payload(collectives: list, expected: list):
    """Assign modeled payload entries to traced collective operands.

    ``expected`` is ``[(shape, np.dtype), ...]`` from the engine's wire
    model AT THE CELL'S PACK FACTOR; traced operands are matched by shape
    literally — since the two-level aggregation (r12) the mesh collectives
    carry exactly the per-device payloads the model describes (psum partials
    unbatched, the factor gather with its leading ``[pack]`` virtual-site
    axis), so there is no site-block normalization to undo. Returns
    ``(matches, missing, leftovers)`` where matches are ``(shape,
    model_dtype, traced_itemsize, prim)``, missing are unmatched model
    entries, and leftovers are traced COMM operands covered by nothing
    (excluding the scalar bookkeeping collectives: loss and
    weight-normalization psums)."""
    import numpy as np

    traced = []
    for site in collectives:
        if site.prim not in COMM_PRIMS:
            continue
        for aval, wi in zip(site.operands, site.wire_itemsizes):
            if aval is None:
                continue
            isz = wi if wi is not None else np.dtype(aval.dtype).itemsize
            traced.append({
                "shape": tuple(aval.shape), "itemsize": isz, "prim": site.prim,
                "matched": False,
            })
    matches, missing = [], []
    for shape, dtype in expected:
        # prefer an operand at exactly the modeled itemsize so two same-shape
        # payloads at different dtypes (a bf16 factor next to an f32 dense
        # leaf) cannot cross-pair; fall back to shape-only so a genuine
        # upcast still pairs with its model entry (and S004 flags it)
        # instead of reading as a coverage hole. Stat-shaped operands can't
        # be excluded here: a dense payload may legitimately share a stat's
        # shape AND dtype, and then either pairing is byte-identical.
        cands = [t for t in traced if not t["matched"] and t["shape"] == shape]
        hit = next(
            (t for t in cands if t["itemsize"] == dtype.itemsize),
            cands[0] if cands else None,
        )
        if hit is None:
            missing.append((shape, dtype))
            continue
        hit["matched"] = True
        matches.append((shape, dtype, hit["itemsize"], hit["prim"]))
    leftovers = [
        t for t in traced if not t["matched"] and t["shape"] != ()
    ]
    return matches, missing, leftovers


def check_wire_bytes(
    collectives: list, engine, params_template, pack: int, path: str,
    stats_shapes=(),
) -> list:
    """S002: traced collective payload bytes == ``Engine.wire_bytes``,
    exactly, with structural coverage both ways — evaluated at the cell's
    site-packing factor ``pack`` (the k virtual sites per device), so a
    model that ignores packing (per-site instead of per-device accounting)
    is flagged on the packed cells."""
    from ..telemetry.metrics import modeled_wire_shapes, payload_bytes_of

    expected = modeled_wire_shapes(engine, params_template, pack=pack)
    model_total = sum(
        math.prod(s) * d.itemsize for s, d in expected
    )
    wb = int(payload_bytes_of(engine, params_template, pack=pack))
    findings = []
    if model_total != wb:
        findings.append(Finding(
            rule="S002", path=path, line=0, col=0,
            message=(
                f"engine '{engine.name}': wire_shapes model sums to "
                f"{model_total} B but wire_bytes reports {wb} B — the "
                f"structured and scalar payload models have drifted"
            ),
            snippet="model-inconsistent",
            fixit="keep Engine.wire_shapes and Engine.wire_bytes derived "
                  "from the same shape arithmetic (engines/lowrank.py "
                  "lowrank_rank_groups)",
        ))
    matches, missing, leftovers = _match_payload(collectives, expected)
    for shape, dtype in missing:
        findings.append(Finding(
            rule="S002", path=path, line=0, col=0,
            message=(
                f"engine '{engine.name}': modeled payload operand "
                f"{shape}@{dtype} never appears as a traced collective "
                f"operand — the wire model OVERCOUNTS what ships"
            ),
            snippet=f"missing {shape}",
            fixit="make Engine.wire_shapes mirror the collectives the "
                  "aggregate actually launches",
        ))
    for t in leftovers:
        if t["shape"] in tuple(stats_shapes):
            continue  # sync-BN running-stat psums are not engine payload
        findings.append(Finding(
            rule="S002", path=path, line=0, col=0,
            message=(
                f"engine '{engine.name}': traced collective '{t['prim']}' "
                f"ships an operand shaped {t['shape']} that no wire-model "
                f"entry covers — the wire model UNDERCOUNTS what ships"
            ),
            snippet=f"unmodeled {t['prim']} {t['shape']}",
            fixit="add the payload to Engine.wire_shapes/wire_bytes (or "
                  "stop shipping it)",
        ))
    traced_total = sum(
        math.prod(shape) * isz for shape, _, isz, _ in matches
    )
    if not findings and traced_total != wb:
        findings.append(Finding(
            rule="S002", path=path, line=0, col=0,
            message=(
                f"engine '{engine.name}': traced payload is {traced_total} "
                f"B/round/device but wire_bytes models {wb} B at pack="
                f"{pack} — telemetry's payload_bytes figures are wrong"
            ),
            snippet="bytes-mismatch",
            fixit="reconcile the traced operand dtypes with the modeled "
                  "payload dtype (see the S004 findings for which operand "
                  "widened)",
        ))
    return findings


def check_dcn_wire(
    collectives: list, engine, params_template, pack: int,
    sites_per_slice: int, path: str, stats_shapes=(), slices: int = 2,
) -> list:
    """The DCN-tier audit for sliced cells (r18): every collective touching
    the slice axis is either the split inter-slice hop (names EXACTLY
    ``(slice,)`` — the re-quantized per-slice partial / the hierarchical
    gather's slice leg) or a fused ``(slice, site)`` reduce (bookkeeping and
    the no-DCN-codec payload form — one collective spanning both tiers,
    bit-identical to the flat reduce); anything else (a slice+model mix, a
    site-inner ordering) is a mis-laid axis (S001). The payloads of those
    collectives must then match the engine's ``dcn_wire_shapes`` model both
    ways at the cell's pack factor and per-slice site count, and the byte
    totals must agree with ``Engine.dcn_bytes`` — so the
    ``dcn_bytes_per_slice_round`` telemetry/bench figure is PROVEN against
    traced operand shapes, codec shrink included (S002)."""
    from ..parallel.mesh import SITE_AXIS, SLICE_AXIS
    from ..telemetry.metrics import dcn_bytes_of, modeled_dcn_shapes

    findings = []
    dcn_colls = []
    for site in collectives:
        if SLICE_AXIS not in site.named_axes:
            continue
        if tuple(site.named_axes) not in (
            (SLICE_AXIS,), (SLICE_AXIS, SITE_AXIS),
        ):
            findings.append(Finding(
                rule="S001", path=path, line=0, col=0,
                message=(
                    f"collective '{site.prim}' touches the slice axis with "
                    f"axes {tuple(site.named_axes)} — the DCN tier is "
                    f"slice-only (the split hop) or the fused (slice, "
                    f"site) reduce; any other mix re-orders the hierarchy"
                ),
                snippet=f"{site.prim} axes={tuple(site.named_axes)}",
                fixit="route inter-slice traffic through "
                      "parallel/collectives.py three_level_psum / "
                      "site_all_gather (the PackedAxis slice forms)",
            ))
            continue
        if site.prim in COMM_PRIMS and site.scan_depth == 0:
            findings.append(Finding(
                rule="S001", path=path, line=0, col=0,
                message=(
                    f"inter-slice collective '{site.prim}' appears OUTSIDE "
                    f"the rounds scan — stray per-epoch DCN traffic"
                ),
                snippet=f"{site.prim} dcn-outside-scan",
                fixit="keep the DCN hop inside the rounds scan "
                      "(trainer/steps.py one_round)",
            ))
        if site.prim in COMM_PRIMS:
            dcn_colls.append(site)
    expected = modeled_dcn_shapes(
        engine, params_template, pack=pack, sites_per_slice=sites_per_slice
    )
    model_total = sum(math.prod(s) * d.itemsize for s, d in expected)
    db = int(dcn_bytes_of(
        engine, params_template, pack=pack, sites_per_slice=sites_per_slice,
        slices=slices,
    ))
    if model_total != db:
        findings.append(Finding(
            rule="S002", path=path, line=0, col=0,
            message=(
                f"engine '{engine.name}': dcn_wire_shapes model sums to "
                f"{model_total} B but dcn_bytes reports {db} B — the "
                f"structured and scalar DCN payload models have drifted"
            ),
            snippet="dcn-model-inconsistent",
            fixit="derive Engine.dcn_bytes and Engine.dcn_wire_shapes from "
                  "the same shape arithmetic",
        ))
    matches, missing, leftovers = _match_payload(dcn_colls, expected)
    for shape, dtype in missing:
        findings.append(Finding(
            rule="S002", path=path, line=0, col=0,
            message=(
                f"engine '{engine.name}': modeled DCN payload "
                f"{shape}@{dtype} never appears as an operand of a "
                f"slice-axis collective — the DCN wire model OVERCOUNTS "
                f"what crosses the inter-slice hop"
            ),
            snippet=f"dcn-missing {shape}",
            fixit="make Engine.dcn_wire_shapes mirror the slice-axis "
                  "collectives the aggregate actually launches",
        ))
    for t in leftovers:
        if t["shape"] in tuple(stats_shapes):
            continue  # fused sync-BN stat reduces are not engine payload
        findings.append(Finding(
            rule="S002", path=path, line=0, col=0,
            message=(
                f"engine '{engine.name}': slice-axis collective "
                f"'{t['prim']}' ships an operand shaped {t['shape']} that "
                f"no DCN wire-model entry covers — the DCN model "
                f"UNDERCOUNTS what crosses the inter-slice hop"
            ),
            snippet=f"dcn-unmodeled {t['prim']} {t['shape']}",
            fixit="add the payload to Engine.dcn_wire_shapes/dcn_bytes (or "
                  "stop shipping it across slices)",
        ))
    traced_total = sum(
        math.prod(shape) * isz for shape, _, isz, _ in matches
    )
    if not findings and traced_total != db:
        findings.append(Finding(
            rule="S002", path=path, line=0, col=0,
            message=(
                f"engine '{engine.name}': traced DCN payload is "
                f"{traced_total} B/round/slice but dcn_bytes models {db} B "
                f"at pack={pack}, sites_per_slice={sites_per_slice} — the "
                f"per-tier telemetry figures are wrong"
            ),
            snippet="dcn-bytes-mismatch",
            fixit="reconcile the slice-collective operand dtypes with the "
                  "modeled DCN payload dtype (is the codec re-quantization "
                  "really happening at the slice boundary?)",
        ))
    return findings


def check_precision_flow(
    collectives: list, engine, params_template, pack: int, path: str,
    require_lowp_dot: bool = False, dots=(),
) -> list:
    """S004: no payload rides the wire wider than the engine's modeled
    payload dtype, and a 16-bit wire on a compression engine really lowers
    low-precision dots for the power-iteration products. ``pack`` selects
    the wire model's site-packing factor like :func:`check_wire_bytes`."""
    from ..telemetry.metrics import modeled_wire_shapes

    expected = modeled_wire_shapes(engine, params_template, pack=pack)
    matches, _, _ = _match_payload(collectives, expected)
    findings = []
    for shape, dtype, traced_isz, prim in matches:
        if traced_isz is not None and traced_isz > dtype.itemsize:
            findings.append(Finding(
                rule="S004", path=path, line=0, col=0,
                message=(
                    f"engine '{engine.name}': payload {shape} rides "
                    f"'{prim}' at {traced_isz * 8}-bit floats but the wire "
                    f"model says {dtype} — an accidental upcast on the "
                    f"wire path (the precision_bits compression is not "
                    f"happening)"
                ),
                snippet=f"upcast {prim} {shape}",
                fixit="quantize the payload to the wire dtype before the "
                      "collective (parallel/collectives.py payload_cast / "
                      "wire_compress)",
            ))
    if require_lowp_dot:
        lowp = any(
            a is not None and b is not None and a < 4 and b < 4
            for a, b, _ in dots
        )
        if not lowp:
            findings.append(Finding(
                rule="S004", path=path, line=0, col=0,
                message=(
                    f"engine '{engine.name}' with a 16-bit wire lowers no "
                    f"low-precision dot_general — the mixed-precision "
                    f"power-iteration matmuls (engines/lowrank.py "
                    f"lp_matmul) silently run full f32"
                ),
                snippet="no-lowp-dot",
                fixit="thread matmul_dtype=jnp.bfloat16 through the "
                      "engine's factorization path when the wire is 16-bit",
            ))
    return findings


# ---------------------------------------------------------------------------
# S003 — donation proof
# ---------------------------------------------------------------------------

#: one `{out_idx}: (param_num, {param_idx}, kind)` entry of the optimized
#: HLO module's input_output_alias attribute
_ALIAS_ENTRY_RE = re.compile(
    r"\{[\d,\s]*\}:\s*\((\d+),\s*\{[^{}]*\},\s*(?:may|must)-alias\)"
)


def check_donation(
    compiled, args: tuple, donate_argnums: tuple, path: str
) -> list:
    """S003: every leaf of every donated argument appears in the compiled
    executable's input-output aliasing. Parameter numbers in the optimized
    HLO correspond to the flattened argument leaves in order."""
    import jax

    aliased = {int(p) for p in _ALIAS_ENTRY_RE.findall(compiled.as_text())}
    findings = []
    flat_index = 0
    for argnum, arg in enumerate(args):
        leaves = jax.tree_util.tree_flatten_with_path(arg)[0]
        for keypath, leaf in leaves:
            if argnum in tuple(donate_argnums) and flat_index not in aliased:
                kp = jax.tree_util.keystr(keypath)
                findings.append(Finding(
                    rule="S003", path=path, line=0, col=0,
                    message=(
                        f"donated buffer arg{argnum}{kp} "
                        f"({tuple(leaf.shape)} {leaf.dtype}) is NOT in the "
                        f"compiled executable's input-output aliasing — "
                        f"donation silently dropped, the epoch holds a "
                        f"second copy of this buffer"
                    ),
                    snippet=f"unaliased arg{argnum}{kp}",
                    fixit="give the donated leaf a same-shape/dtype output "
                          "to alias into (or stop donating it); see "
                          "trainer/steps.py donate_state",
                ))
            flat_index += 1
    return findings


# ---------------------------------------------------------------------------
# S005 — program-identity gate
# ---------------------------------------------------------------------------


def check_lowering_identity(pairs: list, path_prefix: str = "lowering://") -> list:
    """S005: each ``(label, text_a, text_b, expect_identical)`` pair is run
    through the normalized differ; an unexpected divergence (or an expected
    divergence that vanished — the opt-out no longer removes anything) is a
    finding."""
    from .lowering import diff_report

    findings = []
    for label, text_a, text_b, expect_identical in pairs:
        report = diff_report(text_a, text_b, "baseline", label)
        if expect_identical and report is not None:
            first = "\n".join(report.splitlines()[:6])
            findings.append(Finding(
                rule="S005", path=path_prefix + label, line=0, col=0,
                message=(
                    f"'{label}' must be lowering-identical to its baseline "
                    f"but diverges:\n{first}"
                ),
                snippet=f"divergent {label}",
                fixit="gate the feature behind a trace-time static branch "
                      "so the off-form compiles the exact baseline program "
                      "(the telemetry/quarantine_rounds pattern, "
                      "trainer/steps.py)",
            ))
        if not expect_identical and report is None:
            findings.append(Finding(
                rule="S005", path=path_prefix + label, line=0, col=0,
                message=(
                    f"'{label}' was expected to DIVERGE from its baseline "
                    f"but the programs are identical — the static opt-out "
                    f"no longer changes the compiled program (dead flag, "
                    f"or the machinery is no longer compiled out)"
                ),
                snippet=f"non-divergent {label}",
                fixit="check the trace-time gate (telemetry= / "
                      "quarantine_rounds) still switches the program form",
            ))
    return findings


# ---------------------------------------------------------------------------
# the trace matrix
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TraceCell:
    """One (engine, topology, pipeline) corner of the verification matrix."""

    engine: str
    # "vmap" (all sites on one device) | "mesh" (1 site/device) |
    # "fold" (2 packed/device) | "fold4" (4 packed/device — the deeper
    # site-packing corner, r12) | "sliced" (2 slices × 2 members, K=2 —
    # the r18 three-tier topology) | "sliced4" (2 slices × 2 members, K=4
    # — packed fold4 under slicing)
    topology: str
    pipeline: str  # "host" | "device"
    precision_bits: str = "32"
    donate: bool = False
    dense_model: bool = False  # non-compressible fallback workload
    engine_kw: tuple = ()  # sorted (key, value) engine kwargs
    # staleness_bound for the buffered-async aggregation mode (r13); 0 =
    # the bulk-sync program. Async cells verify that the buffered round's
    # collectives still carry exactly the modeled per-device wire (S002) —
    # buffering happens in registers/HBM, never on the wire.
    staleness: int = 0
    # wire codec (r14, parallel/collectives.py WireCodec): quantized-wire
    # cells verify S002's byte proof resolves the quant→collective→dequant
    # chain to the codec itemsize (the ~4x shrink, proven not modeled) and
    # S004 does not read the dequantized f32 operand as an upcast.
    wire_quant: str = "none"
    # overlapped-rounds mode (r14): the double-buffered stash round —
    # overlap cells verify the stash apply ships the SAME per-device wire
    # as the legacy round and keeps every collective inside the scan
    overlap: bool = False
    # byzantine-robust aggregation mode (r17, parallel/collectives.py
    # ROBUST_AGGS): robust cells verify the robust-mode wire models — the
    # gather-based reducers' genuinely pack-scaling per-site payload
    # gathers, and norm_clip's unchanged psum wire plus its two tiny
    # bookkeeping gathers — against the traced program, plus S001 (the
    # reputation layer's scalar psums stay inside the scan)
    robust: str = "none"
    # inter-slice (DCN) wire codec for the sliced topologies (r18,
    # TrainConfig.dcn_wire_quant semantics: "" follows wire_quant). Sliced
    # cells verify the per-TIER wire models: S002's ICI proof ignores
    # slice-only collectives, and the DCN-tier check proves the engine's
    # dcn_wire_shapes against exactly the collectives that touch the slice
    # axis — so "the expensive hop carries one codec-quantized per-slice
    # partial per round" is a traced property, not a modeled one.
    dcn_quant: str = ""
    # slice-fault cells (r19, robustness/faults.py slice windows): feed the
    # [num_slices, rounds] slice-liveness mask (with a dead-slice round)
    # and build with a min_slices=2 quorum — the wire rules must hold
    # UNCHANGED ("engines unchanged under masking"): the mask rides a
    # replicated input and local reductions, zero new collectives, so
    # S002's ICI proof and the DCN-tier check verify the same figures as
    # the fault-free sliced cells
    slice_faults: bool = False
    # r20 privacy plane: extra make_train_epoch_fn kwargs for cells whose
    # machinery lives in the epoch BUILDER rather than the engine
    # (dp_clip / dp_noise_multiplier / personalize) — sorted (key, value)
    # pairs like engine_kw; the personalize patterns also thread into the
    # cell's state init (per-site head rows) and shrink the wire template
    # to the shared subtree
    epoch_kw: tuple = ()
    # free-form label suffix for cells distinguished only by engine_kw
    # (e.g. "+overlap" for the overlapped-rounds corners) — labels key
    # the semantic baseline, so they must stay unique per cell
    tag: str = ""

    @property
    def label(self) -> str:
        name = self.engine
        if self.dense_model:
            name += "-dense"
        if self.precision_bits != "32":
            name += f"@{self.precision_bits}"
        if self.wire_quant != "none":
            name += f"@{self.wire_quant}"
        if self.dcn_quant:
            name += f"@dcn-{self.dcn_quant}"
        if self.donate:
            name += "+donate"
        if self.staleness:
            name += f"+async{self.staleness}"
        if self.robust != "none":
            name += f"+{self.robust}"
        if self.slice_faults:
            name += "+slfault"
        name += self.tag
        return f"{name}/{self.topology}/{self.pipeline}"

    @property
    def sliced(self) -> bool:
        return self.topology.startswith("sliced")


@dataclasses.dataclass
class CellProgram:
    """A traced matrix cell plus everything the rules consume."""

    cell: TraceCell
    engine: object
    state: object
    args: tuple
    block: int  # k sites folded per device (vmap: all of them)
    audit: ProgramAudit
    compiled: object  # only for donate cells
    path: str
    # the r18 sliced topology, derived from the cell's ACTUAL mesh (never
    # hardcoded by the rule driver): 1 / 0 on unsliced cells
    slices: int = 1
    sites_per_slice: int = 0
    # the params template the wire models charge (r20): the SHARED subtree
    # on personalized cells — head leaves never ship, so charging them
    # would make S002's proof vacuous — the full tree otherwise
    wire_template: object = None


def build_cell_inputs(cell: TraceCell, engine=None) -> tuple:
    """``(task, engine, opt, state, args, mesh)`` for one matrix cell — the
    ONE place the tiny CPU corner (model dims, shapes, RNG seeds) is
    defined. :func:`trace_cell`, the S005 identity gate and the tier-1
    identity harness (tests/test_lowering_identity.py) all build from here,
    so a change to the epoch signature or the corner's shapes is made once.
    ``engine`` overrides the registry engine — the hook test fixtures use it
    to trace deliberately-broken engines."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..engines import make_engine
    from ..models import MSANNet
    from ..parallel.mesh import host_mesh, sliced_site_mesh
    from ..trainer.steps import (
        FederatedTask,
        init_train_state,
        make_optimizer,
    )

    S = {"fold": 4, "fold4": 8, "sliced": 8, "sliced4": 16}.get(
        cell.topology, 2
    )
    steps, B, N = 2, 4, 8
    if cell.dense_model:
        # every leaf non-compressible ([1, 2] kernel + bias): the low-rank
        # engines' dense fallback path carries the whole wire
        model = MSANNet(in_size=1, hidden_sizes=(), out_size=2)
    else:
        model = MSANNet(in_size=6, hidden_sizes=(8,), out_size=2)
    D = model.in_size
    task = FederatedTask(model)
    if engine is None:
        engine = make_engine(
            cell.engine, precision_bits=cell.precision_bits,
            wire_quant=cell.wire_quant, robust_agg=cell.robust,
            dcn_wire_quant=cell.dcn_quant,
            **dict(cell.engine_kw),
        )
    opt = make_optimizer("adam", 1e-2)
    if cell.topology in ("mesh", "fold", "fold4"):
        mesh = host_mesh(2)
    elif cell.sliced:
        # the r18 three-tier corner: 2 slices × 2 site members over 4 CPU
        # devices, with K = S/4 virtual sites packed per member
        mesh = sliced_site_mesh(2, S // 2, S // 4)
    else:
        mesh = None
    state = init_train_state(
        task, engine, opt, jax.random.PRNGKey(0),
        jnp.ones((B, D), jnp.float32), num_sites=S,
        staleness_bound=cell.staleness,
        overlap_rounds=cell.overlap,
        reputation=cell.robust != "none",
        personalize=dict(cell.epoch_kw).get("personalize", ()),
    )
    rng = np.random.default_rng(0)
    if cell.pipeline == "device":
        args = (
            state,
            jnp.asarray(rng.normal(size=(S, N, D)).astype(np.float32)),
            jnp.zeros((S, N), jnp.int32),
            jnp.zeros((S, steps, B), jnp.int32),
        )
    else:
        args = (
            state,
            jnp.asarray(rng.normal(size=(S, steps, B, D)).astype(np.float32)),
            jnp.zeros((S, steps, B), jnp.int32),
            jnp.ones((S, steps, B), jnp.float32),
        )
    if cell.slice_faults:
        # the r19 slice-liveness input: [num_slices, rounds] with slice 1
        # dead in round 0 — fed after the positional optional inputs
        # (live / [poison] / attack), which ride as empty-pytree Nones
        slice_mask = jnp.asarray([[1.0, 1.0], [0.0, 1.0]], jnp.float32)
        pad = (None, None, None) if cell.pipeline == "device" else (None, None)
        args = args + pad + (slice_mask,)
    return task, engine, opt, state, args, mesh


def trace_cell(cell: TraceCell, engine=None) -> CellProgram:
    """Build and trace one matrix cell's REAL epoch program (tiny shapes,
    CPU)."""
    from ..parallel.mesh import pack_factor
    from ..trainer.steps import epoch_program_artifacts, make_train_epoch_fn

    task, engine, opt, state, args, mesh = build_cell_inputs(cell, engine)
    fn = make_train_epoch_fn(
        task, engine, opt, mesh=mesh, pipeline=cell.pipeline,
        donate_state=cell.donate, staleness_bound=cell.staleness,
        overlap_rounds=cell.overlap, robust_agg=cell.robust,
        # slice-fault cells trace the FULL r19 machinery (mask gate +
        # quorum hold) so the wire proofs cover it
        min_slices=2 if cell.slice_faults else 1,
        # privacy-plane cells (r20): dp / personalize live in the builder
        **dict(cell.epoch_kw),
    )
    closed, _, comp = epoch_program_artifacts(fn, *args, compiled=cell.donate)
    S = args[1].shape[0]
    block = S if mesh is None else pack_factor(mesh, S)
    from ..parallel.mesh import slice_count

    slices = slice_count(mesh)
    # personalized cells charge the SHARED subtree only — exactly what the
    # traced program ships (trainer/steps.py _eng_grads)
    wire_tmpl = state.params
    pers = dict(cell.epoch_kw).get("personalize", ())
    if pers:
        from ..privacy.personalize import head_leaf_paths, strip_tree

        wire_tmpl = strip_tree(
            state.params, head_leaf_paths(state.params, pers),
            keep_head=False,
        )
    return CellProgram(
        cell=cell, engine=engine, state=state, args=args, block=block,
        audit=audit_jaxpr(closed), compiled=comp,
        path=f"trace://{cell.label}",
        slices=slices,
        sites_per_slice=S // slices if slices > 1 else 0,
        wire_template=wire_tmpl,
    )


#: engine corners: the three registry engines plus the low-rank engines'
#: non-compressible fallback (the "fourth engine" — same registry entry,
#: dense-only workload, entirely different wire)
_ENGINE_CORNERS = (
    ("dSGD", (), False),
    ("rankDAD", (("dad_num_pow_iters", 2), ("dad_reduction_rank", 2)), False),
    ("powerSGD", (("dad_reduction_rank", 2),), False),
    ("rankDAD", (("dad_reduction_rank", 4),), True),
)


def default_matrix() -> list:
    """The full engine × topology × pipeline matrix plus the precision-flow
    and donation-audit corners."""
    cells = [
        TraceCell(name, topo, pipe, engine_kw=kw, dense_model=dense)
        for name, kw, dense in _ENGINE_CORNERS
        for topo in ("vmap", "mesh", "fold")
        for pipe in ("host", "device")
    ]
    # bf16 wire: S002's byte proof must survive quantization and S004 must
    # see the low-precision dots
    cells += [
        TraceCell(name, "mesh", "host", precision_bits="16", engine_kw=kw)
        for name, kw, dense in _ENGINE_CORNERS
        if not dense
    ]
    # deeper site packing (K=4/device, r12): the per-device wire proof at a
    # pack factor where a per-site model would be 4x wrong — the K-scaling
    # factor gather (rankDAD), the K-invariant psum wire (dSGD, device
    # pipeline), and the quantized packed partial (bf16 dSGD)
    cells += [
        TraceCell(
            "rankDAD", "fold4", "host",
            engine_kw=(("dad_num_pow_iters", 2), ("dad_reduction_rank", 2)),
        ),
        TraceCell("dSGD", "fold4", "device"),
        TraceCell("dSGD", "fold4", "host", precision_bits="16"),
    ]
    # buffered-async cells (r13): every engine corner under the staleness
    # mode on a real mesh — S001 (buffer selects stay inside the scan, no
    # stray collectives) and S002 (the buffered round's wire is EXACTLY the
    # bulk-sync wire: buffering spends HBM, never bytes) — plus a packed
    # async corner (per-device buffers on the [K] block) and an async
    # donation proof (the buffer leaves must alias like every other carried
    # state, or async mode silently doubles a params-sized residency)
    cells += [
        TraceCell(name, "mesh", "host", engine_kw=kw, dense_model=dense,
                  staleness=2)
        for name, kw, dense in _ENGINE_CORNERS
    ]
    cells += [
        TraceCell("dSGD", "fold", "device", staleness=2),
        TraceCell("dSGD", "vmap", "device", donate=True, staleness=2),
    ]
    # donation proof: compiled executables for the trainer's real default
    # (device pipeline + donated state) on both topologies
    cells += [
        TraceCell("dSGD", "vmap", "device", donate=True),
        TraceCell(
            "powerSGD", "mesh", "device", donate=True,
            engine_kw=(("dad_reduction_rank", 2),),
        ),
    ]
    # quantized wires (r14): int8 across the engine corners plus fp8, the
    # stochastic-rounding chain, and a packed-partial re-quantization cell —
    # S002 must prove the codec-itemsize bytes against the traced
    # quant→collective→dequant chain, and S004 must not read the
    # dequantized f32 operand as an upcast
    cells += [
        TraceCell(name, "mesh", "host", engine_kw=kw, wire_quant="int8")
        for name, kw, dense in _ENGINE_CORNERS
        if not dense
    ]
    cells += [
        TraceCell("dSGD", "mesh", "host", wire_quant="fp8"),
        TraceCell(
            "rankDAD", "mesh", "host", wire_quant="fp8",
            engine_kw=(("dad_num_pow_iters", 2), ("dad_reduction_rank", 2)),
        ),
        TraceCell("dSGD", "fold4", "device", wire_quant="int8"),
        TraceCell(
            "dSGD", "mesh", "host", wire_quant="int8",
            engine_kw=(("wire_stochastic", True),), tag="+sr",
        ),
        # overlapped rounds (r14): the stash apply's collectives are the
        # SAME wire as the legacy round (S002), and the stash selects stay
        # inside the rounds scan (S001)
        TraceCell("dSGD", "mesh", "host", tag="+overlap", overlap=True),
        TraceCell("dSGD", "fold", "device", tag="+overlap", overlap=True),
        # the stash must alias like every other carried state under
        # donation, or overlap mode silently doubles a grads-sized residency
        TraceCell("dSGD", "vmap", "device", donate=True, overlap=True,
                  tag="+overlap"),
    ]
    # byzantine-robust aggregation (r17): the robust-mode wire models proved
    # against the traced programs — the gather reducers' genuinely
    # pack-scaling per-site payload gathers (S002 on packed AND unpacked
    # cells: a pack-unaware robust model would be 4x wrong on fold4),
    # norm_clip's unchanged psum wire + two tiny bookkeeping gathers
    # (composing with the int8 codec), rankDAD's factor gather unchanged
    # with only the dense half switching to gathers, and powerSGD's factor
    # psums becoming factor gathers. The reputation layer's scalar psums
    # must stay inside the rounds scan (S001) on every robust cell.
    cells += [
        TraceCell("dSGD", "mesh", "host", robust="trimmed_mean"),
        TraceCell("dSGD", "fold4", "device", robust="trimmed_mean"),
        TraceCell("dSGD", "mesh", "host", robust="norm_clip"),
        TraceCell("dSGD", "mesh", "host", robust="norm_clip",
                  wire_quant="int8"),
        TraceCell(
            "rankDAD", "mesh", "host", robust="coordinate_median",
            engine_kw=(("dad_num_pow_iters", 2), ("dad_reduction_rank", 2)),
        ),
        TraceCell(
            "rankDAD", "fold4", "host", robust="coordinate_median",
            engine_kw=(("dad_num_pow_iters", 2), ("dad_reduction_rank", 2)),
        ),
        TraceCell(
            "powerSGD", "mesh", "host", robust="trimmed_mean",
            engine_kw=(("dad_reduction_rank", 2),),
        ),
    ]
    # multi-slice cells (r18): the three-tier topology across the engine
    # corners — the per-TIER wire proofs. The fused (no DCN codec) form
    # must show the ICI model unchanged with the (slice, site) reduces
    # covering the DCN model at the intra wire dtype; the int8-DCN split
    # cells must show slice-ONLY collectives carrying exactly one
    # codec-quantized per-slice partial per payload (dSGD: the whole tree
    # as ONE fused vector) at ≤ ¼ the f32 bytes — proven against traced
    # operand shapes, incl. the packed K=4 corner (sliced4) where a
    # per-device-charged DCN model would be 4x wrong.
    cells += [
        TraceCell(name, "sliced", "host", engine_kw=kw, dense_model=dense)
        for name, kw, dense in _ENGINE_CORNERS
    ]
    cells += [
        TraceCell("dSGD", "sliced", "host", dcn_quant="int8"),
        TraceCell("dSGD", "sliced4", "device", wire_quant="int8",
                  dcn_quant="int8"),
        TraceCell(
            "rankDAD", "sliced4", "host", wire_quant="int8",
            dcn_quant="int8",
            engine_kw=(("dad_num_pow_iters", 2), ("dad_reduction_rank", 2)),
        ),
        TraceCell(
            "powerSGD", "sliced", "host", dcn_quant="int8",
            engine_kw=(("dad_reduction_rank", 2),),
        ),
        # robust × sliced (the review corner): the gather reducers' dense
        # payload must cross the slice hop DCN-re-quantized exactly as the
        # engines' dcn models charge it — the powerSGD dense-gather path
        # shipped f32 across DCN against an int8 model until this cell
        TraceCell(
            "powerSGD", "sliced", "host", dcn_quant="int8",
            robust="trimmed_mean", engine_kw=(("dad_reduction_rank", 2),),
        ),
        TraceCell("dSGD", "sliced", "host", dcn_quant="int8",
                  robust="norm_clip"),
    ]
    # slice-fault cells (r19): the slice-liveness mask + min_slices=2
    # quorum in the traced program — "engines unchanged under masking":
    # S002's ICI figures and the DCN-tier proof must verify the SAME wire
    # as the fault-free sliced cells (the mask is a replicated input and
    # local reductions, zero new collectives), incl. the packed
    # int8-both-tiers corner and the device pipeline.
    cells += [
        TraceCell(name, "sliced", "host", engine_kw=kw, slice_faults=True)
        for name, kw, dense in _ENGINE_CORNERS
        if not dense
    ]
    cells += [
        TraceCell("dSGD", "sliced4", "device", wire_quant="int8",
                  dcn_quant="int8", slice_faults=True),
    ]
    # secure-aggregation masked wires (r20, privacy/secure_agg.py): S002
    # must prove the int32 grid model — the SAME dense shapes as the legacy
    # psum at 4 B/element, the masked partial K-invariant under packing —
    # against the traced padded program (the per-leaf amax pmax scalars are
    # genuine collectives but carry () operands, outside payload
    # accounting), S001 must keep the whole pad→psum chain inside the
    # rounds scan, and the sliced cell must show the fused exact
    # (slice, site) int32 reduce covering the DCN model with no
    # slice-boundary re-quantization.
    cells += [
        TraceCell("dSGD", "mesh", "host",
                  engine_kw=(("secure_agg", "mask"),), tag="+secureagg"),
        TraceCell("dSGD", "fold4", "device",
                  engine_kw=(("secure_agg", "mask"),), tag="+secureagg"),
        TraceCell("dSGD", "vmap", "device", donate=True,
                  engine_kw=(("secure_agg", "mask"),), tag="+secureagg"),
        TraceCell("dSGD", "sliced", "host",
                  engine_kw=(("secure_agg", "mask"),), tag="+secureagg"),
    ]
    # DP-SGD + personalized heads (r20): the mechanism/partition live in
    # the epoch builder, not the engine — their wire impact is proven on
    # dedicated cells below via epoch_kw (dp adds ZERO collectives; the
    # personalized cell's wire model covers the SHARED subtree only)
    cells += [
        TraceCell("dSGD", "fold4", "device", tag="+dp",
                  epoch_kw=(("dp_clip", 1.0),
                            ("dp_noise_multiplier", 0.5))),
        TraceCell("dSGD", "mesh", "host", tag="+personal",
                  epoch_kw=(("personalize", ("fc_out",)),)),
    ]
    return cells


#: the S005 identity pairs, declaratively: label -> (epoch-build kwargs,
#: expect_identical). Off-forms (True) must compile the exact baseline
#: program; opt-outs/opt-ins (False) must genuinely change it — if those
#: stop diverging, "compiled out" has silently stopped being true. ``None``
#: kwargs means the DEFAULT build traced under ``jax.checking_leaks`` (the
#: sanitizer's observation mode, which must not perturb what it observes).
#: tests/test_lowering_identity.py is the tier-1 mirror of exactly this
#: table — extend it here and both the CLI gate and the tests pick it up.
IDENTITY_CASES = {
    "telemetry-off": (dict(telemetry=False), True),
    "faults-default": (dict(quarantine_rounds=3), True),
    "sanitize-leaks": (None, True),
    "faults-opt-out": (dict(quarantine_rounds=-1), False),
    "telemetry-on": (dict(telemetry=True), False),
    # elastic rounds (r13): staleness_bound=0 must compile the EXACT
    # bulk-sync program (the async machinery statically out), and a positive
    # bound must genuinely add the buffered round
    "async-off": (dict(staleness_bound=0), True),
    "async-on": (dict(staleness_bound=2), False),
    # quantized wires (r14): wire_quant="none" must keep the legacy
    # precision_bits program byte-for-byte, and each codec must genuinely
    # change the wire path. The reserved "engine" key rebuilds the corner's
    # engine with the given make_engine overrides (the knob lives in the
    # engine, not the epoch builder).
    "wirequant-off": (dict(engine=dict(wire_quant="none")), True),
    "wirequant-bf16": (dict(engine=dict(wire_quant="bf16")), False),
    "wirequant-int8": (dict(engine=dict(wire_quant="int8")), False),
    # overlapped rounds (r14): off = the exact legacy round, on = the
    # double-buffered stash apply genuinely in the program
    "overlap-off": (dict(overlap_rounds=False), True),
    "overlap-on": (dict(overlap_rounds=True), False),
    # byzantine-robust aggregation (r17): robust_agg="none" must compile the
    # EXACT legacy program (engine AND epoch builder both off — the
    # acceptance gate), and each robust mode must genuinely change it (the
    # inverse divergence gate: if the gather reducers / norm clip / the
    # reputation layer stop appearing, "robust" has silently become a no-op)
    "robust-off": (
        dict(robust_agg="none", engine=dict(robust_agg="none")), True,
    ),
    "robust-trimmed": (
        dict(robust_agg="trimmed_mean",
             engine=dict(robust_agg="trimmed_mean")),
        False,
    ),
    "robust-normclip": (
        dict(robust_agg="norm_clip", engine=dict(robust_agg="norm_clip")),
        False,
    ),
    # privacy plane (r20): every off-form must compile the EXACT legacy
    # program — dp_clip=dp_noise_multiplier=0 (privacy/dpsgd.py),
    # secure_agg="off" (privacy/secure_agg.py, an engine knob) and
    # personalize=() (privacy/personalize.py) — and each on-form must
    # genuinely inject its machinery (the inverse gate: a dp-on program
    # that stops diverging is a mechanism that silently stopped running,
    # and every ε it reports is a lie)
    "dp-off": (dict(dp_clip=0.0, dp_noise_multiplier=0.0), True),
    "dp-on": (dict(dp_clip=1.0, dp_noise_multiplier=0.5), False),
    "dp-clip-only": (dict(dp_clip=1.0), False),
    "secureagg-off": (dict(engine=dict(secure_agg="off")), True),
    "secureagg-on": (dict(engine=dict(secure_agg="mask")), False),
    "personalize-off": (dict(personalize=()), True),
    "personalize-on": (dict(personalize=("fc_out",)), False),
}

def identity_text_fn(cell: TraceCell):
    """``text(**case_kw)`` builder for one identity corner — the ONE
    implementation behind the S005 CLI gate and the tier-1 mirror
    (tests/test_lowering_identity.py). ``case_kw`` may carry the reserved
    ``engine`` key: a dict of ``make_engine`` overrides layered onto the
    cell's engine kwargs (for knobs that live in the engine — wire_quant,
    secure_agg)."""
    from ..engines import make_engine
    from ..trainer.steps import make_train_epoch_fn

    task, engine, opt, _, args, mesh = build_cell_inputs(cell)

    def text(**kw):
        kw = dict(kw)
        eng_kw = kw.pop("engine", None)
        eng = engine
        if eng_kw:
            eng = make_engine(
                cell.engine, precision_bits=cell.precision_bits,
                **{"wire_quant": cell.wire_quant,
                   **dict(cell.engine_kw), **eng_kw},
            )
        fn = make_train_epoch_fn(task, eng, opt, mesh=mesh, **kw)
        return fn.lower(*args).as_text()

    return text


def slices_identity_pairs() -> list:
    """The r18 S005 pairs, as ``(label, text_a, text_b, expect_identical)``:

    - ``slices-off`` — the ``num_slices=1`` opt-out must lower the EXACT
      legacy single-mesh program (sliced_site_mesh(1, ...) collapses to
      packed_site_mesh; if it ever starts building a 1-deep slice axis
      instead, this gate trips before any perf number does);
    - ``slices-on`` — the sliced topology must genuinely change the program
      (the inverse gate: a "sliced" mesh that silently flattens back would
      make every multi-slice claim vacuous);
    - ``slices-dcn-int8`` — the DCN codec must genuinely split the
      inter-slice hop (re-quantized slice-only collectives in the program)
      vs the fused no-codec form;
    - ``slicefaults-off`` (r19) — a sliced epoch built WITH a min_slices
      quorum but fed NO slice mask must lower the exact r18 sliced program
      (the slice-fault machinery gates on the mask's presence, not the
      config knob — all-slices-live IS the PR 13 program);
    - ``slicefaults-on`` (r19) — feeding the slice mask must genuinely
      change the program (the inverse gate: if the gate/hold ops stop
      appearing, slice faults have silently become a no-op).

    Shared by the CLI S005 gate and the tier-1 mirror
    (tests/test_multislice.py)."""
    import jax.numpy as jnp
    import numpy as np

    from ..engines import make_engine
    from ..models import MSANNet
    from ..parallel.mesh import packed_site_mesh, sliced_site_mesh
    from ..trainer.steps import (
        FederatedTask,
        init_train_state,
        make_optimizer,
        make_train_epoch_fn,
    )

    import jax

    S, steps, B, D = 8, 2, 4, 6
    model = MSANNet(in_size=D, hidden_sizes=(8,), out_size=2)
    task = FederatedTask(model)
    opt = make_optimizer("adam", 1e-2)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(S, steps, B, D)).astype(np.float32))
    y = jnp.zeros((S, steps, B), jnp.int32)
    w = jnp.ones((S, steps, B), jnp.float32)

    def text(mesh, slice_live=None, min_slices=1, **engine_kw):
        engine = make_engine("dSGD", **engine_kw)
        state = init_train_state(
            task, engine, opt, jax.random.PRNGKey(0),
            jnp.ones((B, D), jnp.float32), num_sites=S,
        )
        fn = make_train_epoch_fn(
            task, engine, opt, mesh=mesh, min_slices=min_slices
        )
        if slice_live is None:
            return fn.lower(state, x, y, w).as_text()
        return fn.lower(state, x, y, w, None, None, slice_live).as_text()

    legacy = text(packed_site_mesh(S, 2))
    off = text(sliced_site_mesh(1, S, 2))
    sliced = text(sliced_site_mesh(2, S // 2, 2))
    sliced_dcn = text(
        sliced_site_mesh(2, S // 2, 2), dcn_wire_quant="int8"
    )
    # r19: the slice-fault gate keys on the MASK input, not the quorum knob
    mask = jnp.asarray(np.array([[1.0, 1.0], [0.0, 1.0]], np.float32))
    slfault_off = text(sliced_site_mesh(2, S // 2, 2), min_slices=2)
    slfault_on = text(
        sliced_site_mesh(2, S // 2, 2), slice_live=mask, min_slices=2
    )
    return [
        ("slices-off", legacy, off, True),
        ("slices-on", legacy, sliced, False),
        ("slices-dcn-int8", sliced, sliced_dcn, False),
        ("slicefaults-off", sliced, slfault_off, True),
        ("slicefaults-on", sliced, slfault_on, False),
    ]


def _identity_gate() -> list:
    """The S005 program-identity pairs (:data:`IDENTITY_CASES` on the
    flagship dSGD corner, plus the r18 multi-slice pairs)."""
    import jax

    pairs = []
    text = identity_text_fn(TraceCell("dSGD", "vmap", "host"))
    base = text()
    for label, (kw, expect_identical) in IDENTITY_CASES.items():
        if kw is None:
            with jax.checking_leaks():
                variant = text()
        else:
            variant = text(**kw)
        pairs.append((label, base, variant, expect_identical))
    pairs += slices_identity_pairs()
    return check_lowering_identity(pairs)


# ---------------------------------------------------------------------------
# serving cells (r15)
# ---------------------------------------------------------------------------


def check_no_collectives(collectives: list, path: str) -> list:
    """S001, serving form: the REQUEST PATH must contain ZERO cross-device
    collectives — inference is replicated per device, and a stray psum in a
    serving program would stall every request on every other device's
    traffic (the training rule merely confines collectives to the rounds
    scan; serving forbids them outright)."""
    findings = []
    for site in collectives:
        if site.prim not in COMM_PRIMS:
            continue
        findings.append(Finding(
            rule="S001", path=path, line=0, col=0,
            message=(
                f"serving request path contains a cross-device collective "
                f"'{site.prim}' (axes {site.named_axes or '(positional)'}) "
                f"— inference must be replicated, never synchronized"
            ),
            snippet=f"{site.prim} in-request-path",
            fixit="keep collectives out of eval_forward/ICALstmStream; "
                  "multi-device serving replicates the engine per device",
        ))
    return findings


def build_serving_cell():
    """The real serving programs on a tiny CPU corner: the engine's batched
    (``eval_forward``) and streaming (session gather→step→scatter) jitted
    entries, exactly as :class:`~..serving.engine.InferenceEngine` compiles
    them at warmup. Returns the engine plus per-lane ``(fn, args)``."""
    import jax
    import jax.numpy as jnp

    from ..core.config import NNComputation, TrainConfig
    from ..runner.registry import get_task
    from ..serving.engine import InferenceEngine
    from ..trainer.steps import FederatedTask

    cfg = TrainConfig(task_id=NNComputation.TASK_ICA).with_overrides({
        "ica_args": {
            "num_components": 3, "window_size": 4, "temporal_size": 32,
            "window_stride": 4, "input_size": 8, "hidden_size": 6,
            "bidirectional": False,
        },
    })
    task = FederatedTask(get_task(cfg.task_id).build_model(cfg))
    params, stats = task.init_variables(
        jax.random.PRNGKey(0), jnp.ones((2, 8, 3, 4))
    )
    engine = InferenceEngine(
        cfg, params=params, batch_stats=stats, row_buckets=(4,),
        stream_buckets=(2,), stream_chunk=4, stream_slots=4,
    )
    infer_args = (
        engine._params, engine._stats,
        jnp.zeros((4, 8, 3, 4), jnp.float32), jnp.ones((4,), jnp.float32),
    )
    stream_args = (
        engine._params, engine._stats, engine._table,
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.float32),
        jnp.zeros((2, 4, 3, 4), jnp.float32), jnp.ones((2, 4), jnp.float32),
        jnp.ones((2,), jnp.float32),
    )
    return engine, (engine._infer_jit, infer_args), (
        engine._stream_jit, stream_args
    )


def run_serving_checks() -> list:
    """The serving S-rule cells (r15): S001 zero collectives on both lanes,
    S003 the donated session-carry table fully aliases in the compiled
    streaming step, S005 the batched serving program is lowering-identical
    to the trainer's eval forward (the bit-exactness bridge as a program
    property, not just a test vector) — and the streaming program genuinely
    diverges from it (the differ is not trivially green)."""
    import jax

    from ..trainer.steps import epoch_program_artifacts, eval_forward

    findings: list = []
    engine, (infer_jit, infer_args), (stream_jit, stream_args) = (
        build_serving_cell()
    )
    infer_jaxpr, infer_low, _ = epoch_program_artifacts(
        infer_jit, *infer_args, lowered=True
    )
    findings += check_no_collectives(
        audit_jaxpr(infer_jaxpr).collectives, "trace://serving/infer"
    )
    stream_jaxpr, stream_low, stream_comp = epoch_program_artifacts(
        stream_jit, *stream_args, lowered=True, compiled=True
    )
    findings += check_no_collectives(
        audit_jaxpr(stream_jaxpr).collectives, "trace://serving/stream"
    )
    # S003: the session-carry table (stream arg 2, donated) must alias into
    # the returned table — the in-place O(1) session cache claim
    findings += check_donation(
        stream_comp, stream_args, (2,), "trace://serving/stream"
    )
    # S005: the batched lane IS the eval forward — prove it at the lowering
    # level against an independently-built reference program
    task = engine.task
    ref = jax.jit(
        lambda p, s, x, w: eval_forward(task, p, s, x, None, w)
    ).lower(*infer_args).as_text()
    findings += check_lowering_identity(
        [
            ("serve-infer-is-eval-forward", ref, infer_low.as_text(), True),
            ("serve-stream-diverges", ref, stream_low.as_text(), False),
        ],
        path_prefix="lowering://serving/",
    )
    # S003, publish plane (r21): the hot-swap graft must alias EVERY
    # params and batch-stats leaf input→output — a publish is pure buffer
    # donation, so any unaliased leaf means the swap copies (and the
    # "pause is a graft, not a transfer" claim is false)
    swap_args = engine._live
    swap_comp = engine._swap_jit.lower(*swap_args).compile()
    findings += check_donation(
        swap_comp, swap_args, (0, 1), "trace://serving/swap"
    )
    # S001 on the same program: a collective in the swap graft would stall
    # every replica's publish on cross-device traffic
    swap_jaxpr, _, _ = epoch_program_artifacts(engine._swap_jit, *swap_args)
    findings += check_no_collectives(
        audit_jaxpr(swap_jaxpr).collectives, "trace://serving/swap"
    )
    return findings


def run_semantic_checks(cells=None) -> list:
    """Trace the matrix and run every S-rule; returns findings sorted like
    the AST tier's. The CLI gates on this list (after the semantic
    baseline); tests assert it is empty."""
    ensure_cpu_devices()
    findings: list = []
    for cell in (default_matrix() if cells is None else cells):
        prog = trace_cell(cell)
        allowed = None
        if cell.sliced:
            from ..parallel.mesh import MODEL_AXIS, SITE_AXIS, SLICE_AXIS

            allowed = {SITE_AXIS, MODEL_AXIS, SLICE_AXIS}
        findings += check_collective_axes(
            prog.audit.collectives, prog.path, allowed_axes=allowed
        )
        if cell.topology in ("mesh", "fold", "fold4") or cell.sliced:
            # the vmap topology folds all sites onto one device — its
            # "collectives" are local reductions with no wire, so the
            # byte/precision proofs run where communication is real
            import jax

            stats_shapes = tuple(
                tuple(leaf.shape)
                for leaf in jax.tree_util.tree_leaves(prog.state.batch_stats)
            )
            ici_colls = prog.audit.collectives
            if cell.sliced:
                # the ICI proof covers tiers 0+1: slice-ONLY collectives
                # are the DCN tier's (proven by check_dcn_wire below);
                # fused (slice, site) reduces still carry the per-device
                # payload the ICI model describes
                from ..parallel.mesh import SLICE_AXIS

                ici_colls = [
                    c for c in prog.audit.collectives
                    if tuple(c.named_axes) != (SLICE_AXIS,)
                ]
            findings += check_wire_bytes(
                ici_colls, prog.engine, prog.wire_template,
                prog.block, prog.path, stats_shapes=stats_shapes,
            )
            findings += check_precision_flow(
                ici_colls, prog.engine, prog.wire_template,
                prog.block, prog.path,
                require_lowp_dot=(
                    cell.precision_bits == "16"
                    and cell.engine in ("rankDAD", "powerSGD")
                    and not cell.dense_model
                ),
                dots=prog.audit.dots,
            )
            if cell.sliced:
                findings += check_dcn_wire(
                    prog.audit.collectives, prog.engine, prog.wire_template,
                    prog.block, prog.sites_per_slice, prog.path,
                    stats_shapes=stats_shapes, slices=prog.slices,
                )
        if cell.donate:
            findings += check_donation(
                prog.compiled, prog.args, (0,), prog.path
            )
    findings += _identity_gate()
    if cells is None:
        findings += run_serving_checks()
    findings.sort(key=lambda f: (f.path, f.rule, f.snippet))
    return findings
