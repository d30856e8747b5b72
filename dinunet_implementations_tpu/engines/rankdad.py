"""rankDAD — distributed-AD low-rank gradient compression.

Reference capability (``comps/__init__.py:15``; knobs
``compspec.json:236-238``; measured run ``nnlogs.ipynb`` cell 2): each site
compresses its per-layer gradient to rank-r factors via power iteration and
ships factors instead of full gradients; the aggregate is the weighted mean of
the sites' rank-r reconstructions.

TPU shape of the exchange (SURVEY.md §2.2): ``all_gather`` of the
``[m, r]``/``[n, r]`` factors over the ``site`` axis — comm volume
``r·(m+n)`` per site instead of ``m·n`` — followed by one batched einsum
reconstruction, which XLA maps straight onto the MXU. 1-D leaves (biases, BN
scales) are aggregated densely like dSGD.

Perf structure (r6 — the rankDAD-32 gap work):

- **Warm-started subspaces**: per-leaf Ω ``[n, r]`` persists in the engine
  state (the same per-site threading powerSGD's Q/error-feedback uses,
  ``trainer/steps.py``) and seeds the next round's power iteration with the
  previous round's right factor. Adjacent rounds' gradients share most of
  their top-r subspace, so the tol-based early exit fires after 1-2
  refinements instead of ``dad_num_pow_iters`` — the knob becomes a cap, not
  a cost. At ``init`` Ω holds the cold-start default draw
  (``lowrank.default_omega``), making round one bit-identical to a cold
  start. ``dad_warm_start=False`` restores stateless behavior.
- **Mixed-precision power iteration**: ``precision_bits="16"`` (the bf16
  wire) also runs the large ``G@Ω``/``GᵀP``/``G(GᵀP)`` products as
  bf16×bf16→f32 MXU contractions; the tiny ``[r, r]`` Gram/Cholesky stays
  f32 (``lowrank.lp_matmul``). ``"16-ieee"`` keeps f32 math — it exists for
  bit-compat with the reference's fp16 wire, not for speed.
- **One while_loop, one gather**: all effective-rank classes factorize in a
  single shared ``lax.while_loop`` (``lowrank.subspace_iteration_grouped``;
  one loop per class serialized on-device), and each class's factors ship in
  ONE packed ``all_gather`` (``collectives.site_all_gather_packed``) instead
  of two launches per leaf.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..parallel.collectives import (
    ROBUST_AGGS,
    PackedAxis,
    clip_site_gradients,
    payload_dtype,
    resolve_dcn_codec,
    resolve_wire_codec,
    robust_site_reduce,
    site_all_gather,
    site_all_gather_packed,
    site_weight_scale,
    weighted_site_sum,
)
from .base import (
    Engine,
    mask_dead_site,
    register_engine,
    robust_gather_dcn_wire,
    robust_gather_wire,
    wire_shapes_bytes,
)
from .lowrank import (
    default_omega,
    from_matrix,
    is_compressible,
    lowrank_rank_groups,
    lowrank_wire_bytes,
    subspace_iteration_grouped,
    to_matrix,
)


@register_engine("rankDAD")
def make_rankdad(
    dad_reduction_rank: int = 10,
    dad_num_pow_iters: int = 5,
    dad_tol: float = 1e-3,
    precision_bits="32",
    dad_warm_start: bool = True,
    wire_quant="none",
    wire_stochastic=False,
    robust_agg="none",
    robust_trim_frac=0.2,
    robust_clip_mult=2.5,
    dcn_wire_quant="",
    secure_agg="off",
    **_unused,
) -> Engine:
    # secure-aggregation masked wires (r20) are a dense-psum construct:
    # this engine ships low-rank factor GATHERS — per-site payloads in the
    # clear by design — so the mode is refused, not silently ignored
    # (privacy/secure_agg.py; dSGD is the masked-wire engine)
    from ..privacy.secure_agg import secure_agg_enabled

    if secure_agg_enabled(secure_agg):
        raise ValueError(
            f"secure_agg={secure_agg!r} is only supported by the dSGD "
            "engine: the low-rank engines gather per-site factors, which "
            "a masked psum wire cannot carry"
        )
    if robust_agg not in ROBUST_AGGS:
        raise ValueError(
            f"robust_agg must be one of {ROBUST_AGGS}, got {robust_agg!r}"
        )
    # robust gather modes (r17): the factor gather ALREADY ships every
    # virtual site's payload, so the robust reduce costs no factor-wire
    # change — only the dense 1-D leaves switch from psum to gather and the
    # weight vector is gathered for the weighted trim/median
    gather_mode = robust_agg in ("trimmed_mean", "coordinate_median")
    pdtype = payload_dtype(precision_bits)
    # bf16 wire ⇒ bf16 power-iteration matmuls (see module docstring);
    # "16-ieee"/"32" keep f32 math.
    mm_dtype = jnp.bfloat16 if pdtype == jnp.bfloat16 else None
    # quantized wire (r14): the gathered P/Q factor blocks round-trip the
    # codec grid (scale per factor, per virtual-site row under packing)
    # before the all_gather; "none" keeps the legacy precision_bits cast
    # byte-for-byte (S005-gated). The matmul precision stays governed by
    # precision_bits — wire and compute knobs compose.
    codec = resolve_wire_codec(precision_bits, wire_quant, wire_stochastic)
    import numpy as np

    wdtype = np.dtype(codec.dtype)
    # the inter-slice codec (r18): the per-slice factor block re-quantizes
    # (scale per virtual-site row) before the DCN gather hop, and the dense
    # 1-D partials before their slice psum; None = the fused form
    dcn = resolve_dcn_codec(
        precision_bits, wire_quant, dcn_wire_quant, wire_stochastic
    )
    ddtype = np.dtype(dcn.dtype) if dcn is not None else None

    def _effective_rank(g) -> int:
        # shape arithmetic only (g may be a ShapeDtypeStruct row template on
        # the packed path)
        from .lowrank import _matrix_shape

        m, n = _matrix_shape(g)
        return min(dad_reduction_rank, m, n)

    def init(grads):
        if not dad_warm_start:
            return {}
        leaves, treedef = jax.tree.flatten(grads)
        # Ω starts as the cold-start default draw, so the first warm round is
        # bit-identical to a cold start; None for dense (1-D) leaves, exactly
        # like powerSGD's q/e state layout.
        oms = [
            default_omega(to_matrix(g), _effective_rank(g))
            if is_compressible(g) else None
            for g in leaves
        ]
        return {"omega": jax.tree.unflatten(treedef, oms)}

    def wire_bytes(grads, pack: int = 1) -> int:
        # factor exchange per compressible leaf: P + Q in the payload dtype
        # (one packed gather per rank class — same bytes); shared low-rank
        # payload model (engines/lowrank.py lowrank_wire_bytes). The gather
        # half scales with the site-packing factor K (every virtual site's
        # factors genuinely cross the wire); the dense 1-D psum half reduces
        # locally over the pack axis first and is K-invariant. Bytes follow
        # the WIRE dtype (codec grid), not the compute dtype — int8/fp8
        # wires model (and S002 proves) the 4x shrink.
        import math

        extras = sum(
            math.prod(s) * d.itemsize
            for s, d in robust_gather_wire(pack, robust_agg)
        )
        return lowrank_wire_bytes(
            grads, dad_reduction_rank, wdtype.itemsize, pack=pack,
            dense_pack=pack if gather_mode else 1,
        ) + extras

    def wire_shapes(grads, pack: int = 1):
        # what `aggregate` actually launches per round per device: ONE packed
        # all_gather per rank class — the device's [pack, Σ(m_i+n_i), r]
        # virtual-site factor block at the payload dtype — plus a dense f32
        # psum per 1-D leaf (pack-invariant: two-level reduced). Must sum to
        # wire_bytes (verified by S002) at every pack factor.
        import numpy as np

        groups, dense = lowrank_rank_groups(grads, dad_reduction_rank)
        shapes = [
            ((pack, sum(m + n for m, n in mns), r), wdtype)
            for r, mns in groups
        ]
        if gather_mode:
            # robust gather mode (r17): dense leaves are gathered per site
            # ([pack, ...] blocks) instead of two-level psummed, plus the
            # weight gather — the factor gather entries are unchanged
            shapes += [
                ((pack,) + tuple(s), np.dtype(np.float32)) for s in dense
            ]
        else:
            shapes += [(s, np.dtype(np.float32)) for s in dense]
        return shapes + robust_gather_wire(pack, robust_agg)

    def dcn_wire_shapes(grads, pack: int = 1, sites_per_slice: int = 1):
        # the inter-slice (DCN) tier, per slice per round: each rank class's
        # gather hop ships the slice's assembled [sites_per_slice, Σ(m+n), r]
        # factor block (DCN-re-quantized per virtual-site row when a codec
        # is set, at the ICI wire dtype otherwise — gathers are always
        # hierarchical under slicing); the dense 1-D leaves ship their
        # per-slice partials (codec grid under a DCN codec, f32 fused
        # otherwise), gathered ×sites_per_slice in the robust gather modes.
        import numpy as np

        groups, dense = lowrank_rank_groups(grads, dad_reduction_rank)
        fdtype = ddtype if ddtype is not None else wdtype
        shapes = [
            ((sites_per_slice, sum(m + n for m, n in mns), r), fdtype)
            for r, mns in groups
        ]
        dense_dtype = (
            ddtype if ddtype is not None else np.dtype(np.float32)
        )
        if gather_mode:
            shapes += [
                ((sites_per_slice,) + tuple(s), dense_dtype) for s in dense
            ]
        else:
            shapes += [(tuple(s), dense_dtype) for s in dense]
        return shapes + robust_gather_dcn_wire(sites_per_slice, robust_agg)

    def dcn_bytes(grads, pack: int = 1, sites_per_slice: int = 1) -> int:
        return wire_shapes_bytes(dcn_wire_shapes(grads, pack, sites_per_slice))

    def aggregate(grads, state, weight, axis_name, live=None, rnd=None):
        # Dead-site round: G zeroed (NaN-safe where) + weight zeroed — the
        # site still factorizes (same program, no recompile) but its Q·scale
        # payload is 0, so the gathered reconstruction is the live sites'
        # weighted mean. Its warm-start Ω is frozen by the trainer for the
        # round (trainer/steps.py), keeping the subspace for its return.
        #
        # Buffered-async rounds (engines/base.py, r13): the inputs are each
        # slot's last DEPOSITED update with staleness-decayed weight; a
        # stale-but-in-bound slot re-factorizes its buffered gradient each
        # round (same program), its Q·scale payload shrinking with age —
        # the decay rides the exact same weighted-factor path as liveness.
        #
        # Packed axes (leaves carrying a leading [K] virtual-site axis): the
        # factorization vmaps over the pack axis, the device's whole [K, …]
        # factor block ships in one gather (the genuinely K-scaling half of
        # the wire), and the dense 1-D leaves take the two-level psum (local
        # pack reduce first — K-invariant wire).
        grads, weight = mask_dead_site(grads, weight, live)
        if robust_agg == "norm_clip":
            # byzantine defense (r17): clip each site's gradient norm to the
            # robust median threshold BEFORE factorization — a sign-flipped
            # or scaled gradient still factorizes, but its reconstruction
            # can pull the mean no further than an honest-sized update
            grads = clip_site_gradients(
                grads, weight, axis_name, robust_clip_mult
            )
        packed = isinstance(axis_name, PackedAxis)
        w_all = None
        if gather_mode:
            # robust gather mode (r17): the weighted trim/median needs every
            # site's live weight on every device; the payload gathers below
            # are the factor exchange the engine launches anyway
            w_all = site_all_gather(
                jnp.asarray(weight, jnp.float32), axis_name
            )
            scale = None  # the robust reduce weighs sites itself
        else:
            scale = site_weight_scale(weight, axis_name)
        leaves, treedef = jax.tree.flatten(grads)
        omegas = (
            treedef.flatten_up_to(state["omega"])
            if dad_warm_start else [None] * len(leaves)
        )
        out: list = [None] * len(leaves)
        new_oms = list(omegas)
        # layers sharing an effective rank factorize in LOCKSTEP so the tiny
        # [r, r] Cholesky work batches across the group; ALL groups then share
        # one while_loop (subspace_iteration_grouped) so rank classes don't
        # serialize against each other.
        groups: dict[int, list[int]] = {}
        for i, g in enumerate(leaves):
            # compressibility is a property of ONE site's leaf — classify on
            # the row shape, not the [K]-batched array (a packed 1-D bias
            # must not read as a compressible [K, n] matrix)
            row = jax.ShapeDtypeStruct(g.shape[1:], g.dtype) if packed else g
            if is_compressible(row):
                groups.setdefault(_effective_rank(row), []).append(i)
            elif gather_mode:
                # robust dense path: gather the per-site leaf and reduce
                # robustly per coordinate (the dense half of the wire now
                # genuinely scales with the pack factor — modeled above)
                out[i] = robust_site_reduce(
                    site_all_gather(
                        g.astype(jnp.float32), axis_name, dcn_wire=dcn
                    ),
                    w_all, robust_agg, robust_trim_frac,
                ).astype(g.dtype)
            elif packed:
                # dense dSGD path for 1-D leaves: two-level weighted psum
                # (three-level on sliced axes — the partial re-quantizes
                # through the DCN codec before the slice hop)
                out[i] = weighted_site_sum(
                    g, scale, axis_name, dcn_wire=dcn
                ).astype(g.dtype)
            else:
                out[i] = jax.lax.psum(
                    g.astype(jnp.float32) * scale, axis_name
                ).astype(g.dtype)
        order = sorted(groups.items())
        if packed and order:
            # one vmap over the pack axis around the SAME grouped while_loop;
            # rank classes stay static (closed over), matrices/Ω are batched
            rs = [r for r, _ in order]
            arg = [
                ([jax.vmap(to_matrix)(leaves[i]) for i in idxs],
                 [omegas[i] for i in idxs])
                for _, idxs in order
            ]

            def factorize(groups_in):
                return subspace_iteration_grouped(
                    [(ms, r, oms) for r, (ms, oms) in zip(rs, groups_in)],
                    dad_num_pow_iters, dad_tol, matmul_dtype=mm_dtype,
                )

            results = jax.vmap(factorize)(arg)
        else:
            results = subspace_iteration_grouped(
                [
                    ([to_matrix(leaves[i]) for i in idxs], r,
                     [omegas[i] for i in idxs])
                    for r, idxs in order
                ],
                dad_num_pow_iters, dad_tol, matmul_dtype=mm_dtype,
            )
        for (r, idxs), pqs in zip(order, results):
            # weight one factor so the gathered reconstruction sums to the
            # weighted mean; cast payloads like the reference's
            # precision_bits, and ship the whole rank group in ONE packed
            # gather (P_0, Q_0, P_1, Q_1, ... interleaved)
            parts = []
            for P, Q in pqs:
                # robust gather modes ship the UNWEIGHTED right factor (the
                # robust reduce weighs the gathered per-site reconstructions
                # itself); the legacy path pre-weights Q so the gathered
                # reconstruction sums straight to the weighted mean
                qs = (
                    Q if gather_mode
                    else Q * (scale[:, None, None] if packed else scale)
                )
                if codec.quant == "none":
                    # legacy precision_bits cast (program-identical pre-r14)
                    parts.append(P.astype(pdtype))
                    parts.append(qs.astype(pdtype))
                else:
                    # quantized wire: each factor round-trips the codec grid
                    # (scale per factor / per virtual-site row) before the
                    # gather; the traced quantize→all_gather chain is what
                    # S002/S004 resolve to prove the byte shrink
                    parts.append(codec.compress(P, batched=packed))
                    parts.append(codec.compress(qs, batched=packed))
            gathered = site_all_gather_packed(parts, axis_name, dcn_wire=dcn)
            for k, (i, (P, Q)) in enumerate(zip(idxs, pqs)):
                if gather_mode:
                    # per-site rank-r reconstructions [S, m, n], robustly
                    # reduced per coordinate — a byzantine site's factors
                    # reach every device (they always did), but the trim /
                    # median caps what they can do to the aggregate. Costs
                    # one [S, m, n] temporary per leaf: compute, not wire.
                    G_site = jnp.einsum(
                        "smr,snr->smn",
                        gathered[2 * k].astype(jnp.float32),      # [S, m, r]
                        gathered[2 * k + 1].astype(jnp.float32),  # [S, n, r]
                    )
                    G_hat = robust_site_reduce(
                        G_site, w_all, robust_agg, robust_trim_frac
                    )
                else:
                    G_hat = jnp.einsum(
                        "smr,snr->mn",
                        gathered[2 * k].astype(jnp.float32),      # [S, m, r]
                        gathered[2 * k + 1].astype(jnp.float32),  # [S, n, r]
                    )
                like = (
                    jax.ShapeDtypeStruct(leaves[i].shape[1:], leaves[i].dtype)
                    if packed else leaves[i]
                )
                out[i] = from_matrix(G_hat, like)
                if dad_warm_start:
                    # next round's subspace guess: this round's (per-site,
                    # unweighted) right factor Q = GᵀP. Y₀ = G@Q ≈ G(GᵀP) —
                    # one power refinement for free at init. A zero gradient
                    # leaves Q=0; the CholeskyQR zero-column fallback then
                    # re-seeds from canonical basis vectors, so the subspace
                    # recovers the round the gradient returns. (Packed: Q is
                    # the [K, n, r] batched factor — matches the [K]-leading
                    # engine-state layout.)
                    new_oms[i] = Q
        new_state = (
            {"omega": jax.tree.unflatten(treedef, new_oms)}
            if dad_warm_start else state
        )
        return jax.tree.unflatten(treedef, out), new_state

    return Engine("rankDAD", init, aggregate, wire_bytes=wire_bytes,
                  wire_shapes=wire_shapes, wire_dtype=wdtype,
                  dcn_bytes=dcn_bytes, dcn_wire_shapes=dcn_wire_shapes,
                  dcn_dtype=ddtype)
