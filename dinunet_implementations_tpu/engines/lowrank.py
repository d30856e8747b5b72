"""Shared low-rank machinery for the compressed engines (rankDAD / powerSGD).

The reference exposes three knobs (``compspec.json:236-238,268-270``):
``dad_reduction_rank`` (default 10), ``dad_num_pow_iters`` (default 5), and
``dad_tol`` (default 1e-3). Tolerance-based early exit inside jit is a
``lax.while_loop`` whose carry tracks the singular-value estimates — shapes
stay static, only the trip count is dynamic (bounded by ``num_iters``).

Matrix convention: a gradient leaf with ndim ≥ 2 is reshaped to
``[prod(leading), last]`` (Dense kernels are already [in, out]; conv kernels
[h, w, cin, cout] → [h*w*cin, cout]); ndim ≤ 1 leaves are "dense" and bypass
compression.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..telemetry import scopes


def is_compressible(g, min_rank_dim: int = 2) -> bool:
    return g.ndim >= 2 and min(_matrix_shape(g)) >= min_rank_dim


def lowrank_rank_groups(grads, rank: int) -> tuple:
    """``(groups, dense)`` — the engine-order wire structure of a low-rank
    factor exchange: ``groups`` is ``[(effective_rank, [(m, n), ...]), ...]``
    sorted by rank class (the exact grouping/order the rankDAD aggregate
    packs its gathers in), ``dense`` the 1-D/non-compressible leaf shapes
    that ride the dense psum path. The structured half of
    :func:`lowrank_wire_bytes`, used by the engines' ``wire_shapes``
    introspection hooks (checks/semantic.py S002)."""
    groups: dict[int, list] = {}
    dense = []
    for g in jax.tree.leaves(grads):
        if is_compressible(g):
            m, n = _matrix_shape(g)
            groups.setdefault(min(rank, m, n), []).append((m, n))
        else:
            dense.append(tuple(g.shape))
    return sorted(groups.items()), dense


def lowrank_wire_bytes(grads, rank: int, itemsize: int, pack: int = 1,
                       dense_pack: int = 1) -> int:
    """Modeled per-round per-DEVICE collective payload of a low-rank factor
    exchange (the shared ``Engine.wire_bytes`` body for rankDAD and
    powerSGD, telemetry/metrics.py): each compressible leaf ships two
    factors ``[m, r]`` + ``[n, r]`` at ``itemsize`` bytes per element with
    the effective rank ``min(rank, m, n)``; 1-D leaves ride the dense f32
    psum path. ``pack`` is the site-packing factor K: a GATHERED factor
    exchange (rankDAD) ships every one of the device's K virtual sites'
    factors, so the factor half scales ×K, while the dense psum half reduces
    locally first and stays K-invariant (powerSGD's psum'd factors are
    likewise K-invariant — it passes ``pack=1``). ``dense_pack`` scales the
    dense 1-D half instead: the robust gather modes (r17) GATHER the dense
    leaves rather than psumming them, so their bytes genuinely scale with K
    too (the legacy psum path keeps ``dense_pack=1``). Pure shape
    arithmetic on THIS module's compressibility criterion — safe on
    tracers, and a criterion change here changes the payload model with
    it."""
    total = 0
    for g in jax.tree.leaves(grads):
        if is_compressible(g):
            m, n = _matrix_shape(g)
            total += min(rank, m, n) * (m + n) * itemsize * pack
        else:
            size = 1
            for d in g.shape:
                size *= d
            total += size * 4 * dense_pack
    return total


def lp_matmul(a, b, dtype=None):
    """``a @ b``, optionally with both operands cast to a low-precision
    ``dtype`` (bf16) while ACCUMULATING in f32 (``preferred_element_type``) —
    the MXU-native mixed-precision contraction. ``dtype=None`` is a plain f32
    matmul. Used for the LARGE power-iteration products ``G@Ω`` / ``GᵀP`` /
    ``G(GᵀP)``; the tiny ``[r, r]`` Gram/Cholesky stays f32 regardless (its
    conditioning drives the CholeskyQR shift analysis in
    :func:`_cholqr_multi`, and it is not where the FLOPs are)."""
    if dtype is None:
        return a @ b
    return jnp.matmul(
        a.astype(dtype), b.astype(dtype), preferred_element_type=jnp.float32
    )


def default_omega(G, r: int, key=None):
    """The per-shape default random init Ω ``[n, r]`` — the draw every solo
    run makes, and the value the rankDAD engine stores at ``init`` so its
    first warm-started round is bit-identical to a cold start."""
    if key is None:
        key = jax.random.PRNGKey(G.shape[0] * 1000003 + G.shape[1])
    return jax.random.normal(key, (G.shape[1], r), jnp.float32)


def _matrix_shape(g):
    m = 1
    for d in g.shape[:-1]:
        m *= d
    return m, g.shape[-1]


def to_matrix(g):
    return g.reshape(_matrix_shape(g))


def from_matrix(mat, like):
    return mat.reshape(like.shape).astype(like.dtype)


def _normalize_cols(Y):
    nc = jnp.linalg.norm(Y, axis=0)
    # exactly-zero columns take canonical basis vectors, so a zero input
    # still yields an ORTHONORMAL Q — matching Householder QR's behavior.
    # powerSGD warm-starts its q factor from the previous round's P; a
    # P=0 here would make q die permanently (q_new = MᵀP = 0 forever)
    # while its error-feedback residual grows unflushed (review, r3).
    # A column whose norm is not a number (a NaN entry, or an overflow of
    # the sum of squares) takes the basis vector too: a CholeskyQR round that
    # broke down hands the next one finite columns (_cholqr_multi).
    fallback = jnp.eye(Y.shape[0], Y.shape[1], dtype=Y.dtype)
    usable = (nc > 0) & jnp.isfinite(nc)
    return jnp.where(usable, Y / jnp.maximum(nc, 1e-30), fallback), nc


def _small_cholesky(G):
    """Unrolled Cholesky of tiny batched SPD matrices ``[..., r, r]``.

    PURE jnp ops, no LAPACK custom-call: the TPU ``cholesky`` custom-call
    costs ~1 µs per matrix REGARDLESS of batching (measured: [32, 10, 10]
    ≈ 33 µs, [224, 10, 10] ≈ 231 µs on v5e — the work is sequential per
    matrix inside the call), and the engines issue it inside every power
    iteration. An unrolled textbook Cholesky–Banachiewicz is r static steps
    of fused vector ops, identical math.
    """
    r = G.shape[-1]
    L = jnp.zeros_like(G)
    for j in range(r):
        # j == 0 guards: zero-size contractions fail to partition under
        # shard_map's manual-computation lowering
        s = G[..., j, j] if j == 0 else (
            G[..., j, j] - jnp.sum(L[..., j, :j] * L[..., j, :j], axis=-1)
        )
        ljj = jnp.sqrt(s)
        if j + 1 < r:
            col = G[..., j + 1:, j] if j == 0 else (
                G[..., j + 1:, j] - jnp.einsum(
                    "...ik,...k->...i", L[..., j + 1:, :j], L[..., j, :j]
                )
            )
            L = L.at[..., j + 1:, j].set(col / ljj[..., None])
        L = L.at[..., j, j].set(ljj)
    return L


def _small_tril_inverse(L):
    """Inverse of tiny batched lower-triangular ``[..., r, r]`` by forward
    substitution — r static steps, no ``triangular_solve`` custom-call
    (same per-matrix-cost pathology as :func:`_small_cholesky`)."""
    r = L.shape[-1]
    eye = jnp.eye(r, dtype=L.dtype)
    X = jnp.zeros_like(L)
    for i in range(r):
        row = jnp.broadcast_to(eye[i], L.shape[:-2] + (r,))
        if i > 0:  # zero-size einsum fails under shard_map (see above)
            row = row - jnp.einsum(
                "...k,...kj->...j", L[..., i, :i], X[..., :i, :]
            )
        X = X.at[..., i, :].set(row / L[..., i, i][..., None])
    return X


def _sound_rows(Linv, delta):
    """``Linv`` with every row that cannot be a row of ``L⁻¹`` replaced by
    the identity's, so that ``Q = Y·L⁻ᵀ`` keeps the normalized input column
    there. ``L`` factors ``G + δI`` with ``G`` a Gram matrix of unit
    columns, so ``L⁻¹(G + δI)L⁻ᵀ = I`` and a row ``x`` of ``L⁻¹`` has
    ``δ‖x‖² ≤ 1``. Rounding over nearly dependent columns puts it at 2 to
    100 now and then, with the column of ``Q`` still of norm about one
    (CPU rehearsal, PERF.md §6, PR 27), so those rows stand; a row past 1e4,
    or not a number, comes from a factorization that broke down. A row that
    stands is left as it is, bit for bit."""
    x_sq = delta[:, :, 0] * jnp.sum(Linv * Linv, axis=-1)
    eye = jnp.eye(Linv.shape[-1], dtype=Linv.dtype)
    return jnp.where((x_sq <= 1e4)[:, :, None], Linv, eye)


def _cholqr_once_multi(Ys, shift, sound: bool = False):
    """One column-normalized shifted CholeskyQR round, LOCKSTEP over a group
    of same-r matrices (possibly different row counts).

    The group's ``[r, r]`` Gram matrices stack and factor through the
    unrolled :func:`_small_cholesky` + :func:`_small_tril_inverse` — zero
    custom-calls (profiled ~45% of rankDAD's compression overhead when the
    LAPACK calls were issued per leaf per iteration on v5e).

    ``Q = Y·L⁻ᵀ`` via the explicit inverse (numerically the same triangular
    system as solving against ``Yᵀ``, which cannot batch across differing
    row counts).

    ``sound=True`` passes ``L⁻¹`` through :func:`_sound_rows`: every column
    of ``Q`` is then finite and bounded, whatever the factorization did.
    """
    pairs = [_normalize_cols(Y) for Y in Ys]
    Yn = [p[0] for p in pairs]
    ncs = [p[1] for p in pairs]
    r = Yn[0].shape[-1]
    eye = jnp.eye(r, dtype=Yn[0].dtype)
    Gms = jnp.stack([Y.T @ Y for Y in Yn])  # [L, r, r]
    tr = jnp.trace(Gms, axis1=-2, axis2=-1)[:, None, None]
    delta = shift * tr + 1e-30
    Gms = Gms + delta * eye
    if jax.default_backend() == "tpu":
        # on TPU the LAPACK custom-calls pay ~1 µs PER MATRIX regardless of
        # batching; the unrolled forms are fused vector ops (the engines
        # call this inside every power iteration). On CPU LAPACK is fine
        # and the unrolled graph only bloats compile time.
        Ls = _small_cholesky(Gms)
        Linv = _small_tril_inverse(Ls)
    else:
        Ls = jnp.linalg.cholesky(Gms)
        Linv = jax.scipy.linalg.solve_triangular(
            Ls, jnp.broadcast_to(eye, Gms.shape), lower=True
        )
    if sound:
        Linv = _sound_rows(Linv, delta)
    Qs = [Y @ jnp.swapaxes(Linv[i], -1, -2) for i, Y in enumerate(Yn)]
    return Qs, ncs


def _cholqr_multi(Ys):
    """Column-normalized shifted CholeskyQR2 of each ``Y [m_l, r]`` →
    ``([Q_l], [colnorm_l])``, lockstep over the group.

    TPU-first replacement for ``jnp.linalg.qr``: Householder QR lowers to a
    long sequential scalar loop on TPU, while this is two matmuls plus a
    batched ``[r, r]`` Cholesky + triangular inverse per round (r ≤ rank,
    default 10) — MXU/batch friendly, and (unlike an eigh-based Löwdin
    orthonormalization, which was tried and reverted) CONTINUOUS in Y:
    float-noise between the vmapped and unbatched lowerings stays
    proportional instead of being amplified by near-degenerate
    eigen-subspace mixing.

    Each round first normalizes columns, so the trace-relative Cholesky shift
    is a PER-COLUMN relative floor rather than a global one — a naive
    ``shift·trace`` floor is dominated by σ₁ and collapses every direction
    with σᵢ² ≲ √shift·σ₁² (review finding r3; measured rec-error 16× worse on
    a decaying spectrum). With normalization the variant matches Householder
    QR's orthogonality (~6e-7) and reconstruction error on spectra spanning
    4 decades, while staying NaN-safe for rank-deficient / all-zero Y (true
    gradient rank is routinely < r, e.g. bounded by the batch size).
    ``colnorm`` is the pre-normalization column-norm vector of the first
    round — the σ-scale convergence proxy.

    Every ``Q`` is FINITE for every ``Y``. In float32 the Gram matrix of
    unit columns is positive semidefinite only up to rounding, which a few
    steps of elimination over nearly dependent columns amplify past either
    shift: once a converged model's gradients are small and numerically
    rank-deficient a pivot of the second round comes out negative, its root
    is NaN, and the parameters are NaN from that round on (rankDAD at toy
    widths: round 338, PERF.md §6, PR 27). A first round that broke down is
    absorbed by the second's column normalization (:func:`_normalize_cols`);
    the second keeps, for each column its factor cannot vouch for, the first
    round's normalized column (:func:`_sound_rows`) — in the span, of unit
    norm, orthogonalized once. Wherever the factorization holds, the result
    is bit for bit what it was without the check.
    """
    Q1s, colnorms = _cholqr_once_multi(Ys, 1e-6)
    Q2s, _ = _cholqr_once_multi(Q1s, 1e-7, sound=True)
    return Q2s, colnorms


def _cholqr(Y):
    """Single-matrix convenience over :func:`_cholqr_multi`."""
    Qs, colnorms = _cholqr_multi([Y])
    return Qs[0], colnorms[0]


def subspace_iteration_grouped(groups, num_iters: int, tol: float,
                               matmul_dtype=None):
    """Rank-r factorizations ``G ≈ P @ Qᵀ`` for SEVERAL same-rank groups in
    ONE shared ``lax.while_loop``.

    ``groups`` is a list of ``(Gs, rank, omegas)`` triples: each group's
    members share ``r = min(rank, m_l, n_l)``; ``omegas`` is a per-member
    list of warm-start subspaces ``[n_l, r]`` (``None`` entries draw the
    :func:`default_omega` for that member, i.e. a cold start; ``omegas=None``
    cold-starts the whole group). Returns one ``[(P_l, Q_l), ...]`` list per
    group, order preserved.

    Why one loop: rankDAD's leaves fall into a handful of effective-rank
    classes (the flagship ICA-LSTM has r=10 for every big kernel plus r=2 for
    the [64, 2] head), and one ``lax.while_loop`` per class SERIALIZES the
    classes — XLA runs whiles one after another, so the tiny r=2 class adds
    its full trip latency to the r=10 class's. Here every class shares a
    single loop (audit, r6): per-class work is emitted side by side in one
    body, the trip count is the max over all members, and per-member trip
    semantics are kept by the same active-mask freezing as before. Within a
    class the ``[r, r]`` Gram matrices still stack and factor through the
    unrolled batched Cholesky (:func:`_cholqr_once_multi`).

    ``matmul_dtype=jnp.bfloat16`` runs the LARGE products (``G@Ω``, ``GᵀP``,
    ``G(GᵀP)``, the final ``Q``) as bf16×bf16→f32 MXU contractions
    (:func:`lp_matmul`); orthonormalization and the σ-convergence test stay
    f32. Warm starts make this safe in practice: bf16 noise perturbs the
    iterate, but the subspace is re-refined every round from the previous
    round's Ω.

    σ estimates come from the orthonormalization's column norms for free —
    ``‖(G Gᵀ P)ᵢ‖`` estimates σᵢ², so ``sqrt`` puts the convergence test on
    the same σ scale the reference's ``dad_tol`` means. A member stops
    updating once its own relative σ-estimate change drops below ``tol``.
    """
    mm = lp_matmul
    if not groups:
        # a fully non-compressible gradient tree (all 1-D/vector leaves):
        # nothing to factorize — the engines' dense fallback carries the
        # whole exchange. The while_loop below cannot carry an empty tuple.
        return []
    prepped = []  # (Gs_f32, omegas_f32) per group, ranks clamped
    for Gs, rank, omegas in groups:
        Gs = [G.astype(jnp.float32) for G in Gs]
        r = min([rank] + [min(G.shape) for G in Gs])
        if omegas is None:
            omegas = [None] * len(Gs)
        elif len(omegas) != len(Gs):
            raise ValueError(
                f"omegas has {len(omegas)} entries for {len(Gs)} matrices"
            )
        oms = [
            default_omega(G, r) if om is None else om.astype(jnp.float32)
            for G, om in zip(Gs, omegas)
        ]
        prepped.append((Gs, oms))

    init_Ps, init_sigs, init_deltas = [], [], []
    for Gs, oms in prepped:
        Ps, _ = _cholqr_multi([mm(G, om, matmul_dtype) for G, om in zip(Gs, oms)])
        sigs = jnp.stack(
            [jnp.linalg.norm(mm(G.T, P, matmul_dtype), axis=0)
             for G, P in zip(Gs, Ps)]
        )  # [L, r] σ estimates, column order
        # Tie the initial deltas to the Gs so their device-varying annotation
        # matches the loop body's output under shard_map (per-site G ⇒
        # per-site delta).
        deltas0 = jnp.full((len(Gs),), jnp.inf, jnp.float32) + 0.0 * sigs.sum(-1)
        init_Ps.append(tuple(Ps))
        init_sigs.append(sigs)
        init_deltas.append(deltas0)

    def cond(carry):
        i, _, _, deltas = carry
        worst = jnp.max(jnp.stack([jnp.max(d) for d in deltas]))
        return jnp.logical_and(i < num_iters, worst > tol)

    def body(carry):
        i, Ps_all, sigs_all, deltas_all = carry
        out_Ps, out_sigs, out_deltas = [], [], []
        for (Gs, _), Ps, sigs, deltas in zip(
            prepped, Ps_all, sigs_all, deltas_all
        ):
            P_cand, colnorms = _cholqr_multi(
                [mm(G, mm(G.T, P, matmul_dtype), matmul_dtype)
                 for G, P in zip(Gs, Ps)]
            )
            sig_new = jnp.sqrt(jnp.stack(colnorms))  # ‖G Gᵀ p‖ ≈ σ² → σ scale
            delta_new = jnp.linalg.norm(sig_new - sigs, axis=-1) / jnp.maximum(
                jnp.linalg.norm(sigs, axis=-1), 1e-12
            )
            active = deltas > tol  # members still iterating (solo trip counts)
            out_Ps.append(tuple(
                jnp.where(active[l], P_cand[l], Ps[l]) for l in range(len(Gs))
            ))
            out_sigs.append(jnp.where(active[:, None], sig_new, sigs))
            out_deltas.append(jnp.where(active, delta_new, deltas))
        return i + 1, tuple(out_Ps), tuple(out_sigs), tuple(out_deltas)

    with jax.named_scope(scopes.POWERITER):
        _, Ps_all, _, _ = jax.lax.while_loop(
            cond, body,
            (jnp.zeros((), jnp.int32), tuple(init_Ps), tuple(init_sigs),
             tuple(init_deltas)),
        )
    return [
        [(P, mm(G.T, P, matmul_dtype)) for G, P in zip(Gs, Ps)]
        for (Gs, _), Ps in zip(prepped, Ps_all)
    ]


def subspace_iteration_multi(Gs, rank: int, num_iters: int, tol: float,
                             keys=None, omegas=None, matmul_dtype=None):
    """Rank-r factorizations ``G_l ≈ P_l @ Q_lᵀ`` by LOCKSTEP subspace (block
    power) iteration over ONE group of matrices sharing
    ``r = min(rank, m_l, n_l)`` — a group of one over
    :func:`subspace_iteration_grouped`.

    Each P_l is [m_l, r] orthonormal, Q_l = G_lᵀ P_l is [n_l, r].
    ``keys[l]`` overrides the PRNG key for member l's default Ω draw;
    ``omegas[l]`` supplies the subspace itself (warm start) and wins over
    ``keys[l]``. ``None`` entries keep the per-shape default — identical to
    what each solo run drew.
    """
    L = len(Gs)
    if keys is None:
        keys = [None] * L
    elif len(keys) != L:
        raise ValueError(f"keys has {len(keys)} entries for {L} matrices")
    r = min([rank] + [min(G.shape) for G in Gs])
    if omegas is None:
        omegas = [None] * L
    elif len(omegas) != L:
        raise ValueError(f"omegas has {len(omegas)} entries for {L} matrices")
    oms = [
        om if om is not None else default_omega(jnp.asarray(G), r, k)
        for G, om, k in zip(Gs, omegas, keys)
    ]
    return subspace_iteration_grouped(
        [(Gs, rank, oms)], num_iters, tol, matmul_dtype=matmul_dtype
    )[0]


def subspace_iteration(G, rank: int, num_iters: int, tol: float, key=None):
    """Single-matrix rank-r factorization ``G ≈ P @ Qᵀ`` — a group of one
    over :func:`subspace_iteration_multi`. An explicit ``key`` seeds the
    random init Ω; ``None`` draws the per-shape default key (what the
    engines use, so lockstep groups match solo runs)."""
    return subspace_iteration_multi(
        [G], rank, num_iters, tol, keys=None if key is None else [key]
    )[0]


def orthonormalize(P):
    """Orthonormalize columns (shifted CholeskyQR2 — see :func:`_cholqr`)."""
    Q, _ = _cholqr(P)
    return Q
