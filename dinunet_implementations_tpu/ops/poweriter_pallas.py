"""Fused Pallas TPU kernel for the rankDAD power-iteration inner loop.

PR 7's attribution artifact (``docs/bench_rankdad_attr_r12.jsonl``) measured
the subspace/power iteration at **82.7% of a cold rankDAD epoch** (14.2% per
trip): the hot loop is a sequence of small matmuls (``G@Ω``, ``GᵀP``,
``G(GᵀP)``) interleaved with CholeskyQR orthonormalizations, each emitted as
separate XLA ops that spill the ``[m, r]``/``[n, r]`` iterates (and re-read
``G``) through HBM on every trip. This kernel fuses ONE rank class's entire
``lax.while_loop`` — init, every power refinement, the convergence test, and
the final back-projection ``Q = GᵀP`` — into a single VMEM-resident
``pallas_call``: ``G`` is read from HBM once, the iterates live in
registers/VMEM for the whole loop, and only the final ``(P, Q)`` factors are
written back.

Layout: a rank class's members (same effective rank r, possibly different
``(m_l, n_l)``) are bucketed by EXACT shape and each bucket stacks
``[L, m, n]`` into one kernel invocation — the flagship's fwd/bwd LSTM
kernel pairs share shapes, so they batch; a differently-shaped member gets
its own call. (Zero-padding the whole class to its max dims would also be
mathematically exact, but was measured to inflate the iteration FLOPs ~5×
on mixed shapes — every member paying ``m̄·n̄`` instead of its own ``m·n`` —
so it is not done.) The batched member axis maps onto TPU sublanes through
the stacked einsums, so the tiny ``[r, r]`` Cholesky work batches across
the bucket exactly like the XLA path (``lowrank._cholqr_once_multi``).

Semantics mirror ``lowrank.subspace_iteration_grouped`` member-for-member:
the same column-normalized shifted CholeskyQR2 (via the SAME unrolled
``_small_cholesky``/``_small_tril_inverse`` helpers — no LAPACK custom-call
exists inside a kernel anyway), the same σ-estimate convergence test, the
same per-member active-mask freezing. One deliberate divergence: each rank
class's fused loop exits on ITS OWN worst member delta instead of the global
max over all classes — converged members are frozen either way, so the
RESULTS are identical; only wasted trips differ (fewer here: a converged
class stops instead of spinning until the slowest class finishes).

``matmul_dtype=bfloat16`` runs the large products as bf16×bf16→f32 MXU
contractions inside the kernel (the ``lp_matmul`` policy,
``engines/lowrank.py``); normalization/Cholesky/σ stay f32.

Status: opt-in only (``fused_poweriter=True``). The kernel has run in
interpret mode alone (``interpret=True`` on a CPU backend — tier-1, the
parity tests, the paired A/B bench); lowering it for a TPU fails in Mosaic
on the ``scatter`` that ``_small_cholesky`` / ``_small_tril_inverse`` build
with ``.at[...].set`` (ROADMAP S2), and nothing catches that error. VMEM
budget: :func:`class_fits_vmem` estimates the kernel's resident bytes and
callers (``lowrank.subspace_iteration_grouped``) fall back to the legacy
XLA loop for any class that would not fit — a trace-time static decision.

vmap (the r12 packed-sites path): jax's default ``pallas_call`` vmap rule
prepends a grid dimension, which executes SEQUENTIALLY on a TPU core; the
entry point instead carries a ``custom_vmap`` rule that folds the mapped
axis into the member axis (``[K, L, m̄, n̄] → [K·L, m̄, n̄]``) — valid because
every kernel output is member-row-wise (same argument as the LSTM kernel's
batch-row fold).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..engines.lowrank import (
    _small_cholesky,
    _small_tril_inverse,
    _sound_rows,
    default_omega,
)

#: conservative VMEM budget for one fused class (v5e/v4 have ~16 MiB/core;
#: leave headroom for the grid pipeline's other residents)
VMEM_BUDGET_BYTES = 12 * 2**20


def _interpret() -> bool:
    # Pallas TPU kernels run in interpreter mode on CPU (tests / simulators)
    return jax.default_backend() == "cpu"


def class_fits_vmem(Gs, rank: int, matmul_dtype=None,
                    budget: int = VMEM_BUDGET_BYTES) -> bool:
    """Trace-time static estimate of one rank class's kernel residency,
    per EXACT-SHAPE BUCKET (the unit that actually becomes one kernel
    invocation — see :func:`fused_subspace_iteration_grouped`): the
    ``[L, m, n]`` G stack (plus its bf16 copy under mixed precision), ~3
    ``[L, m, r]`` and ~3 ``[L, n, r]`` iterate buffers, and the
    ``[L, r, r]`` Gram scratch. The class fuses iff its LARGEST bucket
    fits. Pure shape arithmetic — safe on tracers."""
    if not Gs:
        return False
    # shapes are static Python ints even on tracers — never traced values
    r = min([rank] + [min(int(d) for d in g.shape) for g in Gs])  # jaxlint: disable=R005
    buckets: dict[tuple, int] = {}
    for g in Gs:
        shape = (int(g.shape[0]), int(g.shape[1]))  # jaxlint: disable=R005
        buckets[shape] = buckets.get(shape, 0) + 1
    for (m, n), L in buckets.items():
        g_bytes = m * n * (4 + (2 if matmul_dtype is not None else 0))
        iter_bytes = 3 * (m + n) * r * 4
        gram_bytes = 4 * r * r * 4
        if L * (g_bytes + iter_bytes + gram_bytes) > budget:
            return False
    return True


# ---------------------------------------------------------------------------
# batched CholeskyQR2 (the in-kernel twin of lowrank._cholqr_multi)
# ---------------------------------------------------------------------------


def _normalize_cols_b(Y):
    """Column-normalize a ``[L, m, r]`` stack; exactly-zero columns take
    canonical basis vectors (same fallback + same reasons as
    ``lowrank._normalize_cols``)."""
    nc = jnp.sqrt(jnp.sum(Y * Y, axis=1))  # [L, r]
    fallback = jnp.broadcast_to(
        jnp.eye(Y.shape[1], Y.shape[2], dtype=Y.dtype)[None], Y.shape
    )
    usable = (nc > 0) & jnp.isfinite(nc)
    Yn = jnp.where(
        usable[:, None, :], Y / jnp.maximum(nc, 1e-30)[:, None, :], fallback
    )
    return Yn, nc


def _cholqr_once_b(Y, shift, sound: bool = False):
    """One column-normalized shifted CholeskyQR round over the ``[L, m, r]``
    member stack — the batched form of ``lowrank._cholqr_once_multi``, with
    the same backend split: unrolled Cholesky/triangular-inverse on TPU (a
    Mosaic kernel has no LAPACK custom-calls, and the unrolled form is the
    fast one there anyway), LAPACK in interpret mode (the kernel body
    traces to plain XLA ops on CPU, where LAPACK wins and the unrolled
    graph only bloats compile time — the same reasoning as
    ``lowrank._cholqr_once_multi``)."""
    Yn, nc = _normalize_cols_b(Y)
    r = Yn.shape[-1]
    eye = jnp.eye(r, dtype=Yn.dtype)
    Gm = jnp.einsum("lmr,lms->lrs", Yn, Yn)  # [L, r, r]
    tr = jnp.trace(Gm, axis1=-2, axis2=-1)[:, None, None]
    delta = shift * tr + 1e-30
    Gm = Gm + delta * eye
    if _interpret():
        Ls = jnp.linalg.cholesky(Gm)
        Linv = jax.scipy.linalg.solve_triangular(
            Ls, jnp.broadcast_to(eye, Gm.shape), lower=True
        )
    else:
        Ls = _small_cholesky(Gm)
        Linv = _small_tril_inverse(Ls)
    if sound:  # finite Q whatever the factorization did (lowrank._sound_rows)
        Linv = _sound_rows(Linv, delta)
    Q = jnp.einsum("lmr,lsr->lms", Yn, Linv)  # Y @ L⁻ᵀ per member
    return Q, nc


def _cholqr2_b(Y):
    Q1, colnorms = _cholqr_once_b(Y, 1e-6)
    Q2, _ = _cholqr_once_b(Q1, 1e-7, sound=True)
    return Q2, colnorms


# ---------------------------------------------------------------------------
# the fused kernel
# ---------------------------------------------------------------------------


def _poweriter_kernel(G_ref, om_ref, P_ref, Q_ref, *, num_iters, tol,
                      mm_name):
    G = G_ref[...]  # [L, m, n] f32, VMEM-resident for the WHOLE loop
    om = om_ref[...]  # [L, n, r] f32
    mmd = jnp.dtype(mm_name) if mm_name is not None else None

    def mm(a, b, spec):
        # the large products at the lp_matmul policy: optional bf16 inputs,
        # f32 accumulation (engines/lowrank.py)
        if mmd is None:
            return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)
        return jnp.einsum(
            spec, a.astype(mmd), b.astype(mmd),
            preferred_element_type=jnp.float32,
        )

    def col_norms(A):  # [L, x, r] -> [L, r]
        return jnp.sqrt(jnp.sum(A * A, axis=1))

    # init: P0 = cholqr2(G @ Ω), σ0 from ‖(GᵀP)ᵢ‖ — identical to the XLA
    # path's prologue (lowrank.subspace_iteration_grouped)
    P, _ = _cholqr2_b(mm(G, om, "lmn,lnr->lmr"))
    sig = col_norms(mm(G, P, "lmn,lmr->lnr"))  # [L, r]
    delta = jnp.full((G.shape[0],), jnp.inf, jnp.float32)

    def cond(carry):
        i, _, _, d = carry
        return jnp.logical_and(i < num_iters, jnp.max(d) > tol)

    def body(carry):
        i, P, sig, delta = carry
        Y = mm(G, mm(G, P, "lmn,lmr->lnr"), "lmn,lnr->lmr")  # G(GᵀP)
        P_cand, colnorms = _cholqr2_b(Y)
        sig_new = jnp.sqrt(colnorms)  # ‖G Gᵀ p‖ ≈ σ² → σ scale
        delta_new = jnp.sqrt(jnp.sum((sig_new - sig) ** 2, axis=-1)) / (
            jnp.maximum(jnp.sqrt(jnp.sum(sig * sig, axis=-1)), 1e-12)
        )
        active = delta > tol  # members still iterating (solo trip counts)
        P = jnp.where(active[:, None, None], P_cand, P)
        sig = jnp.where(active[:, None], sig_new, sig)
        delta = jnp.where(active, delta_new, delta)
        return i + 1, P, sig, delta

    _, P, _, _ = jax.lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), P, sig, delta)
    )
    P_ref[...] = P
    # the back-projection stays fused too: Q = GᵀP reads the resident G one
    # last time instead of round-tripping P through HBM into an XLA matmul
    Q_ref[...] = mm(G, P, "lmn,lmr->lnr")


def _poweriter_call(Gp, omp, r: int, num_iters: int, tol: float, mm_name):
    """One fused ``pallas_call`` for one (padded, stacked) rank class:
    ``[L, m̄, n̄] × [L, n̄, r] → ([L, m̄, r], [L, n̄, r])``. No grid — a single
    invocation whose whole working set is VMEM-resident (class_fits_vmem
    gates callers)."""
    L, m, n = Gp.shape
    kernel = functools.partial(
        _poweriter_kernel, num_iters=num_iters, tol=tol, mm_name=mm_name
    )
    return pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L, m, r), jnp.float32),
            jax.ShapeDtypeStruct((L, n, r), jnp.float32),
        ],
        interpret=_interpret(),
    )(Gp, omp)


def _poweriter_vmappable(r: int, num_iters: int, tol: float, mm_name):
    """The kernel entry with a member-axis-fold vmap rule: a mapped axis
    (the r12 packed virtual-site axis K) folds into the member axis L
    instead of becoming a sequential grid dimension — every kernel output
    is member-row-wise, so the fold is exact (frozen members make the
    shared trip count irrelevant to results)."""

    @custom_vmap
    def call(Gp, omp):
        return _poweriter_call(Gp, omp, r, num_iters, tol, mm_name)

    @call.def_vmap
    def _rule(axis_size, in_batched, Gp, omp):
        g_b, o_b = in_batched
        if not g_b:
            Gp = jnp.broadcast_to(Gp[None], (axis_size,) + Gp.shape)
        if not o_b:
            # cold starts under vmap draw ONE per-shape Ω — every virtual
            # site starts from the same subspace, exactly like the legacy
            # path's unbatched default_omega under the engine's vmap
            omp = jnp.broadcast_to(omp[None], (axis_size,) + omp.shape)
        B, L = Gp.shape[0], Gp.shape[1]
        P, Q = _poweriter_call(
            Gp.reshape((B * L,) + Gp.shape[2:]),
            omp.reshape((B * L,) + omp.shape[2:]),
            r, num_iters, tol, mm_name,
        )
        return (
            P.reshape((B, L) + P.shape[1:]),
            Q.reshape((B, L) + Q.shape[1:]),
        ), (True, True)

    return call


# ---------------------------------------------------------------------------
# the grouped entry point (lowrank.subspace_iteration_grouped's fused twin)
# ---------------------------------------------------------------------------


def fused_subspace_iteration_grouped(groups, num_iters: int, tol: float,
                                     matmul_dtype=None):
    """Drop-in fused twin of ``lowrank.subspace_iteration_grouped`` for
    classes that pass :func:`class_fits_vmem`: same ``[(Gs, rank, omegas)]``
    contract, same ``[[(P_l, Q_l), ...], ...]`` result (order preserved).

    One ``pallas_call`` per (rank class, member shape) bucket: members
    sharing an exact ``(m, n)`` stack into one ``[L, m, n]`` kernel
    invocation (the flagship ICA-LSTM's fwd/bwd LSTM kernel pairs), while
    differently-shaped members get their own call. Padding a heterogeneous
    class to its max dims was measured to inflate the power-iteration
    FLOPs ~5x on mixed shapes (every member paying ``m̄·n̄`` instead of its
    own ``m·n``) — more launches beat that by a wide margin, and each
    bucket's loop still exits on its own convergence."""
    mm_name = jnp.dtype(matmul_dtype).name if matmul_dtype is not None else None
    out = []
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
        buckets: dict[tuple, list[int]] = {}
        for i, G in enumerate(Gs):
            buckets.setdefault(tuple(G.shape), []).append(i)
        results: list = [None] * len(Gs)
        for shape, idxs in buckets.items():
            Gp = jnp.stack([Gs[i] for i in idxs])
            omp = jnp.stack([oms[i] for i in idxs])
            P, Q = _poweriter_vmappable(r, num_iters, tol, mm_name)(Gp, omp)
            for l, i in enumerate(idxs):
                results[i] = (P[l], Q[l])
        out.append(results)
    return out
