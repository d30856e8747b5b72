"""The SPMD federated train/eval steps — where the reference's entire
local↔remote round trip collapses into one compiled program.

Reference execution (SURVEY.md §3.1): per round, every site container steps
``local_iterations`` batches with gradient accumulation, JSON-ships its
(possibly compressed) gradient to the remote, the remote reduces across sites
on an mp.Pool and broadcasts the update back. ~97% of wall-clock was that
transport. Here:

- one epoch = ``jax.lax.scan`` over rounds *inside* a single ``shard_map``
  over the ``(site,)`` mesh — zero host round trips;
- gradient accumulation = inner ``lax.scan`` over ``local_iterations``
  micro-batches (``compspec.json:88-95``);
- the engine's collectives (psum / all-gather, engines/) are the only
  cross-site communication, riding ICI;
- parameters & optimizer state are replicated (every site applies the same
  aggregated update — the invariant the reference maintains by broadcast).

BatchNorm running stats (ICALstm head) are psum-averaged across sites each
round ("sync-BN across sites"): the reference lets per-site buffers drift and
never reconciles them; averaging is the principled SPMD equivalent and keeps
eval single-model. Documented TPU-design divergence.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.struct
import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..data.api import stored_data_index, stored_sample_shape
from ..engines.base import Engine, default_async_buffers, staleness_weights
from ..parallel.collectives import (
    PackedAxis,
    site_weight_scale,
    two_level_psum,
    weighted_site_sum,
)
from ..parallel.mesh import (
    FOLD_AXIS,
    MODEL_AXIS,
    SITE_AXIS,
    SLICE_AXIS,
    site_axis_of,
    slice_count,
)
from ..robustness.health import default_health
from ..telemetry import scopes
from ..telemetry.metrics import (
    TELEMETRY_KEYS,
    dcn_bytes_of,
    default_round_telemetry,
    payload_bytes_of,
    tree_sq_sum,
)


def _model_axis_of(mesh) -> str | None:
    """The bound model/sequence axis name, when the mesh has one of size > 1.

    With a ``(site, model)`` mesh the data stays partitioned over ``site``
    only — every model-axis member sees the full per-site batch and the model
    internally shards its sequence axis (models/icalstm.py sequence_axis,
    models/transformer.py attention="ring")."""
    if mesh is not None and dict(getattr(mesh, "shape", {})).get(MODEL_AXIS, 1) > 1:
        return MODEL_AXIS
    return None


@flax.struct.dataclass
class TrainState:
    params: Any
    batch_stats: Any  # {} when the model tracks no running stats
    opt_state: Any
    engine_state: Any  # PER-SITE: leaves carry a leading [num_sites] axis
    rng: jax.Array
    round: jax.Array  # global round counter (int32)
    # PER-SITE health counters (robustness/health.py): non-finite streak,
    # skipped-round count, sticky quarantine flag. None only for states built
    # by hand pre-0.3 code paths — the epoch fn fills in zeros then.
    health: Any = None
    # PER-SITE round-metric accumulators (telemetry/metrics.py): grad/update
    # norms, engine residual, payload bytes. None whenever
    # TrainConfig.telemetry="off" — the epoch program then carries no
    # telemetry ops at all (bitwise-equal to the pre-telemetry program).
    telemetry: Any = None
    # PER-SLOT staleness buffers (engines/base.py default_async_buffers):
    # each virtual site's last deposited update + its weight + arrival age —
    # the carry of the buffered-async aggregation mode (r13). None whenever
    # TrainConfig.staleness_bound == 0 — the epoch program then carries no
    # buffering ops at all (bitwise-equal to the bulk-sync program, the
    # telemetry=off pattern; S005-gated).
    buffers: Any = None
    # PER-SITE double-buffered round payload (r14 compute/comm overlap,
    # :func:`default_overlap_stash`): the previous round's gradients /
    # weights / loss / liveness, whose aggregation collective is issued
    # while the NEXT round's batch gather + forward/backward compute — the
    # one-round-delayed pipelined update. Riding TrainState (not just the
    # scan carry) means no round is ever dropped at an epoch boundary: the
    # epoch's last stash applies at the next epoch's first round, and a
    # checkpointed fit resumes with its in-flight round intact. None
    # whenever TrainConfig.overlap_rounds is off — the epoch program then
    # carries no overlap ops at all (bitwise-equal legacy program,
    # S005-gated).
    overlap: Any = None
    # PER-SITE personalized-head state (r20, privacy/personalize.py):
    # {"params": head-subtree with [S, ...] leaves, "opt": the per-site
    # optimizer state over it}. Head leaves named by TrainConfig.personalize
    # are partitioned OUT of aggregation entirely — each site trains and
    # evaluates its own row; the global params tree keeps full structure
    # with those leaves frozen at init. Sharded P(site) like health,
    # checkpointed (R006), rejoin-reset via reset_slot_state. None whenever
    # personalization is off — the epoch program then carries no
    # personalization ops at all (bitwise-equal legacy program,
    # S005-gated).
    personal: Any = None


def _state_specs(state: TrainState, site_axis=SITE_AXIS):
    """shard_map partition specs: everything replicated except the per-site
    engine state — powerSGD's error-feedback residual/Q and rankDAD's
    warm-start subspace Ω (engines/rankdad.py) — which is sharded over the
    site axis; collapsing it to one site's copy would silently break error
    feedback (and subspace warm starts) across epoch boundaries. The health
    counters are per-site for the same reason. ``site_axis`` is the leading
    per-site partition entry — the ``(slice, site)`` pair on sliced meshes
    (parallel/mesh.py ``site_axis_of``), plain ``site`` otherwise."""
    return TrainState(
        params=jax.tree.map(lambda _: P(), state.params),
        batch_stats=jax.tree.map(lambda _: P(), state.batch_stats),
        opt_state=jax.tree.map(lambda _: P(), state.opt_state),
        engine_state=jax.tree.map(lambda _: P(site_axis), state.engine_state),
        rng=P(),
        round=P(),
        health=jax.tree.map(lambda _: P(site_axis), state.health),
        telemetry=jax.tree.map(lambda _: P(site_axis), state.telemetry),
        buffers=jax.tree.map(lambda _: P(site_axis), state.buffers),
        overlap=jax.tree.map(lambda _: P(site_axis), state.overlap),
        personal=jax.tree.map(lambda _: P(site_axis), state.personal),
    )


def make_optimizer(name: str, learning_rate: float) -> optax.GradientTransformation:
    """Reference trains with Adam at ``learning_rate`` (coinstac-dinunet
    default); SGD kept as an option."""
    if name == "adam":
        return optax.adam(learning_rate)
    if name == "sgd":
        return optax.sgd(learning_rate)
    raise ValueError(f"unknown optimizer {name!r}")


def cross_entropy(logits, labels, weights):
    """Masked mean cross-entropy. FS uses log_softmax+NLL, ICA uses
    cross_entropy — identical math (``comps/fs/__init__.py:54-55``,
    ``comps/icalstm/__init__.py:60``)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    denom = jnp.maximum(weights.sum(), 1.0)
    return (ce * weights).sum() / denom


class FederatedTask:
    """Bundles a flax model with its loss/apply plumbing for the trainer."""

    def __init__(self, model, has_batch_stats: bool | None = None):
        self.model = model
        self.has_batch_stats = has_batch_stats  # resolved at init_variables
        # one sample as the model takes it (no batch axis), resolved at
        # init_variables: the device pipeline gives it back to the batches
        # it gathers from the resident inventory (_gather_batch)
        self.sample_shape = None

    def init_variables(self, rng, sample_x):
        # init runs OUTSIDE shard_map (no mesh axis bound), so a model
        # configured for sequence parallelism initializes via a dense twin —
        # submodule names/shapes are identical by construction, only the
        # collective plumbing differs
        model = self.model
        dense_kw = {}
        if getattr(model, "sequence_axis", None) is not None:
            dense_kw["sequence_axis"] = None
        if getattr(model, "attention", None) == "ring":
            dense_kw.update(attention="local", axis_name=None)
        if dense_kw:
            model = model.clone(**dense_kw)
        variables = model.init(
            {"params": rng, "dropout": rng}, sample_x, train=True
        )
        self.has_batch_stats = "batch_stats" in variables
        self.sample_shape = tuple(sample_x.shape[1:])
        return variables["params"], variables.get("batch_stats", {})

    def apply(self, params, batch_stats, x, train, rng=None, mask=None, mutable=False):
        variables = {"params": params}
        if self.has_batch_stats:
            variables["batch_stats"] = batch_stats
        rngs = {"dropout": rng} if rng is not None else None
        if mutable and self.has_batch_stats:
            logits, upd = self.model.apply(
                variables, x, train=train, mask=mask, rngs=rngs, mutable=["batch_stats"]
            )
            return logits, upd["batch_stats"]
        logits = self.model.apply(variables, x, train=train, mask=mask, rngs=rngs)
        return logits, batch_stats

    def loss(self, params, batch_stats, rng, x, y, w):
        """The task's training loss ``-> (loss, new_stats)``. Default: the
        model's logits against the batch's labels (:func:`cross_entropy`). A
        model whose targets come from its own input and whose loss is
        computed in blocks (models/afmoe.py: next-token loss per position)
        brings its own as ``model.task_loss(variables, x, w)``."""
        own = getattr(self.model, "task_loss", None)
        if own is not None:
            return own({"params": params}, x, w), batch_stats
        logits, new_stats = self.apply(
            params, batch_stats, x, train=True, rng=rng, mask=w, mutable=True
        )
        return cross_entropy(logits, y, w), new_stats


def init_train_state(
    task: FederatedTask,
    engine: Engine,
    optimizer: optax.GradientTransformation,
    rng,
    sample_x,
    num_sites: int = 1,
    telemetry: bool = False,
    staleness_bound: int = 0,
    overlap_rounds: bool = False,
    reputation: bool = False,
    personalize: tuple = (),
) -> TrainState:
    params, batch_stats = task.init_variables(rng, sample_x)
    # personalized heads (r20): the engine only ever aggregates (and its
    # state/wire models only ever see) the SHARED subtree — head leaves
    # never ship, so engine state must not carry rows for them
    personal = None
    if personalize:
        from ..privacy.personalize import (
            default_personal,
            head_leaf_paths,
            strip_tree,
        )

        paths = head_leaf_paths(params, personalize)
        site_state = engine.init(strip_tree(params, paths, keep_head=False))
        personal = default_personal(num_sites, params, paths, optimizer)
    else:
        site_state = engine.init(params)
    return TrainState(
        params=params,
        batch_stats=batch_stats,
        opt_state=optimizer.init(params),
        # per-site engine state: one copy per site, leading [num_sites] axis
        engine_state=jax.tree.map(
            lambda a: jnp.stack([a] * num_sites), site_state
        ),
        rng=rng,
        round=jnp.zeros((), jnp.int32),
        # reputation=True adds the r17 anomaly-score fields so the robust
        # epoch program's carry structure matches from the first call (the
        # _ensure_health fill would otherwise cost one extra compile)
        health=default_health(num_sites, reputation=reputation),
        # telemetry accumulators only when the epoch fn will maintain them —
        # a telemetry-carrying state fed to a telemetry-off program would
        # force a structure change (and a recompile) at the jit boundary
        telemetry=default_round_telemetry(num_sites) if telemetry else None,
        # staleness buffers only for the buffered-async mode (same structural
        # reasoning as telemetry: the carried state must match the program)
        buffers=(
            default_async_buffers(num_sites, params)
            if staleness_bound > 0 else None
        ),
        # overlap stash only for the pipelined-rounds mode (same structural
        # reasoning: the carried state must match the program)
        overlap=(
            default_overlap_stash(num_sites, params, batch_stats)
            if overlap_rounds else None
        ),
        # per-site head rows only when personalization is on (the telemetry
        # structural reasoning: the carried state must match the program)
        personal=personal,
    )


def default_overlap_stash(num_sites: int, params, batch_stats) -> dict:
    """Fresh (empty) double-buffered round payload for the overlapped-rounds
    mode (r14): per-site ``grads``/``stats``/``weight``/``loss``/``live``
    slots holding the round whose aggregation is still in flight, plus
    ``valid`` (0 = nothing stashed yet — the very first round of a fit
    applies no update). All leaves carry the ``[num_sites]`` leading axis,
    ride ``TrainState.overlap`` sharded ``P(site)``, are checkpointed
    (trainer/checkpoint.py — a resumed fit continues its in-flight round),
    and are distinct arrays so state donation never aliases a buffer
    twice."""
    return {
        "grads": jax.tree.map(
            lambda p: jnp.zeros((num_sites,) + p.shape, p.dtype), params
        ),
        "stats": jax.tree.map(
            lambda s: jnp.zeros((num_sites,) + s.shape, s.dtype), batch_stats
        ),
        "weight": jnp.zeros((num_sites,), jnp.float32),
        "loss": jnp.zeros((num_sites,), jnp.float32),
        "live": jnp.zeros((num_sites,), jnp.float32),
        "valid": jnp.zeros((num_sites,), jnp.float32),
    }


def _gather_batch(inv_x, inv_y, ixs, poison=None, sample_shape=None):
    """On-device batch gather for ONE site: ``ixs [L, B]`` sample positions
    into the site's resident inventory (``inv_x [N + 1, *stored]``, ``inv_y
    [N + 1]``); ``-1`` marks padding. Reproduces the host materialization
    bit-for-bit: padding slots become zero inputs / zero labels / zero
    weight, and ``poison`` (the round's NaN-injection gate,
    robustness/faults.py — a traced scalar, non-None only when the epoch was
    compiled for a NaN-carrying FaultPlan) overwrites the whole round block
    with NaN exactly like ``poison_inputs`` does on host arrays.

    The inventory is in its RESIDENT FORM (data/api.py SiteInventory), so the
    batch is ONE in-bounds row gather:

    - its LAST row is all zeros and ``-1`` is pointed at it on the KB-sized
      index block: padding slots read their zeros from the store, with no
      mask pass over the batch and no fill-mode bounds select;
    - a row is a sample in its ``stored_sample_shape`` (narrow trailing
      dimensions merged, rows padded to whole tiles), so the gathered
      ``[L·B, *stored]`` block is a bitcast of the ``[sites, batch, ...]``
      operand the model reads. ``sample_shape`` (static; ``task.sample_shape``)
      is one sample as the model takes it: the pad rows are sliced off and
      the shape given back here, both of which XLA folds into the model's
      first contraction. An inventory that already holds samples in the
      model's own shape is gathered as it is.

    An inventory built by hand without the zero row works as long as its plan
    holds no ``-1``."""
    with jax.named_scope(scopes.GATHER):
        stored = tuple(inv_x.shape[1:])
        feat = stored if sample_shape is None else tuple(sample_shape)
        if feat != stored and stored_sample_shape(feat) != stored:
            raise ValueError(
                f"the inventory stores samples as {stored}, which is not "
                f"the resident form of the model's sample shape {feat}"
            )
        valid = ixs >= 0
        flat = jnp.where(valid, ixs, inv_x.shape[0] - 1).reshape(-1)
        xb = jnp.take(inv_x, flat, axis=0, mode="clip")
        if feat != stored:
            xb = xb[stored_data_index(feat)]
        xb = xb.reshape(ixs.shape + feat)
        yb = jnp.take(inv_y, flat, axis=0, mode="clip").reshape(ixs.shape)
        yb = jnp.where(valid, yb, 0)
        if poison is not None:
            xb = jnp.where(poison > 0, jnp.full((), jnp.nan, xb.dtype), xb)
        return xb, yb, valid.astype(jnp.float32)


def make_train_epoch_fn(
    task: FederatedTask,
    engine: Engine,
    optimizer: optax.GradientTransformation,
    mesh=None,
    local_iterations: int = 1,
    rounds_scan_xs: bool = True,
    quarantine_rounds: int | None = 3,
    pipeline: str = "host",
    donate_state: bool = False,
    telemetry: bool = False,
    staleness_bound: int = 0,
    staleness_decay: float = 0.5,
    overlap_rounds: bool = False,
    attack_plan=None,
    robust_agg: str = "none",
    reputation_z: float = 2.0,
    reputation_rounds: int = 8,
    min_slices: int = 1,
    dp_clip: float = 0.0,
    dp_noise_multiplier: float = 0.0,
    dp_seed: int = 0,
    personalize: tuple = (),
):
    """Build the jitted epoch function.

    Takes ``(state, inputs [S,steps,B,...], labels [S,steps,B],
    weights [S,steps,B], live=None)``; consumes ``steps`` in rounds of
    ``local_iterations`` micro-batches (trailing remainder < local_iterations
    is dropped, mirroring drop_last at round granularity); returns
    ``(state, per-round weighted loss [rounds])``.

    ``pipeline="device"`` swaps the dense epoch inputs for the
    device-resident form: the returned function takes ``(state,
    inv_x [S, N_max + 1, *stored], inv_y [S, N_max + 1], idx [S, steps, B],
    live=None, poison=None)`` — the inventory is uploaded once per fit and
    reused every epoch, the per-epoch transfer is the int32 index plan
    (data/batching.py EpochPlan), and batches are gathered on-device
    round-by-round inside the scan: ONE row gather a round from the
    inventory's resident form (data/api.py SiteInventory; weights derived
    from ``idx``, padding read from the inventory's zero row, bit-exact with
    the host materialization — :func:`_gather_batch`). ``poison [S,
    rounds]`` is the FaultPlan NaN-injection
    mask (a traced input like ``live`` — one compiled program per fit
    regardless of the fault pattern). The device path always delivers rounds
    as scan xs (the index plan is KB-sized; ``rounds_scan_xs`` only governs
    the host path's dense arrays).

    ``donate_state=True`` donates the carried ``state`` argument's buffers to
    the epoch (``jax.jit(donate_argnums=0)``): the update writes in place
    instead of allocating a second params+optimizer copy per epoch. Callers
    must treat the passed-in state as CONSUMED — rebind to the returned state
    and snapshot (copy) anything kept longer (the trainer's best-state
    tracking does exactly that).

    Fault tolerance (robustness/): ``live [S, rounds]`` is the optional
    scheduled-liveness mask — a TRACED input, so a different fault pattern
    never recompiles the epoch. Each round a site contributes iff it is
    scheduled live AND its round gradient is finite AND it is not
    quarantined; dead sites are zero-weighted inside every engine's
    ``aggregate`` (``jnp.where``-masked payloads, weighted mean renormalized
    over live weight only) and their engine state is frozen for the round. A
    site whose gradient stays non-finite for ``quarantine_rounds``
    consecutive rounds trips a sticky quarantine flag (``TrainState.health``;
    ``quarantine_rounds == 0`` disables the sticky flag but keeps the
    per-round skip). A round with NO live weight leaves
    params/optimizer/batch-stats untouched. ``quarantine_rounds < 0`` with no
    mask statically compiles the fault machinery OUT — the exact
    pre-robustness program, for benchmarking the machinery's cost.
    ``quarantine_rounds=None`` means the default (3).

    Buffered-async aggregation (r13 — elastic rounds): ``staleness_bound >
    0`` switches the aggregation semantics from bulk-synchronous to
    staleness-bounded buffered-async. Each virtual site owns a per-slot
    update buffer riding ``TrainState.buffers`` through the rounds scan: a
    round where the site ARRIVES (scheduled live AND finite AND not
    quarantined) deposits its fresh gradient + example weight and resets the
    slot's age to 0; a round where it doesn't (drop, straggler ``delay_at``,
    membership hole) leaves the buffer and ages it. Aggregation then runs
    over the BUFFERS, each slot's weight scaled by ``staleness_decay^age``
    (engines/base.py ``staleness_weights``) and hard-masked past
    ``staleness_bound`` exactly like a dead site — so a straggling update
    keeps pulling the model with fading weight instead of being lost, and a
    site that stops arriving fades out instead of stalling the round. The
    round loss / sync-BN / health counters stay keyed on FRESH arrivals; a
    round with no in-bound buffered weight holds params/optimizer exactly
    like an all-dead bulk-sync round. ``staleness_bound == 0`` (default)
    statically compiles ALL of it out — the exact bulk-sync program
    (lowering-identical; checks/semantic.py S005 "async-off"), and since
    ``decay^0 == 1`` an async round where EVERY site arrives is bit-identical
    to the bulk-sync round anyway. Arrival masks are traced inputs, so churn
    and straggle patterns never recompile.

    Overlapped rounds (r14 — compute/communication overlap):
    ``overlap_rounds=True`` software-pipelines the rounds scan so round
    *t*'s aggregation collective is issued against a double-buffered stash
    (``TrainState.overlap``) while round *t+1*'s batch gather and
    forward/backward run — the two are data-independent, so XLA's
    latency-hiding scheduler can split the collective into start/done and
    hide ICI/DCN time under the compute (the TPUv4 pjit overlap playbook;
    an ``optimization_barrier`` pins the stash read ahead of the batch
    block). The cost is ONE ROUND of update delay: round *t*'s gradients
    are computed at parameters that do not yet include round *t−1*'s
    update (classic pipelined/delayed SGD — momentum smooths the one-step
    staleness exactly as it does for the buffered-async mode). The stash
    rides ``TrainState`` rather than the bare scan carry, so nothing is
    dropped at epoch boundaries (the last round of epoch *e* applies at
    the first round of epoch *e+1*) and checkpoint/resume keeps the
    in-flight round. The very first round of a fit applies nothing
    (``valid=0`` — reported as a NaN loss, like an all-dead round).
    Mutually exclusive with ``staleness_bound > 0`` (two different
    staleness semantics over one buffer would compound); implies the
    guarded round form. ``overlap_rounds=False`` (default) statically
    compiles ALL of it out — the exact legacy program (S005
    "overlap-off").

    Telemetry (telemetry/metrics.py): ``telemetry=True`` accumulates, every
    round, per-site grad/update norms, the engine aggregation residual and
    modeled payload bytes into ``state.telemetry`` — traced values riding the
    same rounds scan (zero extra host syncs, zero recompiles).
    ``telemetry=False`` (default) statically compiles all of it out and
    carries ``state.telemetry=None``: the exact pre-telemetry program, same
    pattern as ``quarantine_rounds=-1``.

    Hostile sites (r17 — robustness/attacks.py, parallel/collectives.py):
    ``attack_plan`` is an optional :class:`~..robustness.attacks.AttackPlan`
    whose STATIC transform parameters (scale factor, noise σ, seeds) are
    closed over at trace time; the per-(site, round) attack pattern arrives
    as ``attack [S, rounds]`` — an int32 CODE mask fed as a traced input
    exactly like ``live``, so one compiled program per fit covers every
    pattern of the plan. The transform applies to each site's ROUND
    GRADIENT inside the per-site phase (before engine compression), and
    composes freely with FaultPlan drops/delays/NaN poisoning and packing.
    ``robust_agg`` selects the engines' byzantine-robust site reducer (the
    engine must be built with the SAME mode — engines/base.py); any value
    other than ``"none"`` also switches on the anomaly-scored REPUTATION
    layer: per round, each live site's distance-to-robust-aggregate and
    gradient-norm z-scores (across the live cohort, on-device scalar psums
    only) drive ``health.suspect_streak``/``health.anomaly``, and a site
    whose score exceeds ``reputation_z`` for ``reputation_rounds``
    CONSECUTIVE rounds trips the same sticky ``quarantined`` flag as a NaN
    site (``reputation_rounds=0`` scores without quarantining). z-scores
    need a cohort to stand out from: the threshold must be below
    ``(S_live - 1)/sqrt(S_live)`` to be reachable at all, so small-S runs
    lower ``reputation_z`` or rely on the robust reducer alone.
    ``robust_agg="none"`` (default) compiles ALL of it out — the exact
    legacy program (S005 "robust-off"); the mask input is rejected unless
    an attack plan was given.

    Slice elasticity (r19 — robustness/faults.py slice windows): on a
    sliced mesh the epoch accepts an optional ``slice_live [num_slices,
    rounds]`` TRACED input (replicated — it is tiny), the whole-slice twin
    of ``live``. Each round, every member multiplies its own slice's gate
    into the site-level contribute mask, so a dead slice's members are
    excluded from every engine's aggregate, sync-BN, the round loss and
    the weight renormalization EXACTLY as if the mask had zeroed its sites
    outright — bit-identical params, per engine, packed and unpacked
    (tests/test_multislice.py pins it). ``min_slices`` is the slice-quorum
    floor: a round with fewer live slices HOLDS — params, optimizer,
    engine state, health, buffers, stats and the overlap stash all freeze,
    the loss reports NaN, and (telemetry on) the per-site ``held_rounds``
    accumulator counts it — rather than training on a rump cohort. The
    quorum count is a local reduction of the replicated mask, so slice
    faults add ZERO collectives to the program (the wire proofs — S002 —
    hold unchanged on slice-fault cells). ``slice_live=None`` compiles the
    exact r18 program (S005 "slicefaults-off"), and since ``×1.0`` is
    exact an all-slices-live mask reproduces it value-for-value; changing
    WHICH slices die WHEN never retraces. The mask is rejected on unsliced
    topologies (there is no slice tier to fault).

    Site-axis realization (all forms run the *same* per-site program):

    - ``mesh`` given → ``shard_map`` over the mesh's ``site`` axis, with
      ``K = S / mesh_sites`` virtual sites PACKED per device (K=1 is the
      classic one-site-per-slice case): the per-site phase runs under an
      inner vmap over the device's ``[K, …]`` block and aggregation is the
      two-level packed reduction (parallel/collectives.py PackedAxis) —
      local in-register reduce over the packed axis, ONE cross-device
      collective of the partial over ICI. The multi-chip path; how an
      8-device mesh trains 512+ sites in one compiled program (r12).
    - ``mesh=None`` → ``jax.vmap(axis_name="site")``: all S sites fold onto
      the local device as a batched dimension; ``psum``/``all_gather`` resolve
      over the vmapped axis. This is how one TPU chip simulates 32 federated
      sites (BASELINE.json north star) at full MXU utilization.
    """

    assert pipeline in ("host", "device"), pipeline
    model_axis = _model_axis_of(mesh)
    # multi-slice (r18): a mesh built by parallel/mesh.py sliced_site_mesh
    # carries the outer DCN axis — per-site data then shards over the
    # (slice, site) pair and aggregation grows the inter-slice tier
    # (parallel/collectives.py three_level_psum). Single-slice meshes keep
    # the exact legacy program: site_part is the plain site axis and the
    # PackedAxis carries no slice name.
    n_slices = slice_count(mesh)
    sliced = mesh is not None and SLICE_AXIS in mesh.axis_names
    site_part = site_axis_of(mesh) if mesh is not None else SITE_AXIS
    mesh_site_members = (
        dict(mesh.shape)[SITE_AXIS] if mesh is not None else 1
    )
    if quarantine_rounds is None:
        quarantine_rounds = 3  # the default threshold
    if staleness_bound < 0:
        raise ValueError(
            f"staleness_bound must be >= 0, got {staleness_bound}"
        )
    if not 0.0 < staleness_decay <= 1.0:
        raise ValueError(
            f"staleness_decay must be in (0, 1], got {staleness_decay}"
        )
    # trace-time static: the buffered-async machinery exists iff the bound is
    # positive — staleness_bound=0 compiles the exact bulk-sync program
    buffered = staleness_bound > 0
    # builder kwarg, never a tracer: the static TrainConfig.overlap_rounds
    overlap = bool(overlap_rounds)  # jaxlint: disable=R005
    from ..parallel.collectives import ROBUST_AGGS

    if robust_agg not in ROBUST_AGGS:
        raise ValueError(
            f"robust_agg must be one of {ROBUST_AGGS}, got {robust_agg!r}"
        )
    # trace-time static: the reputation layer exists iff a robust reducer is
    # active — robust_agg="none" compiles the exact legacy program
    reputation = robust_agg != "none"
    if reputation_rounds < 0:
        raise ValueError(
            f"reputation_rounds must be >= 0, got {reputation_rounds}"
        )
    # the attack transform's static parameters, closed over at trace time
    # (robustness/attacks.py); the per-(site, round) pattern is a traced
    # mask, so changing WHO attacks WHEN never recompiles
    atk = None
    if attack_plan is not None and attack_plan.injects_attacks():
        from ..robustness.attacks import make_attack_fn

        atk = make_attack_fn(attack_plan)
    # privacy plane (r20) trace-time statics: DP clip/noise parameters are
    # closed over (noise is counter-keyed by (dp_seed, site, round), like
    # AttackPlan noise — chunk/resume/packing-independent); the head
    # partition patterns resolve to leaf paths at trace time from the real
    # params structure. Both off (the defaults) build NOTHING — the epoch
    # program is lowering-identical to the legacy one (S005 "dp-off" /
    # "personalize-off").
    from ..privacy.dpsgd import dp_enabled

    dp_on = dp_enabled(dp_clip, dp_noise_multiplier)
    # builder kwarg, never a tracer: the static TrainConfig.personalize
    personal_on = bool(tuple(personalize))  # jaxlint: disable=R005
    # rnd-aware engine dispatch (r20): the trainer always has the traced
    # global round counter to offer, but legacy/fixture engines keep the
    # pre-r20 aggregate signature — resolve from the signature like
    # telemetry's _accepts_pack (never `except TypeError`, which would
    # swallow a genuine TypeError raised inside an rnd-aware engine)
    import inspect

    try:
        _agg_sig = inspect.signature(engine.aggregate).parameters
    except (TypeError, ValueError):  # builtins/C callables: assume legacy
        _agg_sig = {}
    _agg_takes_rnd = "rnd" in _agg_sig or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in _agg_sig.values()
    )

    def engine_aggregate(grads, es, weight, axis, live, rnd):
        with jax.named_scope(scopes.ENGINE):
            if _agg_takes_rnd:
                return engine.aggregate(grads, es, weight, axis, live=live,
                                        rnd=rnd)
            return engine.aggregate(grads, es, weight, axis, live=live)

    if min_slices < 1:
        raise ValueError(f"min_slices must be >= 1, got {min_slices}")
    if min_slices > 1 and not sliced:
        raise ValueError(
            f"min_slices={min_slices} needs a sliced mesh (num_slices > 1) "
            "— there is no slice quorum on a single-slice topology"
        )
    if min_slices > 1 and min_slices > n_slices:
        raise ValueError(
            f"min_slices={min_slices} exceeds the mesh's {n_slices} slices "
            "— every round would hold"
        )
    if overlap and buffered:
        raise ValueError(
            "overlap_rounds and staleness_bound > 0 are mutually exclusive: "
            "both buffer per-site updates with their own staleness "
            "semantics (one-round pipeline delay vs decay^age weighting) "
            "and composing them would compound the delays"
        )

    def loss_fn(params, batch_stats, rng, x, y, w):
        loss, new_stats = task.loss(params, batch_stats, rng, x, y, w)
        if model_axis is not None:
            # The forward runs on every model-axis member (sequence-sharded
            # inside the model, logits replicated by its final gather), so an
            # unmasked loss would seed the head cotangent once PER member and
            # the later grad psum would count head grads n×. Keep member 0's
            # loss only: its cotangent reaches every member's sequence chunk
            # through the transposed collectives (reduce-scatter / ppermute),
            # and the psum over the axis then assembles the exact full grad.
            keep = (jax.lax.axis_index(model_axis) == 0).astype(loss.dtype)
            loss = loss * keep
        return loss, new_stats

    _value_and_grad = jax.value_and_grad(loss_fn, has_aux=True)

    def grad_fn(*args):
        with jax.named_scope(scopes.MODEL):
            return _value_and_grad(*args)

    def epoch_over_sites(state: TrainState, x, y, w, live, site_axes,
                         inner_axis, inventory=None, poison=None,
                         attack=None, slice_live=None):
        """Run one epoch for the k in-device sites in ``x [k, steps, B, ...]``.

        Device pipeline (``inventory`` given): ``x`` is the ``[k, steps, B]``
        int32 index plan instead (``y``/``w`` are None) and each round's batch
        is gathered on-device from the resident ``inventory = (inv_x, inv_y)``
        just before its gradients — only one round's ``[k, L, B, ...]`` block
        is ever materialized, so peak HBM holds the inventory, not the dense
        epoch tensor.

        Only the per-site work (grads, engine factorization, stat
        accumulation) runs under the inner vmap; the optimizer update applies
        ONCE per round on the (replicated) aggregate. The scan carry
        therefore holds a single copy of params/opt_state — vmapping the
        whole round used to replicate them per site, costing ~k× the
        params+Adam-state in HBM writes every round (measured ~half the
        epoch time at 32 folded sites).

        ``site_axes`` is the bound axis (or (mesh, vmap-fold) pair) that the
        per-site phase's ``axis_index`` linearizes over (the same global,
        device-major site order as the data layout); ``inner_axis`` is the
        vmap axis name for the in-device block.

        Site packing (r12): on a mesh (``site_axes`` a tuple), aggregation
        is a TWO-LEVEL reduction. The per-site gradient phase stays under
        the inner vmap, but everything that communicates — the engine's
        ``aggregate``, sync-BN, the round loss — runs OUTSIDE it on the
        device's ``[k, …]`` virtual-site block with a
        :class:`~..parallel.collectives.PackedAxis`: payloads reduce over
        the packed axis in-register and ONE cross-device collective ships
        the unbatched partial. The legacy form (collectives inside the vmap,
        resolved through jax's batching rules) shipped the whole ``[k, …]``
        block over the mesh — k× the wire bytes per round; at the 512-site
        pack factors that is the difference between aggregation costing one
        model's worth of traffic per device and 64 of them. The folded-vmap
        topology (``mesh=None``) is unchanged — its "collectives" are local
        register reductions with no wire either way.
        """
        k, steps = x.shape[0], x.shape[1]
        # trace-time static: mesh topologies carry the (mesh, fold) pair and
        # take the packed two-level aggregation path; the vmap-folded
        # single-device topology keeps the classic in-vmap form. Sliced
        # meshes (r18) hand the PackedAxis the slice axis too — the same
        # engine calls then lower the three-tier reduction.
        packed = isinstance(site_axes, tuple)
        pax = (
            PackedAxis(SITE_AXIS, k, slice_name=SLICE_AXIS if sliced else None)
            if packed else None
        )
        rounds = steps // local_iterations
        L = rounds * local_iterations
        # privacy plane (r20): the head partition resolves against the REAL
        # params structure at trace time; the DP transform (clip + counter-
        # keyed noise) skips head leaves — they never ship, so the
        # mechanism has nothing to protect there. Both are trace-time
        # presence branches: off builds nothing (S005).
        head_paths = frozenset()
        if personal_on:
            from ..privacy.personalize import (
                graft_shared,
                head_leaf_paths,
                strip_tree,
            )

            head_paths = head_leaf_paths(state.params, personalize)
        dp = None
        if dp_on:
            from ..privacy.dpsgd import make_dp_fn

            dp = make_dp_fn(dp_clip, dp_noise_multiplier, dp_seed, head_paths)

        def _eng_grads(tree):
            """What the engine aggregates: the SHARED subtree under
            personalization (head leaves never reach the wire), the full
            tree otherwise."""
            if not personal_on:
                return tree
            return strip_tree(tree, head_paths, keep_head=False)

        def _full_agg(agg_shared):
            """The optimizer-facing aggregate at full params structure:
            shared leaves from the engine, head leaves exact zeros — the
            frozen global head copies provably never move (zero grad →
            zero Adam moments → zero update)."""
            if not personal_on:
                return agg_shared
            return graft_shared(state.params, agg_shared, head_paths)

        # split the steps axis in place ([k, rounds, L, B, ...] — a free
        # reshape). Each round's block then arrives either as rounds-leading
        # scan xs (default; see the moveaxis note below) or via a per-round
        # dynamic-slice on axis 1 (rounds_scan_xs=False, the measured-slower
        # A/B arm kept for re-benchmarks).
        def split_rounds(a):
            return a[:, :L].reshape((k, rounds, local_iterations) + a.shape[2:])

        # device pipeline: x IS the index plan; one split covers it. The
        # index plan is KB-sized, so it always rides as scan xs regardless of
        # the rounds_scan_xs arm (which exists for multi-GB dense inputs).
        x_rounds = split_rounds(x)
        y_rounds, w_rounds = (
            (None, None) if inventory is not None
            else (split_rounds(y), split_rounds(w))
        )
        poison_rounds = None if poison is None else poison[:, :rounds]
        use_scan_xs = rounds_scan_xs or inventory is not None
        # scheduled liveness, [k, rounds] f32 (None → all live; the branch is
        # trace-time static, so both forms compile once each, never per mask)
        live_rounds = (
            None if live is None else live[:, :rounds].astype(jnp.float32)
        )
        # hostile-site attack codes, [k, rounds] int32 (robustness/attacks.py
        # — 0 = honest; a traced input like `live`, trace-time presence
        # branch). The mask only works with the plan's static transform
        # params closed over above.
        if attack is not None and atk is None:
            raise ValueError(
                "an attack mask was fed but no attack_plan was given to "
                "make_train_epoch_fn (the plan carries the static transform "
                "parameters)"
            )
        attack_rounds = (
            None if (attack is None or atk is None)
            else attack[:, :rounds].astype(jnp.int32)
        )
        attack_on = attack_rounds is not None
        # slice-liveness gate (r19, robustness/faults.py slice windows): the
        # [num_slices, rounds] whole-slice mask arrives REPLICATED (it is
        # tiny); each member reads its OWN slice's row by axis index — no
        # collective — and multiplies it into the per-round site gate, so a
        # dead slice's members mask out exactly like site-level drops. The
        # per-round live-slice count (a local reduction of the replicated
        # mask, again no collective) drives the min_slices quorum hold.
        # Trace-time presence branch like `live`: slice_live=None compiles
        # the exact r18 program, and changing WHO dies WHEN never retraces.
        if slice_live is not None and not sliced:
            raise ValueError(
                "a slice_live mask was fed on an unsliced topology — slice "
                "faults need a (slice, site, model) mesh "
                "(TrainConfig.num_slices > 1)"
            )
        if slice_live is not None and slice_live.shape[0] != n_slices:
            # a wrong-row-count mask would otherwise be silently accepted:
            # XLA clamps the out-of-bounds own-row gather, so extra slices
            # would inherit the LAST row's liveness instead of erroring
            raise ValueError(
                f"slice_live has {slice_live.shape[0]} slice rows but the "
                f"mesh has {n_slices} slices"
            )
        slice_gate = slice_live is not None
        # quorum machinery exists iff a floor above 1 is configured AND the
        # mask is fed — min_slices with no mask adds nothing (S005
        # "slicefaults-off")
        quorum_on = slice_gate and min_slices > 1
        sl_own_rounds = quorum_rounds = None
        if slice_gate:
            sl_full = slice_live[:, :rounds].astype(jnp.float32)
            sl_own_rounds = sl_full[jax.lax.axis_index(SLICE_AXIS)]
            if quorum_on:
                quorum_rounds = jnp.sum(sl_full, axis=0)
        # trace-time static gate: the fault machinery (isfinite reduction over
        # the gradient tree, where-freezes/selects on engine state, params,
        # opt state, BN stats) compiles in only when quarantine is enabled OR
        # a liveness mask is fed; quarantine_rounds=-1 with no mask restores
        # the exact pre-robustness program (the bench escape hatch). The
        # buffered-async mode needs the arrival gates, so it implies guard;
        # so does the overlapped-rounds mode (its empty-stash first round is
        # a zero-live-weight round, which only the guarded form holds).
        # the reputation layer needs the health-updating guarded round; so
        # does an attack mask (an attacked round must be skippable/scorable)
        guard = (
            quarantine_rounds >= 0 or live is not None or buffered or overlap
            or reputation or attack_on or slice_gate
        )
        health = state.health  # filled by epoch_fn before any shard_map
        # trace-time static: telemetry accumulators exist iff the epoch was
        # built with telemetry=True (_ensure_aux normalizes the state), so a
        # telemetry-off program carries zero telemetry ops
        telem = state.telemetry is not None
        # modeled per-round PER-DEVICE collective payload — pure shape
        # arithmetic over the gradient pytree, folded in as a constant. On a
        # packed mesh the pack factor k is what makes the figure honest:
        # psum-shaped exchanges reduce over the packed axis before the wire
        # (k-invariant), only the factor gather scales with k — the model is
        # verified against the traced program by checks/semantic.py S002.
        # under personalization the wire carries the SHARED subtree only —
        # the model must charge exactly what ships (S002 proves it)
        wire_tmpl = _eng_grads(state.params)
        wire_b = (
            payload_bytes_of(engine, wire_tmpl, pack=k if packed else 1)
            if telem else 0.0
        )
        # per-tier split (r18): the inter-slice hop's modeled PER-SLICE
        # bytes — 0.0 on single-slice meshes and the vmap fold (no DCN
        # tier); like wire_b a trace-time constant, verified by the sliced
        # semantic cells rather than merely modeled
        dcn_b = (
            dcn_bytes_of(
                engine, wire_tmpl, pack=k,
                sites_per_slice=k * mesh_site_members, slices=n_slices,
            )
            if telem and packed else 0.0
        )

        def _ts_round(ts, gsq, rsq):
            """Per-site accumulator update for this round from the (already
            reduced) squared grad/residual norms — scalars in the classic
            in-vmap form, ``[k]`` vectors in the packed form. ``grad_sq_last``
            keeps the raw value (NaN = "this site blew up", the signal);
            the sums/max take finite rounds only, or one bad round would
            poison them for the rest of the fit. The update-norm slots are
            filled after the (global) optimizer step in ``one_round``."""
            if ts is None:
                return None
            gsq_f = jnp.where(jnp.isfinite(gsq), gsq, 0.0)
            return {
                "dcn_bytes": ts["dcn_bytes"] + dcn_b,
                "grad_sq_last": gsq,
                "grad_sq_max": jnp.maximum(ts["grad_sq_max"], gsq_f),
                "grad_sq_sum": ts["grad_sq_sum"] + gsq_f,
                # held rounds are counted at the quorum-hold select in
                # one_round (this whole update reverts on a held round)
                "held_rounds": ts["held_rounds"],
                "payload_bytes": ts["payload_bytes"] + wire_b,
                "residual_sq_sum": ts["residual_sq_sum"]
                + jnp.where(jnp.isfinite(rsq), rsq, 0.0),
                "rounds": ts["rounds"] + 1,
                "update_sq_last": ts["update_sq_last"],
                "update_sq_sum": ts["update_sq_sum"],
            }

        def one_round(carry, xs):
            (params, batch_stats, opt_state, engine_state, health, telem_st,
             buffers, ov, personal, rng, rnd) = carry
            pz = None
            if use_scan_xs:
                parts = list(xs)
                if inventory is not None:
                    ib = parts.pop(0)  # [k, L, B] — this round's index block
                    if poison_rounds is not None:
                        pz = parts.pop(0)  # [k] — this round's NaN gate
                else:
                    xb, yb, wb = parts[:3]  # [k, L, B, ...] — this round
                    parts = parts[3:]
                lb = (
                    parts.pop(0) if live_rounds is not None
                    else jnp.ones((k,), jnp.float32)
                )
                ab = parts.pop(0) if attack_on else None
                sl_t = parts.pop(0) if slice_gate else None
                q_t = parts.pop(0) if quorum_on else None
            else:
                xb, yb, wb = (
                    jax.lax.dynamic_index_in_dim(a, xs, axis=1, keepdims=False)
                    for a in (x_rounds, y_rounds, w_rounds)
                )
                lb = (
                    jnp.ones((k,), jnp.float32) if live_rounds is None
                    else jax.lax.dynamic_index_in_dim(
                        live_rounds, xs, axis=1, keepdims=False
                    )
                )
                ab = (
                    jax.lax.dynamic_index_in_dim(
                        attack_rounds, xs, axis=1, keepdims=False
                    ) if attack_on else None
                )
                sl_t = (
                    jax.lax.dynamic_index_in_dim(
                        sl_own_rounds, xs, axis=0, keepdims=False
                    ) if slice_gate else None
                )
                q_t = (
                    jax.lax.dynamic_index_in_dim(
                        quorum_rounds, xs, axis=0, keepdims=False
                    ) if quorum_on else None
                )
            if slice_gate:
                # a dead slice == its sites dead: ×1.0 is exact, ×0 masks —
                # everything downstream (engine aggregate, sync-BN, loss,
                # weight renormalization) then excludes the slice exactly
                # like a site-level mask zeroing its band
                lb = lb * sl_t
            if quorum_on:
                # the quorum HOLD gate, decided before any compute: the
                # round's results are computed and then select-reverted —
                # branchless, so any slice-fault pattern is one program
                held = q_t < jnp.float32(min_slices)
                hold_prev = (
                    batch_stats, engine_state, health, telem_st, buffers, ov,
                    personal,
                )
            if overlap:
                # overlapped rounds: tie the stashed (previous-round) payload
                # and this round's batch block into one availability point.
                # The stash aggregation collectives and the gather+forward
                # are data-independent; the barrier keeps XLA from sinking
                # the stash read below the compute, so the latency-hiding
                # scheduler is free to issue collective-start first and hide
                # the ICI/DCN time under phase B (TPUv4 pjit overlap
                # playbook — the async start/done split happens in XLA).
                if inventory is not None:
                    ov, ib = jax.lax.optimization_barrier((ov, ib))
                else:
                    ov, xb = jax.lax.optimization_barrier((ov, xb))
            if inventory is not None:
                # on-device batch gather from the resident inventory — only
                # this round's [k, L, B, ...] block is materialized
                inv_x, inv_y = inventory
                gather = functools.partial(
                    _gather_batch, sample_shape=task.sample_shape
                )
                if pz is None:
                    xb, yb, wb = jax.vmap(gather)(inv_x, inv_y, ib)
                else:
                    xb, yb, wb = jax.vmap(gather)(inv_x, inv_y, ib, pz)
            rng, sub = jax.random.split(rng)

            def site_micro(xs, ys, ws, ab_site=None, pr_site=None):
                """One site's micro-batch gradient phase — shared by the
                packed and classic forms (always under the inner vmap;
                ``axis_index`` linearizes to the global, device-major site id
                for the dropout-RNG fold, so packed and unpacked runs draw
                identical keys). ``ab_site`` is this site's attack code for
                the round (robustness/attacks.py) — the byzantine transform
                applies to the finished round gradient, before any engine
                compression, keyed by the GLOBAL site id and round so the
                attack replays bit-identically across topologies.
                ``pr_site`` is this site's personalized head subtree (r20,
                privacy/personalize.py) — the forward runs on the merged
                params, so the gradient covers head AND shared leaves (the
                apply half partitions them). The DP-SGD transform (r20,
                privacy/dpsgd.py) runs on the finished round gradient
                BEFORE the attack: an honest site privatizes what it ships,
                a hostile one lies about the privatized quantity."""
                site_ix = jax.lax.axis_index(site_axes)
                p_site = params
                if pr_site is not None:
                    from ..privacy.personalize import merge_head

                    p_site = merge_head(params, pr_site)

                def micro(acc, mb):
                    g_sum, n_sum, stats = acc
                    xm, ym, wm, i = mb
                    key_i = jax.random.fold_in(jax.random.fold_in(sub, site_ix), i)
                    (loss, new_stats), grads = grad_fn(p_site, stats, key_i, xm, ym, wm)
                    if model_axis is not None:
                        # assemble the full gradient (and un-mask the loss
                        # scalar) from the per-member pieces — see loss_fn
                        grads = jax.lax.psum(grads, model_axis)
                        loss = jax.lax.psum(loss, model_axis)
                    n = wm.sum()
                    g_sum = jax.tree.map(lambda a, g: a + g * n, g_sum, grads)
                    return (g_sum, n_sum + n, new_stats), loss * n

                g0 = jax.tree.map(jnp.zeros_like, p_site)
                (g_sum, n_sum, new_stats), loss_sums = jax.lax.scan(
                    micro,
                    (g0, jnp.zeros(()), batch_stats),
                    (xs, ys, ws, jnp.arange(local_iterations)),
                )
                site_grad = jax.tree.map(
                    lambda g: g / jnp.maximum(n_sum, 1.0), g_sum
                )
                if dp is not None:
                    site_grad = dp(site_grad, rnd, site_ix)
                if attack_on:
                    site_grad = atk(site_grad, ab_site, rnd, site_ix)
                return site_grad, n_sum, new_stats, loss_sums.sum()

            def _ts_round_site(ts, site_grad, agg):
                """Classic (in-vmap) accumulator update: scalar norms per
                site, reduced in tree order (telemetry.metrics.tree_sq_sum —
                the host-recompute tests depend on that order). The residual
                covers the SHARED (shipped) subtree — see packed_apply's
                res_sq note; identical trees when personalization is off."""
                if ts is None:
                    return None
                return _ts_round(
                    ts,
                    tree_sq_sum(site_grad),
                    tree_sq_sum(jax.tree.map(
                        lambda g, a: g - a,
                        _eng_grads(site_grad), _eng_grads(agg),
                    )),
                )

            def _rows_sq_sum(tree):
                """Per-virtual-site Σx² over a [k, …]-leading pytree — the
                batched twin of tree_sq_sum, same f32 leaf-order
                accumulation, one [k] vector out."""
                s = jnp.zeros((k,), jnp.float32)
                for leaf in jax.tree.leaves(tree):
                    s = s + jnp.sum(
                        jnp.square(leaf.astype(jnp.float32)).reshape(k, -1),
                        axis=1,
                    )
                return s

            def _per_site(vec, like):
                """Broadcast a [k] per-virtual-site gate against a [k, …]
                leaf."""
                return vec.reshape((k,) + (1,) * (like.ndim - 1))

            # -- fault-pipeline pieces shared by the packed ([k]-vector) and
            # classic (in-vmap scalar) round forms. ONE definition of the
            # liveness/quarantine/loss semantics — only the collective
            # placement (two_level_psum outside the vmap vs lax.psum inside
            # it) stays in the two callers below.

            def _liveness_gate(ls, site_grad, hs, rows=None):
                """scheduled-live AND finite AND not quarantined. ``rows``
                None = scalar per site (under the inner vmap); ``rows=k`` =
                one [k] vector over the device's virtual-site block."""
                if rows is None:
                    finite = jnp.array(True)
                    for leaf in jax.tree.leaves(site_grad):
                        finite &= jnp.isfinite(leaf).all()
                else:
                    finite = jnp.ones((rows,), bool)
                    for leaf in jax.tree.leaves(site_grad):
                        finite &= jnp.isfinite(leaf).reshape(rows, -1).all(axis=1)
                contribute = (
                    ls * finite.astype(jnp.float32)
                    * (1.0 - (hs["quarantined"] > 0).astype(jnp.float32))
                )
                return finite, contribute

            def _freeze_dead(new_tree, old_tree, gate):
                """Hold a dead site's state for the round: its error-feedback
                residual / warm-start subspace must resume where it left off
                when the site returns, not absorb a round it never
                participated in. ``gate(leaf)`` broadcasts the contribute
                mask against a leaf (identity for scalars-in-vmap,
                ``_per_site`` for [k, …] blocks)."""
                return jax.tree.map(
                    lambda new, old: jnp.where(gate(new), new, old),
                    new_tree, old_tree,
                )

            def _deposit(bf, site_grad, n_sum, arrived, gate):
                """Buffered-async arrival: a contributing site deposits this
                round's fresh gradient + weight and resets its age; everyone
                else's buffer survives and ages one round. ``arrived`` is the
                bool arrival mask (scalar per site under the inner vmap, [k]
                on the packed block); ``gate(leaf)`` broadcasts it against a
                gradient leaf — the same shape-polymorphic convention as
                ``_freeze_dead``. Only FINITE gradients are ever deposited
                (arrival requires finiteness), so the buffers stay NaN-free
                by construction."""
                return {
                    "grads": jax.tree.map(
                        lambda g, b: jnp.where(gate(g), g, b),
                        site_grad, bf["grads"],
                    ),
                    "weight": jnp.where(arrived, n_sum, bf["weight"]),
                    "age": jnp.where(arrived, 0, bf["age"] + 1),
                }

            def _personal_apply(pr, site_grad, gate, batched):
                """Per-site personalized-head update (r20): each site's own
                optimizer row advances on its own head gradient — heads
                never enter the engine aggregate. ``gate(leaf)`` broadcasts
                the round's contribute mask like :func:`_freeze_dead`
                (None = the unguarded program: always update); ``batched``
                selects the packed [k]-leading form (rows vmapped) vs the
                classic in-vmap scalar form."""
                if pr is None:
                    return pr
                from ..privacy.personalize import strip_tree as _strip

                hg = _strip(site_grad, head_paths, keep_head=True)

                def upd(hp, ho, g):
                    with jax.named_scope(scopes.OPTIMIZER):
                        u, no = optimizer.update(g, ho, hp)
                        return optax.apply_updates(hp, u), no

                if batched:
                    new_p, new_o = jax.vmap(upd)(pr["params"], pr["opt"], hg)
                else:
                    new_p, new_o = upd(pr["params"], pr["opt"], hg)
                if gate is None:
                    return {"params": new_p, "opt": new_o}

                def keep(new, old):
                    return jnp.where(gate(new), new, old)

                return {
                    "params": jax.tree.map(keep, new_p, pr["params"]),
                    "opt": jax.tree.map(keep, new_o, pr["opt"]),
                }

            def _round_loss(loss_sum, contribute, total_live, psum):
                """Round-weighted global loss over LIVE sites (for logs);
                NaN-safe: a dead site's loss sum is where-excluded. An
                all-dead round has no training loss — report NaN, not a
                spurious 0.0 that would drag the epoch mean down (the
                trainer nan-means per-round losses into the epoch figure)."""
                return jnp.where(
                    total_live > 0,
                    psum(jnp.where(contribute > 0, loss_sum, 0.0))
                    / jnp.maximum(total_live, 1.0),
                    jnp.nan,
                )

            def _health_round(hs, finite, contribute):
                """Health counters: streak of consecutive non-finite rounds,
                sticky quarantine once it reaches the threshold, lifetime
                skip count — elementwise, so the same code serves the scalar
                and [k]-vector forms."""
                streak = jnp.where(finite, 0, hs["streak"] + 1)
                quarantined = hs["quarantined"]
                if quarantine_rounds > 0:
                    quarantined = jnp.maximum(
                        quarantined,
                        (streak >= quarantine_rounds).astype(jnp.int32),
                    )
                return {
                    "streak": streak,
                    "skips": hs["skips"] + (contribute <= 0).astype(jnp.int32),
                    "quarantined": quarantined,
                }

            def _reputation_round(hs_prev, hs_new, dsq, nsq, contribute, rsum):
                """Anomaly-scored reputation (r17): z-scores of this round's
                distance-to-(robust)-aggregate and gradient norm across the
                LIVE cohort — cross-site exchange is four scalar psums, so
                the engines' wire models are untouched. A live site whose
                max z exceeds ``reputation_z`` extends its suspect streak;
                ``reputation_rounds`` CONSECUTIVE suspect rounds latch the
                same sticky quarantine flag a NaN streak does. A site
                sitting the round out (drop, straggle, quarantine) holds
                both its streak and its EMA score — absence is not
                evidence either way. Elementwise over the scalar and
                [k]-vector forms like :func:`_health_round`."""
                livef = (contribute > 0).astype(jnp.float32)
                n_live = jnp.maximum(rsum(livef), 1.0)

                def z_of(x):
                    xf = jnp.where(livef > 0, x, 0.0)
                    m1 = rsum(xf) / n_live
                    m2 = rsum(xf * xf) / n_live
                    std = jnp.sqrt(jnp.maximum(m2 - m1 * m1, 0.0))
                    return (x - m1) / jnp.maximum(std, 1e-12)

                # norms, not squares: closer to Gaussian, so the z threshold
                # means the same thing across engines and models. A
                # non-finite site's NaN score propagates to z = NaN, which
                # fails every comparison — it is scored by the NaN streak
                # machinery, not the reputation layer.
                z = jnp.maximum(
                    z_of(jnp.sqrt(jnp.maximum(dsq, 0.0))),
                    z_of(jnp.sqrt(jnp.maximum(nsq, 0.0))),
                )
                suspect = (z > reputation_z) & (contribute > 0)
                streak = jnp.where(
                    suspect, hs_prev["suspect_streak"] + 1,
                    jnp.where(contribute > 0, 0, hs_prev["suspect_streak"]),
                )
                quarantined = hs_new["quarantined"]
                if reputation_rounds > 0:
                    quarantined = jnp.maximum(
                        quarantined,
                        (streak >= reputation_rounds).astype(jnp.int32),
                    )
                anomaly = jnp.where(
                    contribute > 0,
                    0.9 * hs_prev["anomaly"] + 0.1 * jnp.maximum(z, 0.0),
                    hs_prev["anomaly"],
                )
                return {
                    **hs_new, "suspect_streak": streak,
                    "quarantined": quarantined, "anomaly": anomaly,
                }

            def packed_apply(hs, ts, bf, pr, ls, es, site_grad, n_sum,
                             stats_k, loss_site):
                """The communicate/apply half of the two-level round, on an
                already-computed per-site payload: engine aggregate, sync-BN,
                round loss and health on the [k]-batched block with
                PackedAxis collectives — one cross-device collective per
                payload, k-invariant psum wire. In the overlapped-rounds
                mode the payload comes from the previous round's stash
                instead of this round's fresh gradients. Under
                personalization the engine sees (and ships) the SHARED
                subtree only; head gradients update each site's own
                ``pr`` row."""
                gsq = _rows_sq_sum(site_grad) if ts is not None else None
                if not guard:
                    agg, es_new = engine_aggregate(
                        _eng_grads(site_grad), es, n_sum, pax, None, rnd
                    )
                    agg = _full_agg(agg)
                    pr_new = _personal_apply(pr, site_grad, None, batched=True)
                    if task.has_batch_stats:
                        scale = site_weight_scale(n_sum, pax)
                        stats_out = jax.tree.map(
                            lambda s: weighted_site_sum(s, scale, pax).astype(
                                s.dtype
                            ),
                            stats_k,
                        )
                    else:
                        stats_out = batch_stats
                    loss_round = two_level_psum(loss_site, pax) / jnp.maximum(
                        two_level_psum(n_sum, pax), 1.0
                    )
                    ts_new = (
                        None if ts is None
                        else _ts_round(
                            ts, gsq,
                            _rows_sq_sum(jax.tree.map(
                                lambda g, a: g - a[None],
                                _eng_grads(site_grad), _eng_grads(agg),
                            )),
                        )
                    )
                    return (agg, es_new, hs, ts_new, bf, pr_new, stats_out,
                            loss_round, None)
                finite, contribute = _liveness_gate(ls, site_grad, hs, rows=k)
                n_eff = n_sum * contribute
                if buffered:
                    # buffered-async: arrivals deposit, everyone aggregates
                    # from the buffers at staleness-decayed weight; the
                    # engine's collectives (and therefore the S002-proven
                    # wire) are identical to the bulk-sync form
                    arrived = contribute > 0
                    bf = _deposit(
                        bf, site_grad, n_sum, arrived,
                        lambda leaf: _per_site(arrived, leaf),
                    )
                    stale_w = staleness_weights(
                        bf["age"], staleness_bound, staleness_decay
                    )
                    eff_w = bf["weight"] * stale_w
                    agg, es_new = engine_aggregate(
                        _eng_grads(bf["grads"]), es, eff_w, pax,
                        (stale_w > 0).astype(jnp.float32), rnd,
                    )
                    agg = _full_agg(agg)
                    es_new = _freeze_dead(
                        es_new, es, lambda leaf: _per_site(stale_w > 0, leaf)
                    )
                    # params-hold gate: total in-bound buffered weight; the
                    # loss/BN gates stay keyed on FRESH arrivals below.
                    # Heads update from FRESH arrivals only — they are not
                    # buffered (a head never leaves its site, so there is
                    # no in-flight copy to age).
                    total_live = two_level_psum(eff_w, pax)
                    total_fresh = two_level_psum(n_eff, pax)
                else:
                    agg, es_new = engine_aggregate(
                        _eng_grads(site_grad), es, n_sum, pax, contribute,
                        rnd,
                    )
                    agg = _full_agg(agg)
                    es_new = _freeze_dead(
                        es_new, es, lambda leaf: _per_site(contribute > 0, leaf)
                    )
                    total_live = two_level_psum(n_eff, pax)
                    total_fresh = total_live
                pr_new = _personal_apply(
                    pr, site_grad,
                    lambda leaf: _per_site(contribute > 0, leaf), batched=True,
                )
                if task.has_batch_stats:
                    scale = site_weight_scale(n_eff, pax)
                    zeroed = jax.tree.map(
                        lambda s: jnp.where(
                            _per_site(contribute > 0, s), s, jnp.zeros_like(s)
                        ),
                        stats_k,
                    )
                    syn = jax.tree.map(
                        lambda s: weighted_site_sum(s, scale, pax).astype(
                            s.dtype
                        ),
                        zeroed,
                    )
                    stats_out = jax.tree.map(
                        lambda sn, old: jnp.where(total_fresh > 0, sn, old),
                        syn, batch_stats,
                    )
                else:
                    stats_out = batch_stats
                loss_round = _round_loss(
                    loss_site, contribute, total_fresh,
                    lambda v: two_level_psum(v, pax),
                )
                hs_new = _health_round(hs, finite, contribute)
                # ONE distance-to-aggregate figure serves both consumers:
                # the reputation z-score and the telemetry residual
                # ONE distance-to-aggregate figure serves both consumers —
                # computed over the SHARED (shipped) subtree: under
                # personalization a site's legitimately-divergent head
                # gradient never reaches the engine, so it must count
                # neither as compression residual nor as reputation anomaly
                res_sq = (
                    _rows_sq_sum(jax.tree.map(
                        lambda g, a: g - a[None],
                        _eng_grads(site_grad), _eng_grads(agg),
                    ))
                    if (reputation or ts is not None) else None
                )
                if reputation:
                    hs_new = _reputation_round(
                        hs, hs_new, res_sq,
                        _rows_sq_sum(_eng_grads(site_grad)),
                        contribute, lambda v: two_level_psum(v, pax),
                    )
                ts_new = (
                    None if ts is None else _ts_round(ts, gsq, res_sq)
                )
                return (agg, es_new, hs_new, ts_new, bf, pr_new, stats_out,
                        loss_round, total_live)

            def packed_round(hs, ts, bf, pr, ls, es):
                """The two-level round: per-site grads under the inner vmap,
                then :func:`packed_apply` on this round's fresh payload.
                (None arguments — no attack mask, no personal rows — are
                empty pytrees; vmap maps nothing over them.)"""
                site_grad, n_sum, stats_k, loss_site = jax.vmap(
                    site_micro, axis_name=inner_axis
                )(xb, yb, wb, ab, None if pr is None else pr["params"])
                return packed_apply(
                    hs, ts, bf, pr, ls, es, site_grad, n_sum, stats_k,
                    loss_site,
                )

            def site_apply(es, hs, ts, bf, pr, ls, site_grad, n_sum,
                           new_stats, loss_sum):
                """The communicate/apply half of the classic (in-vmap) round
                on an already-computed per-site payload — the scalar twin of
                :func:`packed_apply`."""
                if not guard:
                    # fault machinery statically compiled out: the exact
                    # legacy round (no finite check, no selects, no counters)
                    agg, es_new = engine_aggregate(
                        _eng_grads(site_grad), es, n_sum, site_axes, None,
                        rnd,
                    )
                    agg = _full_agg(agg)
                    pr_new = _personal_apply(
                        pr, site_grad, None, batched=False
                    )
                    if task.has_batch_stats:
                        scale = site_weight_scale(n_sum, site_axes)
                        new_stats = jax.tree.map(
                            lambda s: jax.lax.psum(s * scale, site_axes),
                            new_stats,
                        )
                    loss_round = jax.lax.psum(
                        loss_sum, site_axes
                    ) / jnp.maximum(jax.lax.psum(n_sum, site_axes), 1.0)
                    return (agg, es_new, hs, _ts_round_site(ts, site_grad, agg),
                            bf, pr_new, new_stats, loss_round, None)
                # -- liveness: a poisoned batch (data corruption, overflow,
                # fault injection) yields a non-finite site gradient; that
                # site is skipped this round and its streak counter advances
                # toward quarantine. All jnp.where / traced — no
                # recompilation.
                finite, contribute = _liveness_gate(ls, site_grad, hs)
                n_eff = n_sum * contribute
                if buffered:
                    # buffered-async (scalar-per-site twin of packed_round's
                    # branch): deposit on arrival, aggregate the buffers at
                    # staleness-decayed weight
                    arrived = contribute > 0
                    bf = _deposit(
                        bf, site_grad, n_sum, arrived, lambda _: arrived
                    )
                    stale_w = staleness_weights(
                        bf["age"], staleness_bound, staleness_decay
                    )
                    eff_w = bf["weight"] * stale_w
                    agg, es_new = engine_aggregate(
                        _eng_grads(bf["grads"]), es, eff_w, site_axes,
                        (stale_w > 0).astype(jnp.float32), rnd,
                    )
                    agg = _full_agg(agg)
                    es_new = _freeze_dead(es_new, es, lambda _: stale_w > 0)
                    total_live = jax.lax.psum(eff_w, site_axes)
                    total_fresh = jax.lax.psum(n_eff, site_axes)
                else:
                    agg, es_new = engine_aggregate(
                        _eng_grads(site_grad), es, n_sum, site_axes,
                        contribute, rnd,
                    )
                    agg = _full_agg(agg)
                    es_new = _freeze_dead(es_new, es, lambda _: contribute > 0)
                    total_live = jax.lax.psum(n_eff, site_axes)
                    total_fresh = total_live
                pr_new = _personal_apply(
                    pr, site_grad, lambda _: contribute > 0, batched=False
                )
                # sync-BN: example-weighted average of FRESHLY-ARRIVED sites'
                # running stats (dead sites' stats may be NaN → where-zeroed,
                # and their weight is already 0); a round with no arrivals
                # keeps the previous stats (stats are not buffered)
                if task.has_batch_stats:
                    scale = site_weight_scale(n_eff, site_axes)
                    new_stats = jax.tree.map(
                        lambda s: jnp.where(contribute > 0, s, jnp.zeros_like(s)),
                        new_stats,
                    )
                    new_stats = jax.tree.map(
                        lambda s: jax.lax.psum(s * scale, site_axes), new_stats
                    )
                    new_stats = jax.tree.map(
                        lambda syn, old: jnp.where(total_fresh > 0, syn, old),
                        new_stats, batch_stats,
                    )
                loss_round = _round_loss(
                    loss_sum, contribute, total_fresh,
                    lambda v: jax.lax.psum(v, site_axes),
                )
                hs_new = _health_round(hs, finite, contribute)
                if reputation:
                    hs_new = _reputation_round(
                        hs, hs_new,
                        tree_sq_sum(jax.tree.map(
                            lambda g, a: g - a,
                            _eng_grads(site_grad), _eng_grads(agg),
                        )),
                        tree_sq_sum(_eng_grads(site_grad)),
                        contribute,
                        lambda v: jax.lax.psum(v, site_axes),
                    )
                return (agg, es_new, hs_new, _ts_round_site(ts, site_grad, agg),
                        bf, pr_new, new_stats, loss_round, total_live)

            def site_part(es, hs, ts, bf, pr, ls, xs, ys, ws, ab_site=None):
                site_grad, n_sum, new_stats, loss_sum = site_micro(
                    xs, ys, ws, ab_site,
                    None if pr is None else pr["params"],
                )
                return site_apply(
                    es, hs, ts, bf, pr, ls, site_grad, n_sum, new_stats,
                    loss_sum,
                )

            if overlap:
                # -- overlapped rounds (r14): phase B computes THIS round's
                # per-site gradients at the carried (pre-update) params;
                # phase A aggregates and applies the STASHED previous round.
                # The two phases share no data, so the stash collectives
                # overlap the gather+forward in the XLA schedule (barrier
                # above). Health/telemetry are valid-gated: the empty-stash
                # first round must not count skips or accumulate rounds.
                fresh_grad, fresh_n, fresh_stats, fresh_loss = jax.vmap(
                    site_micro, axis_name=inner_axis
                )(xb, yb, wb, ab,
                  None if personal is None else personal["params"])
                ls_prev = ov["live"] * ov["valid"]
                if packed:
                    (agg, es_new, hs_new, ts_new, buffers, personal,
                     batch_stats, loss_round, total_live) = packed_apply(
                        health, telem_st, buffers, personal, ls_prev,
                        engine_state,
                        ov["grads"], ov["weight"], ov["stats"], ov["loss"],
                    )
                else:
                    (agg, es_new, hs_new, ts_new, buffers, personal, stats_k,
                     loss_k, tl_k) = jax.vmap(
                        site_apply,
                        in_axes=(0,) * 10,
                        out_axes=(0,) * 9,
                        axis_name=inner_axis,
                    )(engine_state, health, telem_st, buffers, personal,
                      ls_prev, ov["grads"], ov["weight"], ov["stats"],
                      ov["loss"])
                    agg = jax.tree.map(lambda a: a[0], agg)
                    batch_stats = jax.tree.map(lambda a: a[0], stats_k)
                    loss_round = loss_k[0]
                    total_live = tl_k[0]
                vgate = ov["valid"] > 0
                engine_state = es_new
                health = jax.tree.map(
                    lambda new, old: jnp.where(vgate, new, old), hs_new, health
                )
                telem_k = (
                    None if telem_st is None else jax.tree.map(
                        lambda new, old: jnp.where(vgate, new, old),
                        ts_new, telem_st,
                    )
                )
                # refill the stash with this round's fresh payload — its
                # aggregation is issued at the NEXT scan step (or the next
                # epoch's first round: the stash rides TrainState)
                ov = {
                    "grads": fresh_grad,
                    "stats": fresh_stats,
                    "weight": fresh_n,
                    "loss": fresh_loss,
                    "live": lb,
                    "valid": jnp.ones((k,), jnp.float32),
                }
            elif packed:
                # mesh topologies: the two-level form — engine/BN/loss
                # collectives run ONCE per device on the [k]-batched block
                # (agg/stats/loss come back unbatched and replicated)
                (agg, engine_state, health, telem_k, buffers, personal,
                 batch_stats, loss_round, total_live) = packed_round(
                    health, telem_st, buffers, personal, lb, engine_state
                )
            else:
                (agg, engine_state, health, telem_k, buffers, personal,
                 stats_k, loss_k, tl_k) = jax.vmap(
                    site_part, in_axes=(0,) * 10,
                    out_axes=(0,) * 9, axis_name=inner_axis,
                )(engine_state, health, telem_st, buffers, personal, lb,
                  xb, yb, wb, ab)
                # agg/stats/loss are psum'd over site_axes → identical across
                # the k in-device rows; collapse to one copy and update once
                agg = jax.tree.map(lambda a: a[0], agg)
                batch_stats = jax.tree.map(lambda a: a[0], stats_k)
                loss_round = loss_k[0]
                total_live = tl_k[0] if guard else None
            if quorum_on:
                # slice-quorum HOLD (r19): below min_slices live slices the
                # round never happened — every carried piece reverts to its
                # pre-round value (params/opt freeze through the zeroed
                # total_live below), the loss reports NaN like an all-dead
                # round, and the per-site held_rounds accumulator counts it
                def _hold(new, old):
                    return jax.tree.map(
                        lambda n, o: jnp.where(held, o, n), new, old
                    )

                st0, es0, hs0, ts0, bf0, ov0, pr0 = hold_prev
                batch_stats = _hold(batch_stats, st0)
                engine_state = _hold(engine_state, es0)
                health = _hold(health, hs0)
                if personal is not None:
                    personal = _hold(personal, pr0)
                if telem_k is not None:
                    telem_k = _hold(telem_k, ts0)
                    telem_k = {
                        **telem_k,
                        "held_rounds": telem_k["held_rounds"]
                        + held.astype(jnp.int32),
                    }
                if buffers is not None:
                    buffers = _hold(buffers, bf0)
                if ov is not None:
                    ov = _hold(ov, ov0)
                loss_round = jnp.where(held, jnp.nan, loss_round)
                total_live = jnp.where(
                    held, jnp.zeros_like(total_live), total_live
                )
            # the hold is under the scope too: XLA fuses the update into
            # the select (and into the engine's last einsum), and a fusion
            # wears the name of its root
            with jax.named_scope(scopes.OPTIMIZER):
                updates, new_opt_state = optimizer.update(
                    agg, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                if guard:
                    # a round with zero live weight advances nothing: params
                    # AND optimizer state hold (Adam's moment decay on a zero
                    # gradient would otherwise drift the update direction)
                    params = jax.tree.map(
                        lambda new, old: jnp.where(total_live > 0, new, old),
                        new_params, params,
                    )
                    opt_state = jax.tree.map(
                        lambda new, old: jnp.where(total_live > 0, new, old),
                        new_opt_state, opt_state,
                    )
                else:
                    params, opt_state = new_params, new_opt_state
            if telem:
                # the applied optimizer update's squared norm — global (the
                # update is replicated), broadcast into every site's row; a
                # zero-live round applied nothing, so it records 0
                usq = tree_sq_sum(updates)
                if guard:
                    usq = jnp.where(total_live > 0, usq, 0.0)
                telem_k = {
                    **telem_k,
                    "update_sq_last": jnp.zeros_like(
                        telem_k["update_sq_last"]
                    ) + usq,
                    "update_sq_sum": telem_k["update_sq_sum"] + usq,
                }
            return (
                params, batch_stats, opt_state, engine_state, health,
                telem_k, buffers, ov, personal, rng, rnd + 1,
            ), loss_round

        carry0 = (
            state.params,
            state.batch_stats,
            state.opt_state,
            state.engine_state,
            health,
            state.telemetry,
            state.buffers,
            state.overlap,
            state.personal,
            jax.random.fold_in(state.rng, state.round),
            state.round,
        )
        # Scan over rounds-LEADING xs instead of dynamic-indexing axis 1 of
        # the resident arrays per round: lax.scan slices its xs' leading
        # axis, which is contiguous, and under compile_epoch_aot's AUTO
        # input layouts XLA can choose a rounds-major storage order that
        # makes the moveaxis a layout assignment rather than a copy
        # (the r4 profile showed the strided per-round slice costing 3-7x
        # its raw bytes; the r5 A/B that chose this default is not
        # re-measured on the current toolchain — ROADMAP D3). Without AOT
        # layouts (plain jit, as the Trainer uses) the moveaxis may
        # materialize one whole-epoch copy — no more bytes MOVED than the
        # strided slices it replaces, but the copy coexists with the
        # (non-donated) original, so peak HBM residency grows by ~1x the
        # epoch-input size. For epoch inputs big enough for that to matter
        # (multi-GB), pass rounds_scan_xs=False.
        if use_scan_xs:
            if inventory is not None:
                xs = (jnp.moveaxis(x_rounds, 1, 0),)
                if poison_rounds is not None:
                    xs = xs + (jnp.moveaxis(poison_rounds, 1, 0),)
            else:
                xs = tuple(
                    jnp.moveaxis(a, 1, 0)
                    for a in (x_rounds, y_rounds, w_rounds)
                )
            if live_rounds is not None:
                xs = xs + (jnp.moveaxis(live_rounds, 1, 0),)
            if attack_rounds is not None:
                xs = xs + (jnp.moveaxis(attack_rounds, 1, 0),)
            if slice_gate:
                # own-slice gate + (quorum on) live-slice count, one scalar
                # each per round — already rounds-leading
                xs = xs + (sl_own_rounds,)
                if quorum_on:
                    xs = xs + (quorum_rounds,)
        else:
            xs = jnp.arange(rounds)
        (params, stats, opt_state, engine_state, health, telem_out, buf_out,
         ov_out, pr_out, rng, rnd), losses = jax.lax.scan(one_round, carry0, xs)
        new_state = TrainState(
            params=params,
            batch_stats=stats,
            opt_state=opt_state,
            engine_state=engine_state,
            rng=state.rng,
            round=rnd,
            health=health,
            telemetry=telem_out,
            buffers=buf_out,
            overlap=ov_out,
            personal=pr_out,
        )
        return new_state, losses

    def _ensure_health(state: TrainState, inputs) -> TrainState:
        # states built by pre-0.3 code paths carry health=None (or, like
        # dSGD's leafless engine state, a site count the data overrides);
        # fill fresh counters at the jit boundary so specs/carry structures
        # are uniform. Counters only survive when the site count matches —
        # per-site bookkeeping is meaningless across a site-count change.
        if (
            state.health is None
            or state.health["streak"].shape[0] != inputs.shape[0]
        ):
            state = state.replace(
                health=default_health(inputs.shape[0], reputation=reputation)
            )
        # the reputation fields (r17) mirror the robust_agg flag this epoch
        # was built with, same trace-time normalization as telemetry: a
        # robust run resumed from a legacy checkpoint gains fresh zero
        # scores (the 3 legacy counters survive), a legacy run resumed from
        # a robust checkpoint drops them — the program form is stable per
        # flag either way
        elif reputation and "suspect_streak" not in state.health:
            from ..robustness.health import reputation_fields

            state = state.replace(health={
                **state.health,
                **reputation_fields(state.health["streak"].shape[0]),
            })
        elif not reputation and "suspect_streak" in state.health:
            from ..robustness.health import REPUTATION_KEYS

            state = state.replace(health={
                k: v for k, v in state.health.items()
                if k not in REPUTATION_KEYS
            })
        # telemetry accumulators mirror the flag this epoch was built with:
        # off drops any carried accumulators (a checkpoint from a telemetry
        # run resumed with telemetry off — the program stays the legacy
        # one), on fills/resizes them like health. Trace-time structure
        # normalization, so the compiled form is stable per flag.
        if not telemetry:
            if state.telemetry is not None:
                state = state.replace(telemetry=None)
        elif (
            state.telemetry is None
            or state.telemetry["rounds"].shape[0] != inputs.shape[0]
            # key-set drift (e.g. a pre-r18 checkpoint without the per-tier
            # dcn_bytes accumulator): refill fresh — per-site sums are
            # meaningless across a schema change anyway
            or set(state.telemetry) != set(TELEMETRY_KEYS)
        ):
            state = state.replace(
                telemetry=default_round_telemetry(inputs.shape[0])
            )
        # staleness buffers mirror the bound this epoch was built with, same
        # trace-time normalization: bound 0 drops any carried buffers (an
        # async checkpoint resumed in bulk-sync mode — the program stays the
        # legacy one), bound > 0 fills/resizes fresh never-deposited buffers
        if not buffered:
            if state.buffers is not None:
                state = state.replace(buffers=None)
        elif (
            state.buffers is None
            or state.buffers["age"].shape[0] != inputs.shape[0]
        ):
            state = state.replace(
                buffers=default_async_buffers(inputs.shape[0], state.params)
            )
        # the overlap stash mirrors the overlap_rounds flag the same
        # trace-time way: off drops any carried stash (an overlapped fit's
        # checkpoint resumed in the plain mode — the program stays legacy,
        # the in-flight round is dropped once), on fills/resizes an EMPTY
        # (valid=0) stash whose first round applies nothing
        if not overlap:
            if state.overlap is not None:
                state = state.replace(overlap=None)
        elif (
            state.overlap is None
            or state.overlap["valid"].shape[0] != inputs.shape[0]
        ):
            state = state.replace(
                overlap=default_overlap_stash(
                    inputs.shape[0], state.params, state.batch_stats
                )
            )
        # personalized-head rows mirror the personalize patterns this epoch
        # was built with, same trace-time normalization: off drops any
        # carried rows (a personalized checkpoint resumed plain — the
        # program stays legacy), on fills/resizes fresh rows seeded from
        # the CURRENT global params (a new cohort size starts every head
        # from the common model)
        if not personal_on:
            if state.personal is not None:
                state = state.replace(personal=None)
        elif (
            state.personal is None
            or jax.tree.leaves(state.personal["params"])[0].shape[0]
            != inputs.shape[0]
        ):
            from ..privacy.personalize import (
                default_personal,
                head_leaf_paths,
            )

            state = state.replace(personal=default_personal(
                inputs.shape[0], state.params,
                head_leaf_paths(state.params, personalize), optimizer,
            ))
        return state

    # donate the carried state's buffers to the epoch program: the update
    # aliases in place instead of allocating a second params+opt copy. The
    # caller contract (rebind, snapshot what you keep) is documented above.
    jit_kw = {"donate_argnums": (0,)} if donate_state else {}

    if pipeline == "device" and mesh is not None:

        def epoch_fn_impl(state: TrainState, inv_x, inv_y, idx, live=None,
                          poison=None, attack=None, slice_live=None):
            state = _ensure_health(state, idx)
            specs = _state_specs(state, site_part)
            # optional traced inputs (liveness / NaN gate / attack codes /
            # slice mask): trace-time presence branches, one compiled
            # program per form — a fit feeds a fixed form, so the compile
            # counter still sees one program
            extras = [a for a in (live, poison, attack) if a is not None]
            extra_specs = [P(site_part)] * len(extras)
            if slice_live is not None:
                # the [num_slices, rounds] whole-slice mask rides
                # REPLICATED (tiny); members index their own slice's row
                extras.append(slice_live)
                extra_specs.append(P())
            has_live, has_poison = live is not None, poison is not None
            has_attack = attack is not None
            has_slice = slice_live is not None
            axes = (
                (SLICE_AXIS, SITE_AXIS, FOLD_AXIS) if sliced
                else (SITE_AXIS, FOLD_AXIS)
            )

            def wrapped(st, ex, ey, ix, *opt):
                opt = list(opt)
                lv = opt.pop(0) if has_live else None
                pz = opt.pop(0) if has_poison else None
                ak = opt.pop(0) if has_attack else None
                sm = opt.pop(0) if has_slice else None
                return epoch_over_sites(
                    st, ix, None, None, lv, site_axes=axes,
                    inner_axis=FOLD_AXIS, inventory=(ex, ey), poison=pz,
                    attack=ak, slice_live=sm,
                )

            return shard_map(
                wrapped,
                mesh=mesh,
                in_specs=(specs, P(site_part), P(site_part), P(site_part))
                + tuple(extra_specs),
                out_specs=(specs, P()),
                check_vma=False,
            )(state, inv_x, inv_y, idx, *extras)

        epoch_fn = jax.jit(epoch_fn_impl, **jit_kw)

    elif pipeline == "device":

        def epoch_fn_impl(state: TrainState, inv_x, inv_y, idx, live=None,
                          poison=None, attack=None, slice_live=None):
            # all S sites fold onto the local device: the inner vmap IS the
            # site axis; the gather vmaps over the same leading site dim
            # (slice_live is rejected inside epoch_over_sites — the vmap
            # fold has no slice tier)
            return epoch_over_sites(
                _ensure_health(state, idx), idx, None, None, live,
                site_axes=SITE_AXIS, inner_axis=SITE_AXIS,
                inventory=(inv_x, inv_y), poison=poison, attack=attack,
                slice_live=slice_live,
            )

        epoch_fn = jax.jit(epoch_fn_impl, **jit_kw)

    elif mesh is not None:

        def epoch_fn_impl(state: TrainState, inputs, labels, weights,
                          live=None, attack=None, slice_live=None):
            state = _ensure_health(state, inputs)
            specs = _state_specs(state, site_part)
            has_live, has_attack = live is not None, attack is not None
            has_slice = slice_live is not None
            axes = (
                (SLICE_AXIS, SITE_AXIS, FOLD_AXIS) if sliced
                else (SITE_AXIS, FOLD_AXIS)
            )

            def shard_wrapped(st, x, y, w, *opt):
                # x: [k, steps, B, ...] — this device's block of k sites.
                # k > 1 is the folded case (cfg.sites_per_device: more
                # simulated sites than devices); cross-site collectives span
                # the (mesh site, fold) axis pair — plus the outer slice
                # axis on sliced meshes. k == 1 is the one-site-per-device
                # case, same program.
                opt = list(opt)
                lv = opt.pop(0) if has_live else None
                ak = opt.pop(0) if has_attack else None
                sm = opt.pop(0) if has_slice else None
                return epoch_over_sites(
                    st, x, y, w, lv, site_axes=axes,
                    inner_axis=FOLD_AXIS, attack=ak, slice_live=sm,
                )

            extras = [a for a in (live, attack) if a is not None]
            extra_specs = [P(site_part)] * len(extras)
            if slice_live is not None:
                extras.append(slice_live)
                extra_specs.append(P())
            in_specs = (
                (specs, P(site_part), P(site_part), P(site_part))
                + tuple(extra_specs)
            )
            return shard_map(
                shard_wrapped,
                mesh=mesh,
                in_specs=in_specs,
                out_specs=(specs, P()),
                check_vma=False,
            )(state, inputs, labels, weights, *extras)

        epoch_fn = jax.jit(epoch_fn_impl, **jit_kw)

    else:

        def epoch_fn_impl(state: TrainState, inputs, labels, weights,
                          live=None, attack=None, slice_live=None):
            # all S sites fold onto the local device: the inner vmap IS the
            # site axis (slice_live is rejected inside epoch_over_sites)
            return epoch_over_sites(
                _ensure_health(state, inputs), inputs, labels, weights, live,
                site_axes=SITE_AXIS, inner_axis=SITE_AXIS, attack=attack,
                slice_live=slice_live,
            )

        epoch_fn = jax.jit(epoch_fn_impl, **jit_kw)

    return epoch_fn


def epoch_program_artifacts(epoch_fn, *args, lowered: bool = False,
                            compiled: bool = False):
    """The traced/lowered/compiled forms of one epoch program, for semantic
    auditing (checks/semantic.py): ``(ClosedJaxpr, Lowered | None,
    Compiled | None)``.

    The jaxpr is what rules S001/S002/S004 walk (collective axes, payload
    operand shapes/dtypes, precision flow); the lowering feeds the
    program-identity differ (checks/lowering.py, S005); the compiled
    executable exposes the input-output aliasing S003 proves donation
    against. Tracing only — no execution; safe on CPU for any topology the
    epoch builder supports."""
    trace = getattr(epoch_fn, "trace", None)
    if trace is not None and (lowered or compiled):
        # one trace serves both artifacts (the AOT Traced stage lowers from
        # the jaxpr it already holds); older jax lacks .trace and pays two
        traced = trace(*args)
        closed, low = traced.jaxpr, traced.lower()
    else:
        closed = jax.make_jaxpr(epoch_fn)(*args)
        low = epoch_fn.lower(*args) if (lowered or compiled) else None
    comp = low.compile() if compiled else None
    return closed, low, comp


def compile_epoch_aot(epoch_fn, state: TrainState, x, y, w, live=None,
                      attack=None):
    """AOT-compile an epoch function letting XLA choose the INPUT layout for
    the (large, resident) epoch inputs.

    Fed default-layout inputs, the compiled epoch relayouts + copies the
    whole input array on-device every call (profiled ~8% of the 32-site ICA
    bench epoch); with the input layout AUTO-chosen the copy moves into the
    one-time ``device_put``. Only ``x`` gets AUTO — AUTO on the carried
    ``state`` makes each chained call relayout the state (output layouts are
    default), measured strictly slower.

    Returns ``(compiled, put_x)``: call ``put_x(x)`` once on the resident
    inputs, then ``compiled(state, put_x(x), y, w)`` exactly like
    ``epoch_fn``. Single-device path (``mesh=None``) — the shard_map path
    distributes inputs instead of keeping them resident. Pass ``live``
    (``[S, rounds]``) to compile the fault-injected program (bench
    ``--faults``); the compiled callable then takes it as a fifth argument.
    ``attack`` (``[S, rounds]`` int32, robustness/attacks.py) likewise
    compiles the attack-injected program (bench ``--attacks``) — it rides
    after ``live`` in the positional order, so an attack-only build passes
    ``live=None`` explicitly at call time.
    """
    from jax.experimental.layout import Format, Layout

    in_sh = (
        jax.tree.map(lambda _: None, state), Format(Layout.AUTO), None, None
    )
    args = (state, x, y, w)
    if live is not None or attack is not None:
        in_sh = in_sh + (None,)
        args = args + (live,)
    if attack is not None:
        in_sh = in_sh + (None,)
        args = args + (attack,)
    comp = jax.jit(epoch_fn, in_shardings=in_sh).lower(*args).compile()
    x_fmt = comp.input_formats[0][1]
    return comp, lambda xs: jax.device_put(xs, x_fmt)


def eval_forward(task: FederatedTask, params, batch_stats, x, y=None, w=None):
    """THE per-task inference forward — the single definition both the
    trainer's eval path (:func:`make_eval_fn`) and the serving engine
    (serving/engine.py) compile, so a served checkpoint reproduces the
    trainer's recorded eval scores bit-for-bit on identical batches
    (tests/test_serving.py; the S005 serving identity cell proves the two
    programs lower identically).

    ``x [B, ...]`` is one batch; ``w [B]`` is the per-example valid mask
    (serving's request padding and eval's plan padding share these
    semantics — for batch-stat models like MSANNet the mask also keeps pad
    rows out of the BatchNorm statistics, exactly as in training). With
    labels ``y`` also returns the per-example cross-entropy (the eval loss
    path); ``y=None`` (serving) returns probs only — a trace-time branch,
    so the serving program carries no label ops at all."""
    if getattr(task.model, "task_loss", None) is not None:
        # a task with its own loss has no classes: its eval is that loss, row
        # by row, and a zero-wide probability block (trainer/metrics.py
        # NoClassMetrics)
        probs = jnp.zeros((x.shape[0], 0), jnp.float32)
        if y is None:
            return probs
        one = jnp.ones((1,), jnp.float32)
        ce = jax.lax.map(
            lambda xy: task.loss(
                params, batch_stats, None, xy[0][None], xy[1][None], one)[0],
            (x, y))
        return probs, ce
    logits, _ = task.apply(params, batch_stats, x, train=False, mask=w)
    probs = jax.nn.softmax(logits, -1)
    if y is None:
        return probs
    logp = jax.nn.log_softmax(logits, -1)
    ce = -jnp.take_along_axis(logp, y[..., None].astype(jnp.int32), -1)[..., 0]
    return probs, ce


def make_eval_fn(task: FederatedTask, mesh=None, personalize: tuple = ()):
    """Jitted full-pass eval: returns per-site ``probs [S, steps, B, C]``,
    ``loss_sum [S]``, ``weight_sum [S]`` — metric scalars are computed
    host-side (trainer/metrics.py). ``mesh=None`` folds sites via vmap, as in
    :func:`make_train_epoch_fn`. The per-batch forward is
    :func:`eval_forward` — shared verbatim with the serving engine.

    ``personalize`` (r20, privacy/personalize.py): with patterns set AND a
    state carrying ``personal`` rows, each site evaluates on its OWN merged
    head — eval is per-site by construction, so the per-site scores in
    ``logs.json`` measure each site's personalized model. A personalized
    build fed a personal-less state (``mode="test"`` from a params-only
    restore) evaluates the frozen global heads — a trace-time presence
    branch, like every other optional input."""
    # builder kwarg, never a tracer: the static TrainConfig.personalize
    personal_on = bool(tuple(personalize))  # jaxlint: disable=R005

    def per_site_eval(params, batch_stats, x, y, w, head=None):
        if head is not None:
            from ..privacy.personalize import merge_head

            params = merge_head(params, head)

        def step(_, batch):
            xb, yb, wb = batch
            probs, ce = eval_forward(task, params, batch_stats, xb, yb, wb)
            return None, (probs, (ce * wb).sum())

        _, (probs, loss_sums) = jax.lax.scan(step, None, (x, y, w))
        return probs, loss_sums.sum(), w.sum()

    if mesh is not None:
        part = site_axis_of(mesh)  # (slice, site) on sliced meshes (r18)

        @jax.jit
        def eval_fn(state: TrainState, inputs, labels, weights):
            heads = (
                state.personal["params"]
                if personal_on and state.personal is not None else None
            )
            extras = () if heads is None else (heads,)
            extra_specs = () if heads is None else (
                jax.tree.map(lambda _: P(part), heads),
            )
            return shard_map(
                # inner vmap over the device's site block (k ≥ 1 folded sites)
                lambda p, s, x, y, w, *h: jax.vmap(
                    per_site_eval, in_axes=(None, None, 0, 0, 0, 0)
                )(p, s, x, y, w, h[0] if h else None),
                mesh=mesh,
                in_specs=(
                    jax.tree.map(lambda _: P(), state.params),
                    jax.tree.map(lambda _: P(), state.batch_stats),
                    P(part),
                    P(part),
                    P(part),
                ) + extra_specs,
                out_specs=(P(part), P(part), P(part)),
                check_vma=False,
            )(state.params, state.batch_stats, inputs, labels, weights,
              *extras)

    else:

        @jax.jit
        def eval_fn(state: TrainState, inputs, labels, weights):
            heads = (
                state.personal["params"]
                if personal_on and state.personal is not None else None
            )
            return jax.vmap(
                per_site_eval, in_axes=(None, None, 0, 0, 0, 0)
            )(state.params, state.batch_stats, inputs, labels, weights, heads)

    return eval_fn
