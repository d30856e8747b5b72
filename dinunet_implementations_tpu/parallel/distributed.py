"""Multi-host (DCN) runtime — scaling the site mesh past one host.

The reference scales out by running one Docker container per site on
whatever machines the COINSTAC pipeline coordinator can reach, shipping
JSON payloads over the network every round (reference ``entry.py:5``,
``compspec.json:284-296``). The TPU-native equivalent keeps the exact same
trust topology — one coordinator, N workers — but swaps the transport for
XLA collectives:

- :func:`distributed_init` is the COINSTAC-coordinator equivalent: it brings
  up JAX's multi-process runtime so every host's chips join one global device
  set (DCN between hosts, ICI within).
- :func:`multihost_site_mesh` lays the ``(site, model)`` mesh over that
  device set **hybrid-style**: the ``model`` (sequence/tensor) axis is packed
  inside a host's ICI domain where bandwidth is highest, while the ``site``
  axis spans hosts — so the only traffic that crosses DCN is the once-per-round
  gradient aggregation, mirroring the reference's site-local-compute /
  central-aggregation split (SURVEY.md §2.2 "Communication backend").

Everything downstream (trainer/steps.py, engines/) is topology-agnostic:
collectives take the axis *name*, so the same compiled program runs on a
single chip, an 8-chip slice, or a multi-host pod — only the mesh changes.
"""

from __future__ import annotations

import jax
import numpy as np

from ..robustness.retry import with_retry
from .mesh import MODEL_AXIS, SITE_AXIS, SLICE_AXIS, site_axis_of
from jax.sharding import NamedSharding, PartitionSpec as P

_initialized = False

#: wall-clock budget for the whole coordinator join (retries included): a
#: coordinator that never comes up fails the worker in ~2 minutes instead of
#: retrying forever — preemptible fleets must recycle the slot, not camp on it
JOIN_DEADLINE_S = 120.0
#: per-attempt cap: one hung initialize (half-open TCP, wedged coordinator)
#: is abandoned to its worker thread and retried, instead of blocking the
#: process indefinitely (robustness/retry.py timeout_s semantics)
JOIN_ATTEMPT_TIMEOUT_S = 45.0


def distributed_init(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    join_deadline_s: float | None = JOIN_DEADLINE_S,
    join_timeout_s: float | None = JOIN_ATTEMPT_TIMEOUT_S,
    **kwargs,
) -> bool:
    """Join (or skip joining) the multi-host runtime.

    Returns ``True`` when a multi-process runtime was initialized, ``False``
    for the single-process case (``num_processes`` in (None, 1) with no
    coordinator given) — callers can branch on it for logging only; nothing
    else changes downstream.

    With all arguments ``None``, JAX's own cluster autodetection applies
    (TPU pod metadata, SLURM, etc.), so on a real pod this is simply
    ``distributed_init(coordinator_address="host0:1234", num_processes=N,
    process_id=rank)`` or no args at all.

    A worker that comes up before its coordinator (pod rollout races, spot
    restarts) retries the join under jittered exponential backoff
    (robustness/retry.py) instead of dying on the first refused connection —
    but fail-FAST, not forever: ``join_deadline_s`` bounds the whole join
    wall-clock and ``join_timeout_s`` abandons a single hung attempt (a
    wedged coordinator that accepts the TCP connect and then never
    completes the handshake used to hang the worker indefinitely). Pass
    ``None`` for either to restore the unbounded behavior.
    """
    global _initialized
    if coordinator_address is None and num_processes in (None, 1):
        return False
    if _initialized:  # idempotent use — NB: probing jax.process_count()
        return True   # here would initialize the backend and make
    # jax.distributed.initialize() below raise ("must be called before any
    # JAX calls"), so idempotency is tracked by module flag only

    if _jax_distributed_client() is not None:
        # the runtime was initialized by code OUTSIDE this module (our flag is
        # False but jax's global client exists): we don't own it, so no retry
        # and ABSOLUTELY no reset — let jax raise its own clear
        # "should only be called once" error, exactly as before this wrapper
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            **kwargs,
        )

    def _attempt_initialize():
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
                **kwargs,
            )
        except (RuntimeError, OSError):
            _dcn_counter("dcn_join_retries_total")
            # the retryable failure modes: coordinator not up yet (refused
            # connect → ConnectionError ⊂ OSError), DNS/socket errors, and
            # jaxlib surfacing a failed join as RuntimeError/XlaRuntimeError.
            # A failed connect leaves jax's module-global client/service SET
            # (State.initialize assigns self.client before connect() and has
            # no failure cleanup), so a bare retry would die on "initialize
            # should only be called once" instead of retrying the join —
            # clear the partial state first. Non-retryable errors (bad
            # arguments → ValueError/TypeError) propagate untouched: no
            # retry will follow, so there is no partial state to clear for.
            _reset_partial_distributed_state()
            raise

    from ..robustness.retry import RetryTimeout

    try:
        with_retry(
            _attempt_initialize,
            attempts=3,
            base_delay=0.5,
            retry_on=(RuntimeError, OSError, ConnectionError),
            describe="jax.distributed.initialize",
            deadline_s=join_deadline_s,
            timeout_s=join_timeout_s,
            # a TIMED-OUT join is fatal, not retryable: the abandoned
            # attempt's thread may still be mutating jax's global
            # distributed state, and a concurrent re-initialize would race
            # it — fast failures (refused connect) still retry via retry_on
            retry_on_timeout=False,
        )()
    except RetryTimeout:
        # the hung-coordinator fail-fast path: surfaced on the live bus so
        # a fleet supervisor sees "joins are timing out", not just dying
        _dcn_counter("dcn_join_timeouts_total")
        raise
    _initialized = True
    return True


def _dcn_counter(name: str, **labels) -> None:
    """Best-effort live-bus counter for DCN runtime events (join retries,
    join timeouts — the r19 dcn_timeout observability). The bus is never
    load-bearing here: a half-imported telemetry layer (early interpreter
    teardown, exotic embedding) must not turn a join failure into a
    different failure."""
    try:
        from ..telemetry.bus import global_bus

        # API-boundary forward: NAME is a literal at every call site
        global_bus().counter(name, **labels)  # jaxlint: disable=R007
    # observability only — the join path's own exception must propagate,
    # never be replaced by a bus import/publish error
    except Exception:  # jaxlint: disable=R002
        pass


def _jax_distributed_client():
    """jax's module-global distributed client, or None (guarded private-API
    probe — used only to detect a runtime initialized outside this module)."""
    state = getattr(getattr(jax, "_src", None), "distributed", None)
    state = getattr(state, "global_state", None)
    return getattr(state, "client", None)


def _reset_partial_distributed_state() -> None:
    """Best-effort teardown of a PARTIALLY-initialized jax.distributed state
    (client constructed, connect failed), so the next initialize attempt
    starts clean. ``shutdown()`` is the public reset, but it can itself raise
    on a never-connected client (``client.shutdown()`` precedes ``client =
    None``); fall back to nulling the global state's handles directly."""
    try:
        jax.distributed.shutdown()
        return
    except (RuntimeError, OSError, AttributeError):
        # the shutdown-on-partial-state failure modes: RuntimeError (incl.
        # XlaRuntimeError) from a never-connected client's shutdown(),
        # OSError from the socket teardown, AttributeError when the state
        # object predates/postdates the private-API shape we probe — in all
        # of them we fall through to nulling the handles directly
        pass
    state = getattr(getattr(jax, "_src", None), "distributed", None)
    state = getattr(state, "global_state", None)
    if state is not None:
        for attr in ("client", "service", "preemption_sync_manager"):
            try:
                setattr(state, attr, None)
            except AttributeError:
                # a jax version exposing this as a read-only/absent slot:
                # skip that handle, best-effort by design
                pass


def distributed_shutdown() -> None:
    """Tear down the multi-host runtime and clear the idempotency flag, so
    ``distributed_init`` is re-entrant (worker restarts within one process,
    coordinated test harnesses). A no-op when nothing was initialized."""
    global _initialized
    try:
        if _initialized:
            jax.distributed.shutdown()
    finally:
        # clear the flag even when shutdown() raises (wedged peer, never-
        # connected client): the runtime is gone either way, and a stale True
        # would make every later distributed_init a silent no-op
        _initialized = False


def multihost_site_mesh(
    sites_per_process: int | None = None,
    model_axis_size: int = 1,
    devices: list | None = None,
) -> jax.sharding.Mesh:
    """A global ``(site, model)`` mesh over every process's devices.

    The ``model`` axis is contiguous within each process's ICI domain; the
    ``site`` axis tiles processes outer-most, so cross-site collectives (the
    per-round aggregation) are the only DCN traffic. Single-process callers
    get the same mesh :func:`parallel.mesh.make_site_mesh` would build.

    ``sites_per_process`` defaults to ``local devices // model_axis_size``.
    """
    n_proc = jax.process_count()
    devices = devices if devices is not None else jax.devices()
    per_proc = len(devices) // n_proc
    if sites_per_process is None:
        sites_per_process = max(per_proc // model_axis_size, 1)
    need = sites_per_process * model_axis_size
    if need > per_proc:
        raise ValueError(
            f"{sites_per_process} sites × model={model_axis_size} needs "
            f"{need} devices per process, have {per_proc}"
        )
    if need < per_proc:
        # surplus chips idle (same contract as make_site_mesh's devices[:need]
        # subset on one host): take each process's leading devices
        by_proc: dict[int, list] = {}
        for d in devices:
            by_proc.setdefault(d.process_index, []).append(d)
        devices = [d for p in sorted(by_proc) for d in by_proc[p][:need]]
    if n_proc == 1:
        from .mesh import make_site_mesh

        return make_site_mesh(sites_per_process, devices, model_axis_size)
    from jax.experimental import mesh_utils

    # per-ICI-slice shape × DCN shape: sites stack across processes (outer),
    # the model axis never leaves a process. The DCN granule is the TPU
    # slice when slices map 1:1 to processes (the usual pod config — gives
    # ICI-topology-aware ordering within each slice); otherwise the process
    # itself (mesh_utils' documented fallback for platforms without usable
    # slice_index — e.g. multi-process CPU, where every device reports
    # slice 0 and slice-granule mode would reject the (n_proc, 1) shape).
    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    by_process = None in slice_ids or len(slice_ids) != n_proc
    arr = mesh_utils.create_hybrid_device_mesh(
        mesh_shape=(sites_per_process, model_axis_size),
        dcn_mesh_shape=(n_proc, 1),
        devices=devices,
        process_is_granule=by_process,
    )
    return jax.sharding.Mesh(arr, (SITE_AXIS, MODEL_AXIS))


def multihost_sliced_site_mesh(
    num_slices: int | None = None,
    sites_per_slice: int | None = None,
    sites_per_device: int = 1,
    model_axis_size: int = 1,
    devices: list | None = None,
) -> jax.sharding.Mesh:
    """The real-host form of ``parallel/mesh.py sliced_site_mesh``: a global
    ``(slice, site, model)`` mesh where the SLICE axis tiles processes —
    the multi-slice deployment shape (one ``runner/dcn_worker.py`` process
    per TPU slice), so the ONLY traffic that crosses DCN is the per-round
    inter-slice hop of the three-tier aggregation, and the intra-slice
    psum + the model axis never leave a process's ICI domain.

    ``num_slices`` defaults to ``jax.process_count()`` (the 1:1
    process-per-slice deployment) and must divide it; ``sites_per_slice``
    is the VIRTUAL site count per slice (defaults to packing every local
    device: ``local_devices // model_axis_size × sites_per_device``).
    Single-process callers collapse to :func:`sliced_site_mesh` over the
    local devices — the CPU-emulation path."""
    n_proc = jax.process_count()
    if num_slices is None:
        num_slices = n_proc if n_proc > 1 else 1
    devices = devices if devices is not None else jax.devices()
    per_proc = len(devices) // max(n_proc, 1)
    if sites_per_slice is None:
        procs_per_slice = max(n_proc // max(num_slices, 1), 1)
        sites_per_slice = max(
            per_proc // model_axis_size, 1
        ) * sites_per_device * procs_per_slice
    if n_proc == 1:
        from .mesh import sliced_site_mesh

        return sliced_site_mesh(
            num_slices, sites_per_slice, sites_per_device, devices,
            model_axis_size,
        )
    if n_proc % num_slices:
        raise ValueError(
            f"num_slices={num_slices} must divide the process count "
            f"({n_proc}) — slices are process granules over DCN"
        )
    if sites_per_slice % sites_per_device:
        raise ValueError(
            f"sites_per_device={sites_per_device} must divide the per-slice "
            f"site count ({sites_per_slice})"
        )
    procs_per_slice = n_proc // num_slices
    site_members = sites_per_slice // sites_per_device  # per slice
    if site_members % procs_per_slice:
        raise ValueError(
            f"{site_members} site-axis members per slice must divide over "
            f"{procs_per_slice} processes per slice"
        )
    per_proc_sites = site_members // procs_per_slice
    need = per_proc_sites * model_axis_size
    if need > per_proc:
        raise ValueError(
            f"{per_proc_sites} sites × model={model_axis_size} needs "
            f"{need} devices per process, have {per_proc}"
        )
    if need < per_proc:
        by_proc: dict[int, list] = {}
        for d in devices:
            by_proc.setdefault(d.process_index, []).append(d)
        devices = [d for p in sorted(by_proc) for d in by_proc[p][:need]]
    from jax.experimental import mesh_utils

    # DCN granules: slices stack processes outermost (the slice axis), any
    # surplus processes extend the site axis within a slice; the model axis
    # never leaves a process. Same granule fallback as multihost_site_mesh.
    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    by_process = None in slice_ids or len(slice_ids) != n_proc
    arr = mesh_utils.create_hybrid_device_mesh(
        mesh_shape=(1, per_proc_sites, model_axis_size),
        dcn_mesh_shape=(num_slices, procs_per_slice, 1),
        devices=devices,
        process_is_granule=by_process,
    )
    return jax.sharding.Mesh(arr, (SLICE_AXIS, SITE_AXIS, MODEL_AXIS))


def spans_processes(mesh) -> bool:
    """True when ``mesh`` includes devices of other processes (a real
    multi-host mesh) — the cases where plain host-local arrays can neither
    feed a shard_map nor be fetched with ``np.asarray``."""
    if mesh is None:
        return False
    me = jax.process_index()
    return any(d.process_index != me for d in mesh.devices.flat)


def put_site_batch(mesh, arr, dtype=None):
    """Ship a host-side ``[S, ...]`` per-site batch onto the mesh, split over
    the site axis.

    Single-process meshes: a plain committed ``device_put``. Multi-host
    meshes: every process holds the full global batch (the runner loads the
    same dataset tree on each host) and
    ``jax.make_array_from_process_local_data`` takes each process's
    addressable slices — the documented JAX recipe for feeding pjit across
    hosts."""
    a = np.asarray(arr)
    if dtype is not None:
        a = a.astype(dtype)
    # the leading per-site dim splits over (slice, site) on sliced meshes
    sh = NamedSharding(mesh, P(site_axis_of(mesh)))
    if spans_processes(mesh):
        return jax.make_array_from_process_local_data(sh, a, global_shape=a.shape)
    return jax.device_put(a, sh)


def input_cast_dtype(inputs, compute_dtype):
    """The dtype host inputs are cast to on their way up (None = as they
    are): the model's compute dtype for floating inputs; integer samples
    (token ids, which a cast to bfloat16 would destroy) stay what they are."""
    floating = np.issubdtype(np.asarray(inputs).dtype, np.floating)
    return compute_dtype if floating else None


def put_site_inventory(mesh, inventory, input_dtype=None):
    """One-shot placement of a site inventory in its resident form
    (data/api.py SiteInventory: ``[S, rows + 1, *stored_sample_shape]``, the
    last row all zeros) onto the mesh, split over the site axis — the upload
    the device-resident pipeline pays ONCE per fit. Inputs are cast to the
    compute dtype here, on the host, so no per-epoch convert+copy ever runs
    on-device and the device never holds a second, transient copy; the zero
    row and every pad row are written as zeros into the cast copy
    (``clear_padding``), so nothing the epoch program can gather depends on
    what the host arrays held there. The arrays go up in the device's
    DEFAULT layout: the stored shape is what makes that the layout the gather
    reads (an explicit ``Layout`` at ``device_put`` is rejected by an
    executable loaded from the persistent compile cache, and relayouts on
    the device besides: PERF.md §6, PR 26). ``mesh=None`` is the vmap-folded
    single-device path (plain local arrays); multi-host meshes
    take each process's addressable slices exactly like the per-epoch
    batches used to (:func:`put_site_batch`)."""
    inputs = np.asarray(inventory.inputs)
    # one cast pass, always into a fresh copy: clear_padding writes into it
    inputs = inputs.astype(input_cast_dtype(inputs, input_dtype) or inputs.dtype)
    labels = np.array(inventory.labels)
    inventory.clear_padding(inputs, labels)
    if mesh is None:
        return jax.device_put(inputs), jax.device_put(labels)
    return put_site_batch(mesh, inputs), put_site_batch(mesh, labels)


def put_replicated(mesh, arr, dtype=None):
    """Ship a small host array to the mesh FULLY REPLICATED — the r19
    slice-liveness mask's placement (every member reads its own slice's row
    from the same tiny ``[num_slices, rounds]`` array; sharding it would
    buy nothing and cost a spec). Multi-host meshes feed it per process
    like the batches — every process holds the identical mask, so the
    process-local data IS the global array."""
    a = np.asarray(arr)
    if dtype is not None:
        a = a.astype(dtype)
    sh = NamedSharding(mesh, P())
    if spans_processes(mesh):
        return jax.make_array_from_process_local_data(sh, a, global_shape=a.shape)
    return jax.device_put(a, sh)


def put_epoch_plan(mesh, positions, live=None, poison=None, attack=None,
                   slice_live=None):
    """Ship one epoch's compact plan — the ``[S, steps, B]`` int32 index
    grid plus the optional ``[S, rounds]`` fault masks, attack-code mask
    (robustness/attacks.py, r17) and ``[num_slices, rounds]`` slice-
    liveness mask (r19, replicated) — to the mesh. This is the ENTIRE
    per-epoch host→device traffic of the device pipeline: index-plan bytes,
    not dataset bytes."""
    import jax.numpy as jnp

    def put(a):
        return jnp.asarray(a) if mesh is None else put_site_batch(mesh, a)

    return (
        put(positions),
        None if live is None else put(live),
        None if poison is None else put(poison),
        None if attack is None else put(attack),
        None if slice_live is None else (
            jnp.asarray(slice_live) if mesh is None
            else put_replicated(mesh, slice_live)
        ),
    )


def fetch_site_outputs(tree, mesh):
    """Bring per-site (``P(site)``-sharded) outputs back to host numpy on
    every process. Multi-host meshes need a ``process_allgather`` first —
    ``np.asarray`` on an array spanning non-addressable devices raises."""
    if not spans_processes(mesh):
        return jax.tree.map(np.asarray, tree)
    from jax.experimental import multihost_utils

    return jax.tree.map(
        np.asarray, multihost_utils.process_allgather(tree, tiled=True)
    )
