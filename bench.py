"""Benchmark: ICA-LSTM federated training throughput, 32 simulated sites.

The north-star metric (BASELINE.json): samples/sec/chip for the ICA-LSTM
fMRI classifier trained across 32 simulated federated sites, vs the
CPU reference baseline. One chip simulates all 32 sites via the vmap-folded
site axis (trainer/steps.py); the measured step is the FULL federated round:
per-site grad, dSGD example-weighted aggregation across the 32 sites, Adam
update — i.e. what the reference needs a 32-container COINSTAC deployment
plus a remote to do.

MEASUREMENT METHODOLOGY:

1. chain N epochs (each consumes the previous state),
2. copy EVERY leaf of the final state to the host (np.asarray over the
   tree) — jax dispatch is asynchronous, and the copy ends the timed region
   only once the whole chain has run (``jax.block_until_ready`` is the
   synchronisation; the copy also pays the device→host transfer, which the
   marginal in step 3 cancels),
3. report the MARGINAL epoch cost between two LONG chains,
   (min T(N) - min T(N/2)) / (N/2), minimizing each chain length over three
   runs SEPARATELY: interference from other work on the machine only ever
   ADDS time, so the minimum per endpoint is its least-disturbed
   observation. (Minimizing the paired differences instead would be
   downward-biased — a disturbed half chain subtracts from the difference.)
   ROADMAP S0 replaces this estimator with medians and quartiles over paired
   runs.

Baseline: the reference's torch ICALstm (loaded from
/root/reference/comps/icalstm/models.py) doing fwd+bwd+Adam on one CPU site
measured in this environment = 67.3 samples/sec (B=16, 238 ms/iter; falls back
to this recorded constant when the live measurement is unavailable).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} (plus an
``mfu`` field — fraction of v5e bf16 peak sustained by the model's matmul
FLOPs at the measured throughput).
"""

import json
import statistics
import sys
import time

# Recorded in this environment (see module docstring); re-measured live when
# --live-baseline is passed.
CPU_BASELINE_SAMPLES_PER_SEC = 67.3

NUM_SITES = 32
BATCH_PER_SITE = 16
STEPS_PER_EPOCH = 2
TIMED_EPOCHS = 100  # long chains: the marginal compute must dwarf fetch jitter

# flagship model dims (HCP inputspec, datasets/icalstm/inputspec.json:32-43)
WINDOWS, COMPS, WLEN = 98, 100, 10
ENC_IN, ENC_OUT, HIDDEN = COMPS * WLEN, 256, 348

V5E_BF16_PEAK_FLOPS = 197e12


def chain_epochs(epoch_fn, state0, x, y, w, n: int, live=None,
                 attack=None, slice_live=None) -> float:
    """Run ``n`` chained epochs from ``state0`` and FULLY materialize the
    final state (np.asarray over every leaf), which waits for the whole
    chain. Returns wall-clock seconds. This is the
    shared measurement primitive for bench.py and bench_matrix.py; any
    methodology fix belongs here, once. ``live`` is the optional ``[S,
    rounds]`` liveness mask (``--faults``): the same device array feeds every
    epoch (throughput of the masked program, not of a changing schedule);
    ``attack`` is the optional ``[S, rounds]`` attack-code mask
    (``--attacks``, robustness/attacks.py) riding after it;
    ``slice_live`` the optional ``[num_slices, rounds]`` slice-liveness
    mask (r19 — sliced meshes under a slice-fault plan)."""
    import jax
    import numpy as np

    s = state0
    t0 = time.time()
    for _ in range(n):
        if slice_live is not None:
            s, _ = epoch_fn(s, x, y, w, live, attack, slice_live)
        elif attack is not None:
            s, _ = epoch_fn(s, x, y, w, live, attack)
        elif live is not None:
            s, _ = epoch_fn(s, x, y, w, live)
        else:
            s, _ = epoch_fn(s, x, y, w)
    jax.tree.map(np.asarray, s)
    return time.time() - t0


def least_contended_marginal(run_chain, n: int, repeats: int = 3,
                             pre_full: float | None = None) -> float:
    """Marginal seconds/epoch between an ``n``-epoch and an ``n/2``-epoch
    chain, taking the MINIMUM of ``repeats`` runs PER ENDPOINT (module
    docstring step 3): interference only adds time, so each endpoint's
    minimum is its least-disturbed observation; minimizing paired
    differences instead would be downward-biased. ``run_chain(k)`` must
    return wall-clock seconds for a k-epoch fully-materialized chain.
    ``pre_full`` feeds an already-observed (n+1)-chain timing into the
    full-endpoint minimum (valid for a min estimator; saves a chain)."""
    half = n // 2
    t_half = min(run_chain(half + 1) for _ in range(repeats))
    fulls = [run_chain(n + 1) for _ in range(repeats)]
    if pre_full is not None:
        fulls.append(pre_full)
    return max((min(fulls) - t_half) / (n - half), 1e-9)


def marginal_distribution(pairs, n: int, pre_full: float | None = None) -> dict:
    """Distribution summary over N paired (half-chain, full-chain) timings.

    ``pairs`` is a list of ``(T(n/2+1), T(n+1))`` wall-clock observations.
    The headline ``marginal_seconds_per_epoch`` is the least-contended
    estimator (endpoint minima — module docstring step 3); the
    ``per_observation`` marginals pair each observation's own endpoints,
    giving the contention distribution that retires single-observation
    claims: ``min``/``median``/``spread`` (max − min) are all in
    seconds/epoch. An observation whose half chain was contended can come
    out non-positive (full ≤ half); those are recorded verbatim in
    ``per_observation`` and counted in ``contended``, but EXCLUDED from the
    min/median/spread summary — a clamped near-zero marginal would
    otherwise masquerade as an absurd throughput outlier. If even the
    ENDPOINT-MIN estimate is non-positive (every full chain beat by a half
    chain — heavy contention), the record is flagged ``unreliable`` rather
    than reporting the clamp as a measurement. The headline is the number to
    cite, the spread is the error bar.

    ``pre_full`` feeds an already-observed full-chain timing into the
    HEADLINE's endpoint minimum only (valid for a min estimator; saves a
    chain) — it is NOT paired into the distribution, whose observations must
    be adjacent in time.
    """
    half = n // 2
    denom = n - half
    halves = [h for h, _ in pairs]
    fulls = [f for _, f in pairs]
    per_obs = [(f - h) / denom for h, f in pairs]
    valid = [v for v in per_obs if v > 0]
    headline = (min(fulls + ([pre_full] if pre_full is not None else []))
                - min(halves)) / denom
    out = {
        "marginal_seconds_per_epoch": max(headline, 1e-9),
        "observations": len(pairs),
        "per_observation": [round(v, 9) for v in per_obs],
        "contended": len(per_obs) - len(valid),
    }
    if headline <= 0:
        out["unreliable"] = True
    if valid:
        out.update(
            min=min(valid), median=statistics.median(valid),
            spread=max(valid) - min(valid),
        )
    return out


def throughput_stats(dist: dict, samples_per_epoch: float) -> dict:
    """Convert a :func:`marginal_distribution` summary to samples/sec/chip:
    ``value`` from the least-contended headline; min/median over the VALID
    (positive-marginal) per-observation points (min throughput = slowest
    observation); ``spread`` = max − min. Contended (non-positive)
    observations are excluded from the summary and surfaced as a count; an
    ``unreliable`` distribution (even the endpoint-min estimate was
    contention-dominated) reports ``value: None`` instead of the 1e-9
    clamp's absurd implied throughput."""
    per = [samples_per_epoch / v for v in dist["per_observation"] if v > 0]
    out = {
        "value": (None if dist.get("unreliable") else round(
            samples_per_epoch / dist["marginal_seconds_per_epoch"], 2)),
        "observations": dist["observations"],
        "contended": dist.get("contended", 0),
    }
    if dist.get("unreliable"):
        out["unreliable"] = True
    if per:
        out.update(
            min=round(min(per), 2),
            median=round(statistics.median(per), 2),
            spread=round(max(per) - min(per), 2),
        )
    return out


def interleaved_ab(run_chains: dict, n: int, obs: int = 5) -> dict:
    """Paired interleaved A/B over named arms, N observations per arm.

    ``run_chains[name](k)`` must return wall-clock seconds for a k-epoch
    fully-materialized chain of that arm (arms pre-compiled by their first
    call). Per observation round, every arm's half chain is timed
    back-to-back, then every arm's full chain, with the arm ORDER alternating
    between rounds — a minutes-long contention window lands on all arms
    instead of one (sequential whole-arm A/Bs flipped sign between runs, r5).
    Returns ``{name: marginal_distribution(...)}``.
    """
    names = list(run_chains)
    pairs = {k: [] for k in names}
    halves = {}
    for i in range(obs):
        order = names if i % 2 == 0 else names[::-1]
        for k in order:
            halves[k] = run_chains[k](n // 2 + 1)
        for k in order:
            pairs[k].append((halves[k], run_chains[k](n + 1)))
    return {k: marginal_distribution(v, n) for k, v in pairs.items()}


def flops_per_sample_dims(windows: int, enc_in: int, enc_out: int,
                          hidden: int) -> float:
    """Matmul FLOPs for one training sample at arbitrary flagship-family
    dims (fwd ≈ enc + biLSTM + head; train ≈ 3× fwd for fwd+bwd)."""
    h = hidden // 2  # per direction
    enc = windows * enc_in * enc_out * 2
    lstm = windows * 2 * (enc_out * 4 * h + h * 4 * h) * 2  # both directions
    head = hidden * 256 * 2 + 256 * 64 * 2 + 64 * 2 * 2
    return 3.0 * (enc + lstm + head)


def flops_per_sample() -> float:
    """Matmul FLOPs for one training sample at the flagship HCP dims."""
    return flops_per_sample_dims(WINDOWS, ENC_IN, ENC_OUT, HIDDEN)


def _flagship_arm(engine_name: str = "dSGD", engine_kw: dict | None = None,
                  dims: dict | None = None):
    """Shared flagship-arm construction for every bench mode: the dims dict
    (flagship HCP defaults overridden by ``--small``), the ICA-LSTM
    model/task/engine/optimizer, and the synthetic per-site epoch data as
    NUMPY arrays (one RNG draw sequence — arms agree bit-for-bit on their
    inputs). A dims/model/dtype policy change lands here ONCE and every
    arm — steady-state, pipeline A/B, packed sites sweep — measures the
    same configuration.

    bf16 matmuls AND streamed activations with f32 carries/accumulation;
    the fused Pallas kernel keeps W_ih/W_hh resident in VMEM and streams
    the raw x once per step (ops/lstm_pallas.py)."""
    import numpy as np

    from dinunet_implementations_tpu.engines import make_engine
    from dinunet_implementations_tpu.models import ICALstm
    from dinunet_implementations_tpu.trainer import (
        FederatedTask,
        make_optimizer,
    )

    d = dict(sites=NUM_SITES, steps=STEPS_PER_EPOCH, batch=BATCH_PER_SITE,
             windows=WINDOWS, comps=COMPS, wlen=WLEN, enc_out=ENC_OUT,
             hidden=HIDDEN, compute_dtype="bfloat16")
    d.update(dims or {})
    model = ICALstm(input_size=d["enc_out"], hidden_size=d["hidden"],
                    num_comps=d["comps"], window_size=d["wlen"], num_cls=2,
                    compute_dtype=d["compute_dtype"])
    task = FederatedTask(model)
    engine = make_engine(engine_name, **(engine_kw or {}))
    opt = make_optimizer("adam", 1e-3)
    S, steps, B = d["sites"], d["steps"], d["batch"]
    rng = np.random.default_rng(0)
    np_x = rng.normal(
        size=(S, steps, B, d["windows"], d["comps"], d["wlen"])
    ).astype(np.float32)
    np_y = (rng.random((S, steps, B)) > 0.5).astype(np.int32)
    np_w = np.ones((S, steps, B), np.float32)
    return d, task, engine, opt, np_x, np_y, np_w


def _setup_epoch(engine_name: str = "dSGD", engine_kw: dict | None = None,
                 dims: dict | None = None, fault_plan=None,
                 epoch_kw: dict | None = None):
    """Build the compiled flagship epoch for one bench arm.

    Returns ``(run_chain, samples_per_epoch)``: ``run_chain(k)`` times a
    k-epoch fully-materialized chain (compile happens on the first call —
    call ``run_chain(1)`` once to warm up before timing). ``dims`` overrides
    the flagship model/data dims (``--small`` harness-validation mode).
    ``fault_plan`` (a robustness.FaultPlan) measures the fault-masked round:
    its epoch-0 liveness mask feeds every chained epoch. ``epoch_kw``
    threads extra ``make_train_epoch_fn`` kwargs (the r20 privacy arms:
    dp_clip / dp_noise_multiplier / personalize)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dinunet_implementations_tpu.trainer import (
        compile_epoch_aot,
        init_train_state,
        make_train_epoch_fn,
    )

    d, task, engine, opt, np_x, np_y, np_w = _flagship_arm(
        engine_name, engine_kw, dims
    )
    S, steps, B = d["sites"], d["steps"], d["batch"]
    # ship inputs pre-cast to the model's compute dtype (what the input
    # pipeline does for a bf16 model): halves the resident input footprint
    # and removes XLA's whole-input convert+layout copy from the epoch
    x = jnp.asarray(
        np_x,
        dtype=jnp.bfloat16 if d["compute_dtype"] == "bfloat16" else None,
    )
    y = jnp.asarray(np_y)
    w = jnp.asarray(np_w)

    state0 = init_train_state(
        task, engine, opt, jax.random.PRNGKey(0), x[0, 0], num_sites=S
    )
    epoch_fn = make_train_epoch_fn(
        task, engine, opt, mesh=None, local_iterations=1,
        **(epoch_kw or {}),
    )
    live = None
    if fault_plan is not None and fault_plan.injects_faults():
        # rounds == steps at local_iterations=1; the first epoch's window
        live = jnp.asarray(fault_plan.liveness(S, 0, steps))

    from dinunet_implementations_tpu.checks.sanitize import (
        CompileGuard,
        sanitize_enabled,
    )

    guard = None
    if sanitize_enabled():
        # --sanitize / DINUNET_SANITIZE=1: keep the PLAIN jitted epoch (its
        # compile cache is introspectable; the AOT path compiles exactly once
        # by construction, so there is nothing to guard there) and check the
        # compile counter after every timed chain — a chain that recompiles
        # is measuring compilation, not the federated round.
        guard = CompileGuard({"epoch_fn": epoch_fn}, label=engine_name)
    else:
        # resident epoch inputs live in the layout the executable wants (the
        # per-epoch on-device relayout copy moves into this one-time
        # device_put)
        epoch_fn, put_x = compile_epoch_aot(epoch_fn, state0, x, y, w, live=live)
        x = put_x(x)

    def run_chain(k: int) -> float:
        t = chain_epochs(epoch_fn, state0, x, y, w, k, live=live)
        if guard is not None:
            guard.check(context=f"engine={engine_name}, chain={k} epochs")
        return t

    return run_chain, S * steps * B


def measure_tpu(repeats: int = 5, with_distribution: bool = False,
                fault_plan=None, dims: dict | None = None):
    run_chain, samples = _setup_epoch(fault_plan=fault_plan, dims=dims)
    run_chain(1)  # compile + lazy-runtime warmup
    # N paired observations per endpoint: contended windows last minutes, so
    # more samples raise the odds of catching an uncontended one; the pairs
    # also give the min/median/spread distribution the JSON now carries
    pairs = [
        (run_chain(TIMED_EPOCHS // 2 + 1), run_chain(TIMED_EPOCHS + 1))
        for _ in range(repeats)
    ]
    dist = marginal_distribution(pairs, TIMED_EPOCHS)
    # n_chips = 1: the folded site axis runs on one chip, so per-chip ==
    # absolute. value is None when every observation was contention-dominated
    # (throughput_stats unreliable gate).
    stats = throughput_stats(dist, samples)
    if with_distribution:
        return stats["value"], stats
    return stats["value"]


# rankDAD A/B arms (--ab-rankdad): the r6 levers against the r5 baseline and
# the dSGD ceiling. "warm" = warm-started subspaces (engine-state Ω, the
# default); "bf16-iter" = mixed-precision power iteration via the bf16 wire;
# "cold-f32" = the r5 behavior (stateless, f32 everything).
RANKDAD_AB_ARMS = {
    "dsgd-ceiling": ("dSGD", {}),
    "rankdad-cold-f32": ("rankDAD", dict(
        dad_reduction_rank=10, dad_num_pow_iters=5, dad_tol=1e-3,
        dad_warm_start=False)),
    "rankdad-warm-f32": ("rankDAD", dict(
        dad_reduction_rank=10, dad_num_pow_iters=5, dad_tol=1e-3,
        dad_warm_start=True)),
    "rankdad-warm-bf16-iter": ("rankDAD", dict(
        dad_reduction_rank=10, dad_num_pow_iters=5, dad_tol=1e-3,
        dad_warm_start=True, precision_bits="16")),
}


def measure_rankdad_ab(obs: int = 5, n: int = TIMED_EPOCHS,
                       dims: dict | None = None) -> list[dict]:
    """Paired interleaved A/B of the rankDAD levers (one JSON record per
    arm). All arms compile up front; observations interleave per round
    (:func:`interleaved_ab`)."""
    import jax

    chains = {}
    samples = None
    for arm, (engine, kw) in RANKDAD_AB_ARMS.items():
        chains[arm], samples = _setup_epoch(engine, kw, dims=dims)
        chains[arm](1)  # compile + warm up before any timing starts
    dists = interleaved_ab(chains, n, obs=obs)
    records = []
    for arm, dist in dists.items():
        engine, kw = RANKDAD_AB_ARMS[arm]
        rec = {
            "metric": "samples/sec/chip (ICA-LSTM federated round, interleaved A/B)",
            "arm": arm,
            "engine": engine,
            "engine_kw": kw,
            "sites": (dims or {}).get("sites", NUM_SITES),
            "backend": jax.default_backend(),
            "chain_epochs": n,
            "samples_per_sec": throughput_stats(dist, samples),
            "unit": "samples/sec/chip",
        }
        if dims:
            rec["dims"] = dims
        elif rec["samples_per_sec"]["value"] is not None:
            # flagship dims: the MFU model applies
            rec["mfu"] = round(
                rec["samples_per_sec"]["value"] * flops_per_sample()
                / V5E_BF16_PEAK_FLOPS, 4,
            )
        records.append(rec)
    return records


def _engine_ab_records(arms: dict, metric: str, obs: int, n: int,
                       dims: dict | None, extra=None) -> list[dict]:
    """Shared paired-interleaved engine A/B driver (the --ab-rankdad
    protocol): compile every arm up front, interleave observations, one JSON
    record per arm. ``extra(arm, rec)`` may decorate each record."""
    import jax

    chains = {}
    samples = None
    for arm, (engine, kw) in arms.items():
        chains[arm], samples = _setup_epoch(engine, kw, dims=dims)
        chains[arm](1)  # compile + warm up before any timing starts
    dists = interleaved_ab(chains, n, obs=obs)
    records = []
    for arm, dist in dists.items():
        engine, kw = arms[arm]
        rec = {
            "metric": metric,
            "arm": arm,
            "engine": engine,
            "engine_kw": kw,
            "sites": (dims or {}).get("sites", NUM_SITES),
            "backend": jax.default_backend(),
            "chain_epochs": n,
            "samples_per_sec": throughput_stats(dists[arm], samples),
            "unit": "samples/sec/chip",
        }
        if dims:
            rec["dims"] = dims
        elif rec["samples_per_sec"]["value"] is not None:
            rec["mfu"] = round(
                rec["samples_per_sec"]["value"] * flops_per_sample()
                / V5E_BF16_PEAK_FLOPS, 4,
            )
        if extra is not None:
            extra(arm, rec)
        records.append(rec)
    return records


def _flagship_params_template(engine_name: str, dims: dict | None):
    """The flagship parameter tree (shapes only matter), built ONCE — the
    wire-byte models are pure shape arithmetic over it, so per-arm byte
    figures never rebuild the arm's dataset/state."""
    import jax
    import jax.numpy as jnp

    from dinunet_implementations_tpu.trainer import init_train_state

    # sites/steps/batch don't shape the parameters — shrink them so the
    # template build never allocates the (multi-GB at flagship dims)
    # synthetic dataset just to read shapes
    tiny = {**(dims or {}), "sites": 1, "steps": 1, "batch": 1}
    d, task, engine, opt, np_x, _, _ = _flagship_arm(engine_name, None, tiny)
    state = init_train_state(
        task, engine, opt, jax.random.PRNGKey(0), jnp.asarray(np_x[0, 0]),
        num_sites=1,
    )
    return state.params


def measure_wirequant_ab(quants, obs: int = 5, n: int = TIMED_EPOCHS,
                         dims: dict | None = None,
                         engine_name: str = "dSGD") -> list[dict]:
    """Paired interleaved A/B of the wire-quantization codecs
    (``--wire-quant bf16,int8,fp8``) against the f32 wire, one JSON record
    per arm with the MODELED per-device wire bytes and the shrink vs f32 —
    the same figures S002 verifies against the traced program."""
    from dinunet_implementations_tpu.engines import make_engine
    from dinunet_implementations_tpu.telemetry.metrics import payload_bytes_of

    arms = {"wire-f32": (engine_name, {})}
    for q in quants:
        arms[f"wire-{q}"] = (engine_name, dict(wire_quant=q))
    params = _flagship_params_template(engine_name, dims)
    bytes_by_arm = {
        arm: int(payload_bytes_of(make_engine(e, **kw), params))
        for arm, (e, kw) in arms.items()
    }

    def extra(arm, rec):
        rec["wire_quant"] = arms[arm][1].get("wire_quant", "none")
        rec["wire_bytes_per_device_round"] = bytes_by_arm[arm]
        rec["wire_shrink_vs_f32"] = round(
            bytes_by_arm["wire-f32"] / max(bytes_by_arm[arm], 1), 2
        )

    return _engine_ab_records(
        arms,
        "samples/sec/chip (ICA-LSTM federated round, quantized-wire A/B)",
        obs, n, dims, extra=extra,
    )


def measure_attacks_ab(attack_plan, robust: str = "trimmed_mean",
                       obs: int = 5, n: int = TIMED_EPOCHS,
                       dims: dict | None = None,
                       engine_name: str = "dSGD") -> list[dict]:
    """Hostile-site A/B (``--attacks``, r17): three paired interleaved arms
    of the flagship federated round —

    - ``clean``            : no attack, legacy aggregation (the baseline);
    - ``attacked-open``    : the AttackPlan injected, defense OFF (the
      documented-degradation arm);
    - ``attacked-<robust>``: the same attack with the robust reducer + the
      anomaly reputation layer ON (the defense-cost arm — the gather
      reducers' wire/compute overhead is the throughput claim under test,
      and the loss trajectory is the robustness claim).

    Each record carries throughput stats, the final chained epoch's mean
    train loss (the quality signal: defense-off diverges, defense-on
    tracks clean), the plan JSON, and the robust-mode modeled per-device
    wire bytes (the figure S002 proves against the traced program). The
    AUC-level robustness gates live in tests/test_golden.py; this artifact
    records the measured arms a claim can cite.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dinunet_implementations_tpu.checks.sanitize import (
        CompileGuard,
        sanitize_enabled,
    )
    from dinunet_implementations_tpu.robustness.attacks import attack_window
    from dinunet_implementations_tpu.telemetry.metrics import payload_bytes_of
    from dinunet_implementations_tpu.trainer import (
        init_train_state,
        make_train_epoch_fn,
    )

    arm_specs = {
        "clean": (False, "none"),
        "attacked-open": (True, "none"),
        f"attacked-{robust}": (True, robust),
    }
    chains, states, fns, data, byte_model = {}, {}, {}, {}, {}
    samples = None
    for arm, (attacked, mode) in arm_specs.items():
        d, task, engine, opt, np_x, np_y, np_w = _flagship_arm(
            engine_name, dict(robust_agg=mode), dims
        )
        S, steps = d["sites"], d["steps"]
        x = jnp.asarray(
            np_x,
            dtype=jnp.bfloat16 if d["compute_dtype"] == "bfloat16" else None,
        )
        y, w = jnp.asarray(np_y), jnp.asarray(np_w)
        state0 = init_train_state(
            task, engine, opt, jax.random.PRNGKey(0), x[0, 0], num_sites=S,
            reputation=mode != "none",
        )
        fn = make_train_epoch_fn(
            task, engine, opt, mesh=None, local_iterations=1,
            attack_plan=attack_plan if attacked else None, robust_agg=mode,
        )
        am = (
            jnp.asarray(attack_window(attack_plan, S, 0, steps))
            if attacked else None
        )
        guard = (
            CompileGuard({"epoch_fn": fn}, label=arm)
            if sanitize_enabled() else None
        )

        def run_chain(k, fn=fn, state0=state0, x=x, y=y, w=w, am=am,
                      guard=guard, arm=arm):
            t = chain_epochs(fn, state0, x, y, w, k, live=None, attack=am)
            if guard is not None:
                guard.check(context=f"arm={arm}, chain={k} epochs")
            return t

        run_chain(1)  # compile + warm up before any timing starts
        chains[arm] = run_chain
        states[arm], fns[arm], data[arm] = state0, fn, (x, y, w, am)
        byte_model[arm] = int(payload_bytes_of(engine, state0.params))
        samples = S * steps * d["batch"]
    dists = interleaved_ab(chains, n, obs=obs)
    records = []
    for arm, (attacked, mode) in arm_specs.items():
        # quality probe: n chained TRAINING epochs, last epoch's mean loss —
        # the measured defense-on-tracks-clean / defense-off-diverges signal
        s = states[arm]
        x, y, w, am = data[arm]
        losses = None
        for _ in range(max(n, 2)):
            if am is not None:
                s, losses = fns[arm](s, x, y, w, None, am)
            else:
                s, losses = fns[arm](s, x, y, w)
        lv = np.asarray(losses)
        lv = lv[np.isfinite(lv)]
        rec = {
            "metric": "samples/sec/chip (ICA-LSTM federated round, "
                      "hostile-site A/B)",
            "arm": arm,
            "engine": engine_name,
            "attacked": attacked,
            "robust_agg": mode,
            "attacks": attack_plan.to_json(),
            "sites": (dims or {}).get("sites", NUM_SITES),
            "backend": jax.default_backend(),
            "chain_epochs": n,
            "samples_per_sec": throughput_stats(dists[arm], samples),
            "unit": "samples/sec/chip",
            "final_epoch_loss": (
                round(float(lv.mean()), 6) if lv.size else None
            ),
            "wire_bytes_per_device_round": byte_model[arm],
        }
        if dims:
            rec["dims"] = dims
        records.append(rec)
    return records


def measure_privacy_ab(dp_noise: float = 0.5, dp_clip: float = 1.0,
                       secure_mode: str = "mask", obs: int = 5,
                       n: int = TIMED_EPOCHS, dims: dict | None = None,
                       engine_name: str = "dSGD") -> list[dict]:
    """Privacy-plane A/B (``--dp-noise`` / ``--secure-agg``, r20): paired
    interleaved arms of the flagship federated round —

    - ``clean``        : the legacy program (the baseline);
    - ``dp``           : in-scan DP-SGD (clip ``dp_clip`` + ``dp_noise``·C
      Gaussian noise per site per round, privacy/dpsgd.py) — the
      mechanism-cost arm, with the RDP accountant's ``epsilon_final`` for
      the timed chain length recorded next to the throughput;
    - ``dp+secureagg`` : the same mechanism with the masked fixed-point
      wire on top at ``secure_mode`` ("mask", or "mask-nopads" — the
      verification arm — recorded VERBATIM in the record; "off" drops the
      arm). Without DP noise the masked arm runs standalone
      (``secureagg``).

    Each record carries throughput stats, the modeled per-device wire bytes
    (the figure S002 proves — int32 grid == f32 bytes for the masked
    arms), the spent ε at the recorded chain length, and the privacy knobs
    verbatim. The accuracy-floor gates live in tests/test_golden.py; this
    artifact records the measured arms a claim can cite
    (docs/bench_privacy_ab_r20.jsonl)."""
    import jax

    from dinunet_implementations_tpu.engines import make_engine
    from dinunet_implementations_tpu.privacy import (
        RdpAccountant,
        effective_noise_multiplier,
        sampling_fraction,
    )
    from dinunet_implementations_tpu.telemetry.metrics import payload_bytes_of

    from dinunet_implementations_tpu.privacy import secure_agg_enabled

    secure = secure_agg_enabled(secure_mode)  # validates the mode string
    dp_kw = dict(dp_clip=dp_clip, dp_noise_multiplier=dp_noise)
    arms = {"clean": ({}, {})}
    if dp_noise > 0:
        arms["dp"] = ({}, dp_kw)
        if secure:
            arms["dp+secureagg"] = ({"secure_agg": secure_mode}, dp_kw)
    elif secure:
        arms["secureagg"] = ({"secure_agg": secure_mode}, {})

    chains = {}
    samples = None
    byte_model = {}
    params = _flagship_params_template(engine_name, dims)  # arm-invariant
    for arm, (eng_kw, epoch_kw) in arms.items():
        chains[arm], samples = _setup_epoch(
            engine_name, eng_kw, dims=dims, epoch_kw=epoch_kw
        )
        chains[arm](1)  # compile + warm up before any timing starts
        byte_model[arm] = int(
            payload_bytes_of(make_engine(engine_name, **eng_kw), params)
        )
    dists = interleaved_ab(chains, n, obs=obs)
    d = dict(sites=NUM_SITES, steps=STEPS_PER_EPOCH, batch=BATCH_PER_SITE)
    d.update(dims or {})
    # the synthetic flagship pool: each site holds steps·batch examples and
    # each round consumes batch of them — the accountant's q for the arm
    q = sampling_fraction(d["batch"], 1, [d["steps"] * d["batch"]])
    records = []
    for arm, (eng_kw, epoch_kw) in arms.items():
        eps = None
        if epoch_kw.get("dp_noise_multiplier", 0) > 0:
            acct = RdpAccountant().step(
                effective_noise_multiplier(epoch_kw["dp_noise_multiplier"]),
                q, steps=n * d["steps"],
            )
            eps = round(acct.epsilon(1e-5)[0], 4)
        rec = {
            "metric": "samples/sec/chip (ICA-LSTM federated round, "
                      "privacy-plane A/B)",
            "arm": arm,
            "engine": engine_name,
            "dp_clip": epoch_kw.get("dp_clip", 0.0),
            "dp_noise_multiplier": epoch_kw.get("dp_noise_multiplier", 0.0),
            "secure_agg": eng_kw.get("secure_agg", "off"),
            "epsilon_final": eps,
            "dp_delta": 1e-5 if eps is not None else None,
            "sampling_fraction": round(q, 6),
            "sites": (dims or {}).get("sites", NUM_SITES),
            "backend": jax.default_backend(),
            "chain_epochs": n,
            "samples_per_sec": throughput_stats(dists[arm], samples),
            "unit": "samples/sec/chip",
            "wire_bytes_per_device_round": byte_model[arm],
        }
        if dims:
            rec["dims"] = dims
        records.append(rec)
    return records


def _setup_pipeline_arm(arm: str, dims: dict | None = None,
                        donate: bool = True):
    """One input-pipeline A/B arm (``--pipeline``): unlike the steady-state
    bench arms above (which pre-place the epoch inputs once), these chains
    model the TRAINER's per-epoch input path —

    - ``host``: the dense ``[S, steps, B, ...]`` epoch tensor is re-shipped
      to the device every epoch (cast to the compute dtype in flight), i.e.
      what FederatedTrainer's host pipeline pays each epoch;
    - ``device``: the inventory is uploaded once outside the timed region and
      each epoch ships only the ``[S, steps, B]`` int32 index plan; batches
      are gathered on-device inside the jitted epoch (trainer/steps.py
      ``pipeline="device"``), with the carried state donated.

    Returns ``(run_chain, samples_per_epoch, info)``; ``info`` carries
    ``transfer_bytes_per_epoch`` and a :class:`SpanTracer` whose ``feed``
    spans time the per-epoch host-blocked input path (plan build + transfer
    dispatch — the work the device waits on between fused epoch dispatches).
    The tracer replaced the hand-rolled ``host_s``/``epochs`` timer dict
    (telemetry/tracer.py is the one timing helper). Both arms run the plain
    jitted epoch (no AOT layouts) so the comparison isolates the input
    path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dinunet_implementations_tpu.telemetry import SpanTracer
    from dinunet_implementations_tpu.trainer import (
        init_train_state,
        make_train_epoch_fn,
    )

    d, task, engine, opt, np_x, np_y, np_w = _flagship_arm(dims=dims)
    S, steps, B = d["sites"], d["steps"], d["batch"]
    dt = jnp.bfloat16 if d["compute_dtype"] == "bfloat16" else jnp.float32
    state0 = init_train_state(
        task, engine, opt, jax.random.PRNGKey(0), jnp.asarray(np_x[0, 0]),
        num_sites=S,
    )
    info = {"tracer": SpanTracer()}

    if arm == "host":
        epoch_fn = make_train_epoch_fn(
            task, engine, opt, mesh=None, local_iterations=1,
            pipeline="host", donate_state=donate,
        )

        def feed():
            with info["tracer"].span("feed"):
                return (jnp.asarray(np_x, dtype=dt), jnp.asarray(np_y),
                        jnp.asarray(np_w))

        info["transfer_bytes_per_epoch"] = (
            np_x.size * np.dtype(dt).itemsize + np_y.nbytes + np_w.nbytes
        )
    else:
        epoch_fn = make_train_epoch_fn(
            task, engine, opt, mesh=None, local_iterations=1,
            pipeline="device", donate_state=donate,
        )
        # inventory: each bench site owns exactly steps*B samples; uploaded
        # ONCE, outside the timed chains (what the trainer pays per fit)
        inv_x = jnp.asarray(np_x.reshape((S, steps * B) + np_x.shape[3:]),
                            dtype=dt)
        inv_y = jnp.asarray(np_y.reshape(S, steps * B))
        np_idx = np.broadcast_to(
            np.arange(steps * B, dtype=np.int32).reshape(1, steps, B),
            (S, steps, B),
        ).copy()

        def feed():
            with info["tracer"].span("feed"):
                return (inv_x, inv_y, jnp.asarray(np_idx))

        info["transfer_bytes_per_epoch"] = np_idx.nbytes

    from dinunet_implementations_tpu.checks.sanitize import (
        CompileGuard,
        sanitize_enabled,
    )

    guard = (
        CompileGuard({"epoch_fn": epoch_fn}, label=f"pipeline-{arm}")
        if sanitize_enabled() else None
    )

    def run_chain(k: int) -> float:
        # donation consumes the input state's buffers: every chain starts
        # from a fresh copy so state0 stays reusable across chains (the copy
        # is one epoch-state clone, amortized over the chain and cancelled by
        # the marginal estimator anyway)
        s = jax.tree.map(jnp.copy, state0) if donate else state0
        t0 = time.time()
        for _ in range(k):
            s, _ = epoch_fn(s, *feed())
        jax.tree.map(np.asarray, s)
        t = time.time() - t0
        if guard is not None:
            guard.check(context=f"pipeline={arm}, chain={k} epochs")
        return t

    return run_chain, S * steps * B, info


def measure_pipeline_ab(mode: str = "ab", obs: int = 5, n: int = TIMED_EPOCHS,
                        dims: dict | None = None,
                        donate: bool = True) -> list[dict]:
    """Input-pipeline A/B (``--pipeline host|device|ab``): one JSON record
    per arm with the throughput distribution plus the pipeline-specific
    fields — ``transfer_bytes_per_epoch`` (the per-epoch host→device bytes;
    the device arm ships index-plan bytes, not dataset bytes) and
    ``host_blocked_ms_per_epoch`` (measured host time building/shipping epoch
    inputs). Arms are interleaved per observation round like --ab-rankdad."""
    import jax

    arms = ("host", "device") if mode == "ab" else (mode,)
    chains, infos = {}, {}
    samples = None
    for arm in arms:
        chains[arm], samples, infos[arm] = _setup_pipeline_arm(
            arm, dims=dims, donate=donate
        )
        chains[arm](1)  # compile + warm up before any timing starts
        infos[arm]["tracer"].reset()  # exclude warmup from the feed stats
    if len(arms) == 2:
        dists = interleaved_ab(chains, n, obs=obs)
    else:
        pairs = [
            (chains[arms[0]](n // 2 + 1), chains[arms[0]](n + 1))
            for _ in range(obs)
        ]
        dists = {arms[0]: marginal_distribution(pairs, n)}
    records = []
    for arm in arms:
        info = infos[arm]
        rec = {
            "metric": "samples/sec/chip (ICA-LSTM federated round, "
                      "input-pipeline A/B)",
            "arm": f"pipeline-{arm}",
            "pipeline": arm,
            "sites": (dims or {}).get("sites", NUM_SITES),
            "backend": jax.default_backend(),
            "chain_epochs": n,
            "donate_state": donate,
            "transfer_bytes_per_epoch": int(info["transfer_bytes_per_epoch"]),
            "host_blocked_ms_per_epoch": round(
                1e3 * info["tracer"].total_seconds("feed")
                / max(info["tracer"].count("feed"), 1), 3
            ),
            "samples_per_sec": throughput_stats(dists[arm], samples),
            "unit": "samples/sec/chip",
        }
        if arm == "device" and "host" in infos:
            rec["transfer_reduction_vs_host"] = round(
                infos["host"]["transfer_bytes_per_epoch"]
                / max(info["transfer_bytes_per_epoch"], 1), 1,
            )
        if dims:
            rec["dims"] = dims
        elif rec["samples_per_sec"]["value"] is not None:
            rec["mfu"] = round(
                rec["samples_per_sec"]["value"] * flops_per_sample()
                / V5E_BF16_PEAK_FLOPS, 4,
            )
        records.append(rec)
    return records


def _ensure_host_devices(want: int) -> None:
    """Provision ``want`` virtual CPU devices for the sites-scaling sweep —
    BEFORE jax initializes (bench imports jax lazily inside the measure
    functions, so calling this first in main() is early enough). Only the
    host-platform device count is touched — never JAX_PLATFORMS — so an
    accelerator host (pinned or auto-detected) keeps its hardware mesh and
    the flag only takes effect where jax resolves to the CPU backend."""
    import os

    plat = os.environ.get("JAX_PLATFORMS", "")
    if plat and plat != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={want}"
        ).strip()


def _setup_packed_epoch(S: int, K: int, engine_name: str = "dSGD",
                        engine_kw: dict | None = None,
                        dims: dict | None = None, fault_plan=None,
                        staleness_bound: int = 0, attack_plan=None,
                        robust_agg: str = "none", slices: int = 1,
                        dcn_quant: str = "", epoch_kw: dict | None = None):
    """One sites-scaling arm: S virtual sites packed K per device on a real
    ``(site,)`` mesh — the full federated round as ONE compiled SPMD program
    with two-level aggregation (trainer/steps.py packed path). Epoch inputs
    and state are committed to their steady-state shardings up front, so the
    chains measure the round, not placement, and the program compiles
    exactly once (asserted under --sanitize).

    Returns ``(run_chain, samples_per_epoch, info)``; ``info`` records the
    mesh size and the per-device modeled wire bytes (the figure S002
    verifies against the traced program).

    ``fault_plan`` threads a liveness mask (drops / flaky / delay_at
    stragglers, robustness/faults.py) through the packed round — the churn
    smoke's arm; ``staleness_bound > 0`` additionally measures the
    staleness-bounded buffered-async round (trainer/steps.py, r13), where a
    straggling virtual site's buffered update keeps contributing at decayed
    weight. ``attack_plan`` + ``robust_agg`` (r17, robustness/attacks.py)
    compose on top: the CI hostile-site smoke measures the byzantine-
    attacked, robustly-aggregated packed round as one compiled program.

    ``slices > 1`` (r18) lays the three-tier ``(slice, site)`` topology over
    the same device set — the sweep then ALSO records the per-tier wire
    split (``ici_bytes_per_device_round`` vs ``dcn_bytes_per_slice_round``,
    the latter quantized by ``dcn_quant``; both figures are what the sliced
    semantic cells prove against the traced program)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dinunet_implementations_tpu.parallel.mesh import (
        packed_site_mesh,
        site_axis_of,
        sliced_site_mesh,
    )
    from dinunet_implementations_tpu.telemetry.metrics import (
        dcn_bytes_of,
        payload_bytes_of,
    )
    from dinunet_implementations_tpu.trainer import (
        init_train_state,
        make_train_epoch_fn,
    )
    from dinunet_implementations_tpu.trainer.steps import _state_specs

    if slices > 1:
        if S % slices:
            raise SystemExit(
                f"--slices {slices} must divide the site count ({S}) — "
                f"every slice holds the same number of virtual sites"
            )
        mesh = sliced_site_mesh(slices, S // slices, K)
    else:
        mesh = packed_site_mesh(S, K)
    site_part = site_axis_of(mesh)
    engine_kw = {**(engine_kw or {}), "robust_agg": robust_agg,
                 "dcn_wire_quant": dcn_quant}
    d, task, engine, opt, np_x, np_y, np_w = _flagship_arm(
        engine_name, engine_kw, {**(dims or {}), "sites": S}
    )
    x = jnp.asarray(
        np_x,
        dtype=jnp.bfloat16 if d["compute_dtype"] == "bfloat16" else None,
    )
    y, w = jnp.asarray(np_y), jnp.asarray(np_w)
    state0 = init_train_state(
        task, engine, opt, jax.random.PRNGKey(0), x[0, 0], num_sites=S,
        staleness_bound=staleness_bound,
        reputation=robust_agg != "none",
    )
    live = None
    if fault_plan is not None and fault_plan.injects_faults():
        # rounds == steps at local_iterations=1; the first epoch's window
        live = jnp.asarray(fault_plan.liveness(S, 0, d["steps"]))
    slice_live = None
    if (
        slices > 1 and fault_plan is not None
        and fault_plan.injects_slice_faults()
    ):
        # the r19 slice-tier chaos arm: throughput of the slice-masked
        # three-tier program (replicated mask, one program per pattern)
        slice_live = jnp.asarray(
            fault_plan.slice_liveness(slices, 0, d["steps"])
        )
    attack = None
    if attack_plan is not None and attack_plan.injects_attacks():
        from dinunet_implementations_tpu.robustness.attacks import (
            attack_window,
        )

        attack = jnp.asarray(attack_window(attack_plan, S, 0, d["steps"]))
    ici_bytes = int(payload_bytes_of(engine, state0.params, pack=K))
    info = {
        "mesh_devices": int(mesh.devices.size),
        "wire_bytes_per_device_round": ici_bytes,
        "ici_bytes_per_device_round": ici_bytes,
        # the per-slice inter-slice hop figure (0 on single-slice meshes)
        "dcn_bytes_per_slice_round": int(dcn_bytes_of(
            engine, state0.params, pack=K,
            sites_per_slice=S // max(slices, 1), slices=slices,
        )),
    }
    # commit everything to its steady-state sharding: inputs split over the
    # site tier(s) into [K, ...] device blocks, state to the epoch's own
    # specs (the trainer's _place_state move — avoids a warmup recompile)
    site_sh = NamedSharding(mesh, P(site_part))
    x, y, w = (jax.device_put(a, site_sh) for a in (x, y, w))
    if live is not None:
        live = jax.device_put(live, site_sh)
    if slice_live is not None:
        # replicated: every member reads its own slice's row (r19)
        slice_live = jax.device_put(slice_live, NamedSharding(mesh, P()))
    if attack is not None:
        # the attack mask rides after `live` positionally; live stays None
        # for attack-only runs — the same program form the runner CLI
        # compiles (chain_epochs passes live=None through)
        attack = jax.device_put(attack, site_sh)
    state0 = jax.tree.map(
        lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec)),
        state0, _state_specs(state0, site_part),
    )
    epoch_fn = make_train_epoch_fn(
        task, engine, opt, mesh=mesh, local_iterations=1,
        staleness_bound=staleness_bound, attack_plan=attack_plan,
        robust_agg=robust_agg,
        # r20 privacy arms: dp_clip / dp_noise_multiplier via --dp-noise
        **(epoch_kw or {}),
    )

    from dinunet_implementations_tpu.checks.sanitize import (
        CompileGuard,
        sanitize_enabled,
    )

    guard = (
        CompileGuard(
            {"epoch_fn": epoch_fn},
            label=f"sites{S}-pack{K}" + (f"-slices{slices}" if slices > 1
                                         else ""),
        )
        if sanitize_enabled() else None
    )

    def run_chain(k: int) -> float:
        t = chain_epochs(epoch_fn, state0, x, y, w, k, live=live,
                         attack=attack, slice_live=slice_live)
        if guard is not None:
            guard.check(context=f"sites={S}, pack={K}, chain={k} epochs")
        return t

    return run_chain, S * d["steps"] * d["batch"], info


def measure_sites_scaling(sites_list, packs=None, obs: int = 3,
                          n: int = TIMED_EPOCHS, dims: dict | None = None,
                          engine_name: str = "dSGD",
                          engine_kw: dict | None = None, fault_plan=None,
                          staleness_bound: int = 0, attack_plan=None,
                          robust_agg: str = "none",
                          slices_list=None, dcn_quant: str = "",
                          epoch_kw: dict | None = None) -> list[dict]:
    """The sites-scaling sweep (``--sites``): for each virtual site count S,
    run the packed federated round on the available device mesh and emit one
    JSON record with ``sites`` / ``sites_per_chip`` / ``pack_factor`` — the
    proof that site count is no longer capped at device count. ``packs``
    gives an explicit pack factor per S; default picks the smallest K that
    divides S with an S/K-member site mesh fitting the device set (every
    device used when device_count divides S; e.g. 12 sites on 8 devices
    auto-pack K=2 onto a 6-member mesh).

    ``slices_list`` (r18, ``--slices``) crosses each S with the given slice
    counts on the three-tier ``(slice, site)`` topology: every record then
    carries ``slices`` / ``sites_per_slice`` and the per-TIER wire split —
    ``ici_bytes_per_device_round`` (unchanged by slicing: tiers 0+1 are the
    packed two-level reduce) vs ``dcn_bytes_per_slice_round`` (the
    inter-slice hop, quantized by ``dcn_quant``) with the codec's
    shrink-vs-f32 ratio, the figures the sliced semantic cells prove
    against traced operand shapes."""
    import jax

    def auto_pack(S: int, n_dev: int) -> int:
        k = max(-(-S // n_dev), 1)  # ceil: the densest packing that fits
        while S % k:  # walk up to the next divisor of S
            k += 1
        return k

    records = []
    n_dev = len(jax.devices())
    for i, S in enumerate(sites_list):
        K = packs[i] if packs is not None else auto_pack(S, n_dev)
        for slices in (slices_list or [1]):
            run_chain, samples, info = _setup_packed_epoch(
                S, K, engine_name=engine_name, engine_kw=engine_kw,
                dims=dims, fault_plan=fault_plan,
                staleness_bound=staleness_bound,
                attack_plan=attack_plan, robust_agg=robust_agg,
                slices=slices, dcn_quant=dcn_quant, epoch_kw=epoch_kw,
            )
            run_chain(1)  # compile + warm up outside the timing
            pairs = [
                (run_chain(n // 2 + 1), run_chain(n + 1)) for _ in range(obs)
            ]
            dist = marginal_distribution(pairs, n)
            rec = {
                "metric": "samples/sec (ICA-LSTM federated round, packed "
                          "sites-scaling sweep)",
                "engine": engine_name,
                "sites": S,
                "pack_factor": K,
                "sites_per_chip": K,
                "mesh_devices": info["mesh_devices"],
                "devices_available": n_dev,
                "wire_bytes_per_device_round":
                    info["wire_bytes_per_device_round"],
                "ici_bytes_per_device_round":
                    info["ici_bytes_per_device_round"],
                "backend": jax.default_backend(),
                "chain_epochs": n,
                "samples_per_sec": throughput_stats(dist, samples),
                "unit": "samples/sec (whole mesh)",
            }
            if slices_list is not None:
                rec.update(
                    slices=slices,
                    sites_per_slice=S // max(slices, 1),
                    dcn_bytes_per_slice_round=
                        info["dcn_bytes_per_slice_round"],
                )
                if slices > 1:
                    # codec shrink on the expensive hop: the same sliced
                    # topology's f32 (no-DCN-codec) figure over this one
                    from dinunet_implementations_tpu.engines import (
                        make_engine,
                    )
                    from dinunet_implementations_tpu.telemetry.metrics \
                        import dcn_bytes_of

                    base_kw = {
                        k: v for k, v in (engine_kw or {}).items()
                        if k not in ("wire_quant", "dcn_wire_quant")
                    }
                    ref = make_engine(
                        engine_name, robust_agg=robust_agg, **base_kw
                    )
                    params = _flagship_params_template(engine_name, dims)
                    f32 = dcn_bytes_of(
                        ref, params, pack=K,
                        sites_per_slice=S // slices, slices=slices,
                    )
                    if info["dcn_bytes_per_slice_round"]:
                        rec["dcn_shrink_vs_f32"] = round(
                            f32 / info["dcn_bytes_per_slice_round"], 3
                        )
                if dcn_quant:
                    rec["dcn_wire_quant"] = dcn_quant
            if engine_kw:
                rec["engine_kw"] = engine_kw
            if dims:
                rec["dims"] = {**dims, "sites": S}
            if fault_plan is not None:
                rec["faults"] = fault_plan.to_json()
                steps = (dims or {}).get("steps", STEPS_PER_EPOCH)
                rec["dead_site_rounds"] = int(
                    (fault_plan.liveness(S, 0, steps) == 0).sum()
                )
            if staleness_bound:
                rec["staleness_bound"] = staleness_bound
            if attack_plan is not None:
                rec["attacks"] = attack_plan.to_json()
            if robust_agg != "none":
                rec["robust_agg"] = robust_agg
            # r20 privacy composition (--sites --dp-noise / --secure-agg):
            # the sweep records the mechanism knobs + the spent ε for the
            # timed chain, next to the (S002-proven) wire figures
            sigma = (epoch_kw or {}).get("dp_noise_multiplier", 0.0)
            if sigma > 0:
                from dinunet_implementations_tpu.privacy import (
                    RdpAccountant,
                    effective_noise_multiplier,
                    sampling_fraction,
                )

                steps = (dims or {}).get("steps", STEPS_PER_EPOCH)
                batch = (dims or {}).get("batch", BATCH_PER_SITE)
                q = sampling_fraction(batch, 1, [steps * batch])
                rec["dp_clip"] = (epoch_kw or {}).get("dp_clip", 0.0)
                rec["dp_noise_multiplier"] = sigma
                rec["epsilon_final"] = round(
                    RdpAccountant()
                    .step(effective_noise_multiplier(sigma), q,
                          steps=n * steps)
                    .epsilon(1e-5)[0], 4,
                )
            if (engine_kw or {}).get("secure_agg", "off") != "off":
                rec["secure_agg"] = engine_kw["secure_agg"]
            records.append(rec)
    return records


def measure_cpu_baseline() -> float:
    """Live re-measurement of the torch reference (optional)."""
    import importlib.util

    import torch

    spec = importlib.util.spec_from_file_location(
        "ref_ica", "/root/reference/comps/icalstm/models.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    m = mod.ICALstm(input_size=ENC_OUT, hidden_size=HIDDEN, bidirectional=True,
                    num_cls=2, num_comps=COMPS, window_size=WLEN)
    opt = torch.optim.Adam(m.parameters(), lr=1e-3)
    crit = torch.nn.CrossEntropyLoss()
    B = 16
    x = torch.randn(B, WINDOWS, COMPS, WLEN)
    y = torch.randint(0, 2, (B,))
    for _ in range(2):
        opt.zero_grad(); out, _ = m(x); crit(out, y).backward(); opt.step()
    t = time.time()
    iters = 4
    for _ in range(iters):
        opt.zero_grad(); out, _ = m(x); crit(out, y).backward(); opt.step()
    return iters * B / (time.time() - t)


def _serving_setup(dims: dict | None):
    """Tiny shared builder for the serving arms: an unidirectional ICA-LSTM
    config (the streaming-capable flagship shape) + initialized params."""
    import jax
    import jax.numpy as jnp

    from dinunet_implementations_tpu.core.config import (
        NNComputation,
        TrainConfig,
    )
    from dinunet_implementations_tpu.runner.registry import get_task
    from dinunet_implementations_tpu.trainer.steps import FederatedTask

    d = dims or {}
    windows = d.get("windows", WINDOWS)
    comps = d.get("comps", COMPS)
    wlen = d.get("wlen", WLEN)
    cfg = TrainConfig(task_id=NNComputation.TASK_ICA).with_overrides({
        "ica_args": {
            "num_components": comps, "window_size": wlen,
            "temporal_size": windows * wlen, "window_stride": wlen,
            "input_size": d.get("enc_out", ENC_OUT),
            "hidden_size": d.get("hidden", HIDDEN),
            "bidirectional": False,
        },
    })
    task = FederatedTask(get_task(cfg.task_id).build_model(cfg))
    params, stats = task.init_variables(
        jax.random.PRNGKey(0), jnp.ones((2, windows, comps, wlen))
    )
    return cfg, task, params, stats, (windows, comps, wlen)


def measure_serving(requests: int = 100, dims: dict | None = None,
                    stream_T: int = 512, chunk: int = 8,
                    cache_dir: str | None = None):
    """The serving-path arms (r15), one JSON record each:

    - ``startup``: engine warmup (AOT-compiling every bucket executable)
      twice against one persistent compile cache directory — the one
      ``core/jaxcompat.py`` resolves (``JAX_COMPILATION_CACHE_DIR``, else
      ``cache_dir``, else ``.jax_cache`` beside this file). The first
      warmup is cold only if that directory started empty;
    - ``batched``: a mixed-bucket request storm through the full
      submit→microbatch→executable path: p50/p95/p99 request latency,
      requests/s, samples/s, pad-waste %, bucket hit-rate, and the
      zero-compiles-after-warmup count;
    - ``stream-o1``: per-STEP latency of the streaming executable as a
      session's history grows 0 → ``stream_T`` timesteps (direct
      executable timing, admission delay excluded) — the O(1) claim is
      this curve being FLAT in history length;
    - ``recompute``: the alternative a session cache avoids — re-running
      the full batched forward over the whole prefix at T ∈ {8, 64,
      stream_T}: per-step cost of the recompute path grows with T (and
      each length needs its own compiled program).
    """
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dinunet_implementations_tpu.core.jaxcompat import (
        resolve_compile_cache_dir,
    )
    from dinunet_implementations_tpu.serving.engine import InferenceEngine
    from dinunet_implementations_tpu.serving.session import init_carry_table
    from dinunet_implementations_tpu.trainer.steps import eval_forward

    cfg, task, params, stats, (windows, comps, wlen) = _serving_setup(dims)
    cache = resolve_compile_cache_dir(cache_dir or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".jax_cache"
    ))
    cfg = cfg.replace(compile_cache_dir=cache)
    backend = jax.default_backend()
    base = {
        "unit": None, "backend": backend,
        "dims": dims or {"windows": windows, "comps": comps, "wlen": wlen,
                         "enc_out": ENC_OUT, "hidden": HIDDEN},
    }

    def build(streaming=None):
        eng = InferenceEngine(
            cfg, params=params, batch_stats=stats,
            row_buckets=(1, 2, 4, 8), stream_buckets=(1, 4),
            stream_chunk=chunk, stream_slots=16, max_delay_ms=1.0,
            streaming=streaming,
        )
        eng.warmup()
        return eng

    # -- startup: first warmup vs a restart against the same cache
    # directory, on the batched-only shape
    cold = build(streaming=False)
    cold_s = cold.warmup_seconds
    cold.close()
    warm = build(streaming=False)  # same shapes: XLA compiles load from disk
    records = [{
        **base,
        "metric": "serving start time (AOT warmup, batched buckets, cold "
                  "vs persistent-compile-cache warm)",
        "arm": "startup", "unit": "seconds",
        "cold_start_s": cold_s, "cachewarm_start_s": warm.warmup_seconds,
        "speedup": round(cold_s / max(warm.warmup_seconds, 1e-9), 2),
        "compile_cache_dir": cache,
        "executables": len(warm._exec),
    }]
    warm.close()
    eng = build()  # the streaming engine serving the traffic arms

    try:
        # -- batched traffic: mixed request sizes over every bucket
        rng = np.random.default_rng(0)
        sizes = (1, 2, 3, 4, 8)
        futures = [
            eng.submit(rng.normal(
                size=(sizes[i % len(sizes)], windows, comps, wlen)
            ).astype(np.float32))
            for i in range(requests)
        ]
        for f in futures:
            f.result()
        s = eng.summary()
        records.append({
            **base,
            "metric": "serving request latency / throughput (batched lane)",
            "arm": "batched", "unit": "ms",
            "requests": s["requests"], "dispatches": s["dispatches"],
            "latency_ms_p50": s["latency_ms_p50"],
            "latency_ms_p95": s["latency_ms_p95"],
            "latency_ms_p99": s["latency_ms_p99"],
            "requests_per_s": s["requests_per_s"],
            "samples_per_s": s["samples_per_s"],
            "pad_waste_pct": s["pad_waste_pct"],
            "bucket_hit_rate": s["bucket_hit_rate"],
            "compiles_after_warmup": s["compiles_after_warmup"],
        })

        # -- streaming O(1): per-step executable latency vs session history
        exec1 = eng._exec[("stream", 1)]
        a = cfg.ica_args
        n_chunks = stream_T // chunk
        x = rng.normal(size=(1, chunk, comps, wlen)).astype(np.float32)
        sv = np.ones((1, chunk), np.float32)
        valid = np.ones((1,), np.float32)
        per_chunk = [float("inf")] * n_chunks
        for _ in range(3):  # least-contended minimum per position
            table = jax.device_put(init_carry_table(16, a.hidden_size))
            fresh = np.ones((1,), np.float32)
            for i in range(n_chunks):
                t0 = time.perf_counter()
                probs, table = exec1(
                    params, stats, table, np.zeros((1,), np.int32),
                    fresh, jnp.asarray(x), jnp.asarray(sv),
                    jnp.asarray(valid),
                )
                np.asarray(probs)
                per_chunk[i] = min(
                    per_chunk[i], time.perf_counter() - t0
                )
                fresh = np.zeros((1,), np.float32)
        early = per_chunk[0] / chunk
        late = per_chunk[-1] / chunk
        records.append({
            **base,
            "metric": "streaming per-step latency vs session history "
                      "(O(1) session cache)",
            "arm": "stream-o1", "unit": "ms/step",
            "chunk_windows": chunk, "history_steps": stream_T,
            "per_step_ms_at_T%d" % chunk: round(1e3 * early, 4),
            "per_step_ms_at_T%d" % stream_T: round(1e3 * late, 4),
            "flatness_ratio": round(late / max(early, 1e-12), 3),
        })

        # -- the counterfactual: full-prefix recompute per new chunk
        recompute = {}
        for T in sorted({chunk, 64, stream_T}):
            xt = jnp.asarray(rng.normal(
                size=(1, T, comps, wlen)
            ).astype(np.float32))
            w1 = jnp.ones((1,), jnp.float32)
            fn = jax.jit(
                lambda p, s, xx, ww: eval_forward(task, p, s, xx, None, ww)
            )
            np.asarray(fn(params, stats, xt, w1))  # compile (one per T!)
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                np.asarray(fn(params, stats, xt, w1))
                best = min(best, time.perf_counter() - t0)
            recompute[str(T)] = {
                "full_ms": round(1e3 * best, 4),
                "per_step_ms": round(1e3 * best / T, 4),
            }
        records.append({
            **base,
            "metric": "full-sequence recompute cost vs prefix length "
                      "(what the session cache avoids)",
            "arm": "recompute", "unit": "ms", "per_T": recompute,
        })
    finally:
        eng.close()
    return records


def measure_fleet(replicas_list=(1, 2, 4), requests: int = 120,
                  swaps: int = 4, dims: dict | None = None,
                  devices=None, topology: dict | None = None):
    """The serving-fleet arms (r21, serving/fleet.py + publish.py):

    - ``fleet-scale`` (one record per replica count): the same mixed-bucket
      request storm against a ReplicaSet of 1 → 2 → 4 replicas (one engine
      per virtual device) — aggregate requests/s (the scale-out claim),
      per-replica request occupancy (the least-loaded router spreading
      work), and per-replica bucket hit-rate;
    - ``fleet-swap`` (at the largest replica count): K donated hot-swaps
      fired INTO live traffic — per-swap pause (max across replicas, the
      publish-window figure), its p99 across the K publishes, and the p99
      request latency of the swap-storm window vs the steady window before
      it (``LogHistogram.delta`` between merged-bus snapshots), plus the
      fleet-wide compiles-after-warmup count proving the guard held
      through every publish.

    ``devices``/``topology`` (r22): ``--slices S --pack K`` composes with
    the fleet arms — the emulated pod is sized to S slice-bands of K
    devices, replicas are pinned slice-major over the bands (replica i on
    band i % S, so replicas spread ACROSS slices before doubling up
    within one), and every record carries the active topology so a
    reader can tell a 4-replica/1-slice row from a 4-replica/4-slice
    one.
    """
    import jax
    import numpy as np

    from dinunet_implementations_tpu.serving.fleet import ReplicaSet
    from dinunet_implementations_tpu.telemetry.bus import MetricsBus

    cfg, task, params, stats, (windows, comps, wlen) = _serving_setup(dims)
    backend = jax.default_backend()
    base = {
        "unit": None, "backend": backend,
        "dims": dims or {"windows": windows, "comps": comps, "wlen": wlen,
                         "enc_out": ENC_OUT, "hidden": HIDDEN},
        "topology": topology or {
            "slices": 1,
            "devices": len(devices) if devices else len(jax.devices()),
        },
    }
    rng = np.random.default_rng(0)
    sizes = (1, 2, 3, 4, 8)

    def storm(fleet, n):
        t0 = time.perf_counter()
        futures = [
            fleet.submit(rng.normal(
                size=(sizes[i % len(sizes)], windows, comps, wlen)
            ).astype(np.float32))
            for i in range(n)
        ]
        for f in futures:
            f.result()
        return time.perf_counter() - t0

    records = []
    for n_replicas in replicas_list:
        bus = MetricsBus()
        fleet = ReplicaSet(
            cfg, replicas=n_replicas, params=params, batch_stats=stats,
            bus=bus, row_buckets=(1, 2, 4, 8), streaming=False,
            max_delay_ms=1.0, devices=devices,
        )
        fleet.warmup()
        try:
            elapsed = storm(fleet, requests)
            parts = [
                e.summary() for e in fleet._engines if e is not None
            ]
            records.append({
                **base,
                "metric": "fleet aggregate throughput / per-replica "
                          "occupancy vs replica count",
                "arm": "fleet-scale", "unit": "req/s",
                "replicas": n_replicas,
                "requests": requests,
                "requests_per_s": round(requests / elapsed, 2),
                "per_replica_requests": [p["requests"] for p in parts],
                "per_replica_bucket_hit_rate": [
                    p["bucket_hit_rate"] for p in parts
                ],
                "compiles_after_warmup": sum(
                    p["compiles_after_warmup"] for p in parts
                ),
            })
        finally:
            fleet.close()

    # -- hot-swap under load, at the largest fleet
    n_replicas = max(replicas_list)
    bus = MetricsBus()
    fleet = ReplicaSet(
        cfg, replicas=n_replicas, params=params, batch_stats=stats,
        bus=bus, row_buckets=(1, 2, 4, 8), streaming=False,
        max_delay_ms=1.0,
    )
    fleet.warmup()
    try:
        storm(fleet, requests)  # steady window
        steady = bus.merged_histogram("serving_request_latency_ms")
        pauses = []
        per_swap = max(requests // max(swaps, 1), len(sizes))
        for k in range(swaps):
            futures = [
                fleet.submit(rng.normal(
                    size=(sizes[i % len(sizes)], windows, comps, wlen)
                ).astype(np.float32))
                for i in range(per_swap)
            ]
            cand = jax.tree.map(
                lambda x, _k=k: np.asarray(x) + 1e-4 * (_k + 1), params
            )
            pauses.append(fleet.swap_params(cand, stats)["pause_ms"])
            for f in futures:
                f.result()
        swap_hist = bus.merged_histogram(
            "serving_request_latency_ms"
        ).delta(steady)
        fleet.assert_no_compiles()
        records.append({
            **base,
            "metric": "hot-swap pause and in-swap request latency vs "
                      "steady (donated publish under load)",
            "arm": "fleet-swap", "unit": "ms",
            "replicas": n_replicas, "swaps": swaps,
            "swap_pause_ms_p99": round(
                sorted(pauses)[max(int(0.99 * len(pauses)) - 1, 0)], 4
            ),
            "swap_pause_ms_max": round(max(pauses), 4),
            "steady_latency_ms_p99": steady.quantile(0.99),
            "in_swap_latency_ms_p99": swap_hist.quantile(0.99),
            "compiles_after_warmup": 0,  # assert_no_compiles passed
        })
    finally:
        fleet.close()
    return records


def measure_tenants(tenants: int = 2, pod_slices: int = 2,
                    epochs: int = 6, gap_s: float = 3.0):
    """The fleet-scheduler goodput arms (r22, runner/scheduler.py): K
    identical studies, each with a mid-study quorum gap (every site
    leaves after a staggered epoch mark and rejoins ``gap_s``
    wall-seconds later — the cohort-turnover shape real federations
    idle through), run two ways on the SAME emulated pod:

    - ``tenants-serialized``: one study at a time, each on its own
      scheduler — the pod idles through every gap (the status-quo cost
      of running studies back to back);
    - ``tenants-concurrent``: all K studies on ONE scheduler — weighted
      fair share packs them onto the pod, a holding tenant's slices are
      reclaimed via checkpoint-then-yield, and every gap is overlapped
      by the other tenants' training.

    Records aggregate samples/s, BOTH arms' slice-idle fraction, the
    preemption pause p99 (exit-clean checkpoint on yield + msgpack
    reload on resume) and the per-tenant fairness ratio (min/max busy
    slice-seconds per unit weight) — docs/bench_tenants_r22.jsonl.
    """
    import os
    import tempfile

    import jax
    import numpy as np

    from dinunet_implementations_tpu.core.config import (
        FSArgs, TrainConfig,
    )
    from dinunet_implementations_tpu.data.demo import make_fs_demo_tree
    from dinunet_implementations_tpu.runner.fed_runner import (
        discover_site_dirs,
    )
    from dinunet_implementations_tpu.runner.scheduler import (
        FleetScheduler, TenantSpec,
    )
    from dinunet_implementations_tpu.telemetry.bus import MetricsBus

    work = tempfile.mkdtemp(prefix="bench_tenants_")
    n_sites, subjects, feat = 4, 32, 8

    def spec_for(i: int) -> TenantSpec:
        tree = os.path.join(work, f"tree{i}")
        if not os.path.isdir(tree):
            make_fs_demo_tree(tree, n_sites=n_sites, subjects=subjects,
                              n_features=feat, seed=i)
        cfg = TrainConfig(
            task_id="FS-Classification", batch_size=4,
            staleness_bound=2, num_slices=pod_slices,
            fs_args=FSArgs(input_size=feat, hidden_sizes=(8,)),
        )
        return TenantSpec(
            tenant=f"study{i}", data_path=tree, config=cfg,
            capacity=n_sites, inventory_rows=subjects + 16,
            max_epochs=epochs,
        )

    def gap_after(i: int) -> int:
        # staggered gap marks: tenant i holds after a different epoch,
        # so the concurrent arm's gaps overlap training, not each other
        return max(1, (epochs // (tenants + 1)) * (i + 1))

    def seed_gap(sched, spec: TenantSpec) -> dict:
        t = sched.tenants[spec.tenant]
        dirs = discover_site_dirs(spec.data_path)
        g = gap_after(int(spec.tenant.removeprefix("study")))
        for j in range(len(dirs)):
            path = os.path.join(t.spool_dir, f"gap{j:03d}.json")
            tmp = path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump({"event": "leave", "site": f"local{j}",
                           "after_epoch": g}, fh)
            os.replace(tmp, path)
        # rejoin events must carry each site's config overrides
        # (labels_file / columns, from the tree's inputspec) exactly as
        # the pre-join admission recorded them — a bare join can't load
        # the site's covariates
        return {"tenant": spec.tenant, "t0": None, "rejoined": False,
                "rejoin": [
                    (f"local{j}", d,
                     dict(t.daemon._overrides.get(f"local{j}", {})))
                    for j, d in enumerate(dirs)
                ]}

    def drive(sched, gaps: list) -> float:
        t0 = time.monotonic()
        deadline = t0 + 600.0
        while not sched.done() and time.monotonic() < deadline:
            sched.tick()
            now = time.monotonic()
            for gap in gaps:
                t = sched.tenants[gap["tenant"]]
                if t.status != "active" or gap["rejoined"]:
                    continue
                if gap["t0"] is None and not t.daemon.trainable() \
                        and t.daemon.epochs_run >= 1:
                    gap["t0"] = now  # the hold was observed: clock it
                elif gap["t0"] is not None and now - gap["t0"] >= gap_s:
                    for j, (site, d, conf) in enumerate(gap["rejoin"]):
                        path = os.path.join(
                            t.spool_dir, f"zz_rejoin{j:03d}.json"
                        )
                        tmp = path + ".tmp"
                        with open(tmp, "w") as fh:
                            json.dump({"event": "join", "site": site,
                                       "data_dir": d, "config": conf},
                                      fh)
                        os.replace(tmp, path)
                    gap["rejoined"] = True
        return time.monotonic() - t0

    def samples_per_epoch(t) -> int:
        rows = t.daemon._rows or 10 ** 9
        return sum(
            min(len(v), rows) for v in t.daemon._data.values()
        )

    base = {
        "backend": jax.default_backend(), "tenants": tenants,
        "pod_slices": pod_slices, "epochs_per_study": epochs,
        "gap_s": gap_s, "unit": "samples/s",
        "metric": "aggregate training throughput: K gap-interrupted "
                  "studies serialized vs scheduled-concurrent on one "
                  "emulated pod",
    }
    records = []

    # -- serialized arm: one study at a time, pod idles through gaps
    ser_wall = ser_busy = ser_samples = 0.0
    ser_pauses: list = []
    for i in range(tenants):
        sched = FleetScheduler(
            os.path.join(work, f"solo{i}"), pod_slices=pod_slices,
            bus=MetricsBus(), poll_s=0.02, verbose=False,
        )
        spec = spec_for(i)
        sched.register(spec)
        gaps = [seed_gap(sched, spec)]
        wall = drive(sched, gaps)
        t = sched.tenants[spec.tenant]
        ser_samples += t.daemon.epochs_run * samples_per_epoch(t)
        ser_pauses.extend(t.pauses_ms)
        gp = sched.goodput()
        ser_wall += wall
        ser_busy += gp["busy_slice_s"]
        sched.close()
    ser_idle = round(1.0 - ser_busy / (pod_slices * ser_wall), 4)
    records.append({
        **base, "arm": "tenants-serialized",
        "wall_s": round(ser_wall, 3),
        "samples_per_s": round(ser_samples / ser_wall, 2),
        "slice_idle_fraction": ser_idle,
        "preempt_pause_ms_p99": (
            round(float(np.percentile(ser_pauses, 99)), 3)
            if ser_pauses else 0.0
        ),
    })

    # -- concurrent arm: all K studies on ONE scheduler
    sched = FleetScheduler(
        os.path.join(work, "packed"), pod_slices=pod_slices,
        bus=MetricsBus(), poll_s=0.02, verbose=False,
    )
    specs = [spec_for(i) for i in range(tenants)]
    gaps = []
    for spec in specs:
        sched.register(spec)
        gaps.append(seed_gap(sched, spec))
    conc_wall = drive(sched, gaps)
    conc_samples = sum(
        sched.tenants[s.tenant].daemon.epochs_run
        * samples_per_epoch(sched.tenants[s.tenant])
        for s in specs
    )
    conc_pauses = [
        p for s in specs for p in sched.tenants[s.tenant].pauses_ms
    ]
    gp = sched.goodput()
    per_tenant = [
        gp["busy_slice_s_per_tenant"][s.tenant] / max(s.weight, 1e-9)
        for s in specs
    ]
    fairness = (
        round(min(per_tenant) / max(per_tenant), 4)
        if min(per_tenant) > 0 else 0.0
    )
    sched.close()
    conc_rate = conc_samples / conc_wall
    records.append({
        **base, "arm": "tenants-concurrent",
        "wall_s": round(conc_wall, 3),
        "samples_per_s": round(conc_rate, 2),
        "slice_idle_fraction": round(
            1.0 - gp["busy_slice_s"] / (pod_slices * conc_wall), 4
        ),
        "preempt_pause_ms_p99": (
            round(float(np.percentile(conc_pauses, 99)), 3)
            if conc_pauses else 0.0
        ),
        "preempt_count": gp["preempt_count"],
        "fairness_ratio": fairness,
        "epochs": gp["epochs"],
        "speedup_vs_serialized": round(
            conc_rate / (ser_samples / ser_wall), 3
        ),
    })
    return records


SMALL_DIMS = dict(sites=32, steps=2, batch=4, windows=6, comps=8, wlen=4,
                  enc_out=16, hidden=16, compute_dtype="bfloat16")


def main():
    if "--sanitize" in sys.argv:
        # runtime sanitizer (dinunet_implementations_tpu/checks/sanitize.py):
        # compile-counter guard over the bench's epoch program — same env
        # contract as the trainer CLI: the explicit flag WINS over any
        # DINUNET_SANITIZE value left in the shell (incl. "0")
        import os

        os.environ["DINUNET_SANITIZE"] = "compile"
    if "--tenants" in sys.argv:
        # fleet-scheduler goodput arms (r22, runner/scheduler.py): K
        # gap-interrupted studies serialized vs scheduled-concurrent on
        # the same emulated pod (docs/bench_tenants_r22.jsonl; regen on
        # TPU with the same command, e.g. `--tenants 2`)
        tenants = int(sys.argv[sys.argv.index("--tenants") + 1])
        pod_slices = (int(sys.argv[sys.argv.index("--pod-slices") + 1])
                      if "--pod-slices" in sys.argv else 2)
        n = (int(sys.argv[sys.argv.index("--epochs") + 1])
             if "--epochs" in sys.argv else 6)
        gap_s = (float(sys.argv[sys.argv.index("--gap-s") + 1])
                 if "--gap-s" in sys.argv else 3.0)
        _ensure_host_devices(8)
        for rec in measure_tenants(
            tenants=tenants, pod_slices=pod_slices, epochs=n,
            gap_s=gap_s,
        ):
            print(json.dumps(rec), flush=True)
        return
    if "--serve" in sys.argv:
        # serving-path arms (r15, serving/): AOT warmup cold vs
        # compile-cache-warm, mixed-bucket request latency/throughput
        # through the continuous microbatcher, streaming per-step flatness
        # vs session history (the O(1) session-cache claim), and the
        # full-prefix recompute counterfactual. One JSON line per arm
        # (docs/bench_serving_r15.jsonl; regen on TPU with the same command).
        requests = (int(sys.argv[sys.argv.index("--requests") + 1])
                    if "--requests" in sys.argv else 100)
        stream_T = (int(sys.argv[sys.argv.index("--stream-t") + 1])
                    if "--stream-t" in sys.argv else 512)
        dims = SMALL_DIMS if "--small" in sys.argv else None
        if "--replicas" in sys.argv or "--swap" in sys.argv:
            # fleet arms (r21): `--serve --replicas 1,2,4 --swap 4` — the
            # ReplicaSet scale-out sweep plus hot-swaps under load
            # (docs/bench_fleet_r21.jsonl; regen on TPU, same command).
            # Replicas need distinct devices: size the virtual CPU mesh.
            replicas_list = tuple(
                int(r) for r in (
                    sys.argv[sys.argv.index("--replicas") + 1].split(",")
                    if "--replicas" in sys.argv else ("1", "2", "4")
                )
            )
            swaps = (int(sys.argv[sys.argv.index("--swap") + 1])
                     if "--swap" in sys.argv else 4)
            # --slices/--pack compose with the fleet arms (r22): the
            # emulated pod is S slice-bands of K devices and replicas
            # pin slice-major across the bands; every row records the
            # active topology (previously these flags were silently
            # ignored in the fleet branch)
            devices = topology = None
            if "--slices" in sys.argv:
                slices = int(sys.argv[sys.argv.index("--slices") + 1])
                pack = (int(sys.argv[sys.argv.index("--pack") + 1])
                        if "--pack" in sys.argv else 1)
                _ensure_host_devices(max(slices * pack,
                                         max(replicas_list)))
                import jax

                devs = jax.devices()[:slices * pack]
                bands = [devs[b * pack:(b + 1) * pack]
                         for b in range(slices)]
                devices = [bands[b][j] for j in range(pack)
                           for b in range(slices)]
                topology = {"slices": slices, "devices_per_slice": pack,
                            "placement": "slice-major"}
            else:
                _ensure_host_devices(max(replicas_list))
            for rec in measure_fleet(
                replicas_list=replicas_list, requests=requests,
                swaps=swaps, dims=dims, devices=devices,
                topology=topology,
            ):
                print(json.dumps(rec), flush=True)
            return
        for rec in measure_serving(
            requests=requests, dims=dims, stream_T=stream_T,
        ):
            print(json.dumps(rec), flush=True)
        return
    if "--slices" in sys.argv and "--sites" not in sys.argv:
        raise SystemExit(
            "--slices composes with the --sites sweep (e.g. "
            "`--sites 128,512 --slices 1,2,4`); give a site count to "
            "spread over the slices"
        )
    if "--sites" in sys.argv:
        # sites-scaling sweep: S virtual sites packed K per device on a real
        # site mesh (two-level aggregation, trainer/steps.py), one JSON line
        # per S — e.g. `--sites 8,32,128,512 --small` proves 512 sites train
        # on an 8-device virtual CPU mesh in one compiled program
        # (docs/bench_sites_scaling_r12.jsonl; regen on TPU with the same
        # command). `--pack auto` (default) packs every device; an explicit
        # comma list pins K per S. `--devices N` sizes the virtual CPU mesh
        # (ignored when a real accelerator platform is pinned).
        want = (int(sys.argv[sys.argv.index("--devices") + 1])
                if "--devices" in sys.argv else 8)
        _ensure_host_devices(want)
        sites_list = [
            int(s) for s in sys.argv[sys.argv.index("--sites") + 1].split(",")
        ]
        packs = None
        if "--pack" in sys.argv:
            raw = sys.argv[sys.argv.index("--pack") + 1]
            if raw != "auto":
                packs = [int(p) for p in raw.split(",")]
                if len(packs) == 1:
                    packs = packs * len(sites_list)
        obs = int(sys.argv[sys.argv.index("--obs") + 1]) if "--obs" in sys.argv else 3
        n = (int(sys.argv[sys.argv.index("--epochs") + 1])
             if "--epochs" in sys.argv else TIMED_EPOCHS)
        dims = SMALL_DIMS if "--small" in sys.argv else None
        engine_name = (sys.argv[sys.argv.index("--engine") + 1]
                       if "--engine" in sys.argv else "dSGD")
        # quantized wires compose with the packed sweep (r14): the sweep's
        # wire_bytes_per_device_round then records the codec-grid bytes —
        # the CI int8 packed smoke rides this path
        engine_kw = None
        if "--wire-quant" in sys.argv:
            wq = sys.argv[sys.argv.index("--wire-quant") + 1]
            if "," in wq:
                # the comma-list syntax belongs to the standalone A/B mode;
                # the composed sweep runs ONE codec per invocation
                raise SystemExit(
                    f"--sites composes with a single --wire-quant codec, "
                    f"got {wq!r} (run one sweep per codec)"
                )
            engine_kw = {"wire_quant": wq}
        # churn smoke composition (r13): `--faults` threads a liveness mask
        # (drops / delay_at stragglers) through the PACKED round, and
        # `--staleness N` switches it to the buffered-async aggregation —
        # one compiled program either way (asserted under --sanitize)
        plan = None
        if "--faults" in sys.argv:
            from dinunet_implementations_tpu.robustness import parse_fault_plan

            plan = parse_fault_plan(sys.argv[sys.argv.index("--faults") + 1])
        staleness = (int(sys.argv[sys.argv.index("--staleness") + 1])
                     if "--staleness" in sys.argv else 0)
        # hostile-site composition (r17): `--attacks` threads the byzantine
        # code mask through the packed round and `--robust-agg` switches the
        # engines to robust aggregation — the CI hostile smoke's path; the
        # CompileGuard asserts one compiled program for the attacked,
        # defended, packed chain
        attack = None
        if "--attacks" in sys.argv:
            from dinunet_implementations_tpu.robustness import (
                parse_attack_plan,
            )

            attack = parse_attack_plan(
                sys.argv[sys.argv.index("--attacks") + 1]
            )
        robust = (sys.argv[sys.argv.index("--robust-agg") + 1]
                  if "--robust-agg" in sys.argv else "none")
        # multi-slice composition (r18): `--slices 1,2,4` crosses each S
        # with the three-tier (slice, site) topology — records gain the
        # per-tier wire split (ici vs dcn bytes + codec shrink). The DCN
        # codec follows --wire-quant unless --dcn-wire-quant overrides it
        # (TrainConfig.dcn_wire_quant semantics). The CI multislice smoke
        # rides this path: --slices 2 --sites 64 --pack 8 --wire-quant int8.
        slices_list = None
        if "--slices" in sys.argv:
            slices_list = [
                int(s)
                for s in sys.argv[sys.argv.index("--slices") + 1].split(",")
            ]
        dcn_quant = (sys.argv[sys.argv.index("--dcn-wire-quant") + 1]
                     if "--dcn-wire-quant" in sys.argv else "")
        # privacy composition (r20): `--dp-noise SIGMA [--dp-clip C]`
        # threads in-scan DP-SGD through the packed round (records gain
        # the mechanism knobs + epsilon_final) and `--secure-agg MODE`
        # switches the engine to the masked fixed-point wire — the CI
        # privacy smoke's path, one compiled program under --sanitize
        epoch_kw = None
        if "--dp-noise" in sys.argv:
            epoch_kw = {
                "dp_noise_multiplier": float(
                    sys.argv[sys.argv.index("--dp-noise") + 1]
                ),
                "dp_clip": (
                    float(sys.argv[sys.argv.index("--dp-clip") + 1])
                    if "--dp-clip" in sys.argv else 1.0
                ),
            }
        if "--secure-agg" in sys.argv:
            engine_kw = {
                **(engine_kw or {}),
                "secure_agg": sys.argv[sys.argv.index("--secure-agg") + 1],
            }
        for rec in measure_sites_scaling(
            sites_list, packs=packs, obs=obs, n=n, dims=dims,
            engine_name=engine_name, engine_kw=engine_kw, fault_plan=plan,
            staleness_bound=staleness, attack_plan=attack,
            robust_agg=robust, slices_list=slices_list, dcn_quant=dcn_quant,
            epoch_kw=epoch_kw,
        ):
            print(json.dumps(rec), flush=True)
        return
    baseline = CPU_BASELINE_SAMPLES_PER_SEC
    if "--live-baseline" in sys.argv:
        try:
            baseline = measure_cpu_baseline()
        except Exception:
            pass
    if "--ab-rankdad" in sys.argv:
        # paired interleaved A/B of the rankDAD levers, one JSON line per
        # arm (≥5 observations each; see docs/bench_rankdad_ab_r6.jsonl).
        # --small shrinks the model to harness-validation dims (records the
        # dims + backend so the artifact cannot be mistaken for a TPU
        # flagship number).
        obs = int(sys.argv[sys.argv.index("--obs") + 1]) if "--obs" in sys.argv else 5
        n = (int(sys.argv[sys.argv.index("--epochs") + 1])
             if "--epochs" in sys.argv else TIMED_EPOCHS)
        dims = SMALL_DIMS if "--small" in sys.argv else None
        for rec in measure_rankdad_ab(obs=obs, n=n, dims=dims):
            print(json.dumps(rec), flush=True)
        return
    if "--wire-quant" in sys.argv:
        # quantized-wire A/B (r14): the listed codecs (comma list from
        # {bf16,int8,fp8}) against the f32 wire, paired interleaved; each
        # record carries the MODELED per-device wire bytes + shrink-vs-f32
        # that checks/semantic.py S002 proves against the traced program.
        # (With --sites this flag instead threads the codec into the packed
        # sweep — handled above.)
        quants = [
            q for q in
            sys.argv[sys.argv.index("--wire-quant") + 1].split(",") if q
        ]
        obs = int(sys.argv[sys.argv.index("--obs") + 1]) if "--obs" in sys.argv else 5
        n = (int(sys.argv[sys.argv.index("--epochs") + 1])
             if "--epochs" in sys.argv else TIMED_EPOCHS)
        dims = SMALL_DIMS if "--small" in sys.argv else None
        engine_name = (sys.argv[sys.argv.index("--engine") + 1]
                       if "--engine" in sys.argv else "dSGD")
        for rec in measure_wirequant_ab(
            quants, obs=obs, n=n, dims=dims, engine_name=engine_name
        ):
            print(json.dumps(rec), flush=True)
        return
    if "--pipeline" in sys.argv:
        # input-pipeline A/B: host (dense per-epoch transfer, the legacy
        # trainer path) vs device (resident inventory + per-epoch index
        # plan + donated state). `--pipeline ab` interleaves both arms;
        # a single arm name runs just that arm (the CI CPU smoke uses
        # `--pipeline device --small --sanitize` to exercise the device
        # path + donation under the CompileGuard on every PR).
        mode = sys.argv[sys.argv.index("--pipeline") + 1]
        if mode not in ("host", "device", "ab"):
            raise SystemExit(f"--pipeline expects host|device|ab, got {mode!r}")
        obs = int(sys.argv[sys.argv.index("--obs") + 1]) if "--obs" in sys.argv else 5
        n = (int(sys.argv[sys.argv.index("--epochs") + 1])
             if "--epochs" in sys.argv else TIMED_EPOCHS)
        dims = SMALL_DIMS if "--small" in sys.argv else None
        for rec in measure_pipeline_ab(
            mode=mode, obs=obs, n=n, dims=dims,
            donate="--no-donate" not in sys.argv,
        ):
            print(json.dumps(rec), flush=True)
        return
    if "--dp-noise" in sys.argv or "--secure-agg" in sys.argv:
        if "--attacks" in sys.argv:
            # without this guard the privacy branch would return before the
            # attacks branch and the plan would be silently dropped
            raise SystemExit(
                "--dp-noise/--secure-agg and --attacks are separate "
                "standalone A/B modes; compose them through the packed "
                "sweep instead (--sites ... --attacks ... --dp-noise ...) "
                "or run two invocations"
            )
        # privacy-plane A/B (r20): clean vs dp vs dp+secureagg paired
        # interleaved arms — throughput (the clip/noise + masked-wire
        # cost) plus the accountant's epsilon_final for the timed chain,
        # one JSON line per arm (docs/bench_privacy_ab_r20.jsonl; regen on
        # TPU with the same command). --secure-agg alone runs the
        # clean-vs-masked pair. (With --sites these flags instead thread
        # into the packed sweep — handled above.)
        sigma = (float(sys.argv[sys.argv.index("--dp-noise") + 1])
                 if "--dp-noise" in sys.argv else 0.0)
        clip = (float(sys.argv[sys.argv.index("--dp-clip") + 1])
                if "--dp-clip" in sys.argv else 1.0)
        obs = int(sys.argv[sys.argv.index("--obs") + 1]) if "--obs" in sys.argv else 5
        n = (int(sys.argv[sys.argv.index("--epochs") + 1])
             if "--epochs" in sys.argv else TIMED_EPOCHS)
        dims = SMALL_DIMS if "--small" in sys.argv else None
        mode = (sys.argv[sys.argv.index("--secure-agg") + 1]
                if "--secure-agg" in sys.argv else "off")
        for rec in measure_privacy_ab(
            dp_noise=sigma, dp_clip=clip,
            secure_mode=mode, obs=obs, n=n, dims=dims,
        ):
            print(json.dumps(rec), flush=True)
        return
    if "--attacks" in sys.argv:
        # hostile-site A/B (r17): clean vs attacked-undefended vs
        # attacked-defended paired interleaved arms — throughput (the robust
        # reducers' gather/compute overhead) plus the final-epoch-loss
        # quality signal, one JSON line per arm
        # (docs/bench_attacks_ab_r17.jsonl; regen on TPU with the same
        # command). --robust-agg picks the defense (default trimmed_mean).
        from dinunet_implementations_tpu.robustness import parse_attack_plan

        plan = parse_attack_plan(sys.argv[sys.argv.index("--attacks") + 1])
        robust = (sys.argv[sys.argv.index("--robust-agg") + 1]
                  if "--robust-agg" in sys.argv else "trimmed_mean")
        obs = int(sys.argv[sys.argv.index("--obs") + 1]) if "--obs" in sys.argv else 5
        n = (int(sys.argv[sys.argv.index("--epochs") + 1])
             if "--epochs" in sys.argv else TIMED_EPOCHS)
        dims = SMALL_DIMS if "--small" in sys.argv else None
        engine_name = (sys.argv[sys.argv.index("--engine") + 1]
                       if "--engine" in sys.argv else "dSGD")
        for rec in measure_attacks_ab(
            plan, robust=robust, obs=obs, n=n, dims=dims,
            engine_name=engine_name,
        ):
            print(json.dumps(rec), flush=True)
        return
    if "--faults" in sys.argv:
        # fault-masked federated round throughput: same flagship epoch with a
        # FaultPlan's liveness mask threaded through the engines (the masking
        # overhead is the claim under test — the program is identical for any
        # mask, so one measurement covers every fault pattern of this shape)
        from dinunet_implementations_tpu.robustness import parse_fault_plan

        plan = parse_fault_plan(sys.argv[sys.argv.index("--faults") + 1])
        dims = SMALL_DIMS if "--small" in sys.argv else None
        value, stats = measure_tpu(with_distribution=True, fault_plan=plan,
                                   dims=dims)
        sites = (dims or {}).get("sites", NUM_SITES)
        live = plan.liveness(sites, 0, (dims or {}).get("steps", STEPS_PER_EPOCH))
        rec = {
            "metric": "samples/sec/chip (ICA-LSTM federated round, fault-masked)",
            "value": value,
            "unit": "samples/sec/chip",
            "samples_per_sec": stats,
            "faults": plan.to_json(),
            "dead_site_rounds": int((live == 0).sum()),
        }
        if dims:
            # --small: record the dims, omit vs_baseline — the CPU baseline
            # is the FLAGSHIP config's, and a toy-dims ratio would masquerade
            # as a real number (same policy as --ab-rankdad)
            rec["dims"] = dims
        elif value is not None:
            rec["vs_baseline"] = round(value / baseline, 2)
        print(json.dumps(rec))
        return
    value, stats = measure_tpu(with_distribution=True)
    rec = {
        "metric": "samples/sec/chip (ICA-LSTM, 32 sites, full federated round)",
        "value": value,
        "unit": "samples/sec/chip",
        "samples_per_sec": stats,  # min/median/spread over the N observations
    }
    if value is not None:
        rec["vs_baseline"] = round(value / baseline, 2)
        rec["mfu"] = round(value * flops_per_sample() / V5E_BF16_PEAK_FLOPS, 4)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
