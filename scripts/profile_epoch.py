"""Per-op device-time profile + per-engine cost attribution of the flagship
bench epoch.

Two modes:

1. **Trace** (default): captures a ``jax.profiler`` trace of the 32-site
   ICA-LSTM federated epoch (the bench.py configuration) and prints the top
   device ops by total duration — the tool that found the conv-emitter dW_hh
   lowering, the whole-input relayout copy, and the lane-misaligned BiLSTM
   concat in round 3. ``--engine rankDAD|powerSGD|dSGD`` traces that engine's
   epoch (default dSGD).

2. **Attribution** (``--attribution``): per-engine cost attribution of the
   rankDAD round — compression (power iteration) vs gather vs reconstruction
   — via DIFFERENTIAL epochs rather than trace-name classification (XLA
   fusions don't carry phase names; epoch differentials need nothing from
   the backend but a clock):

   - ``dsgd``                 = model grads + optimizer only (the floor);
   - ``exchange-only``        = a stub engine whose factors are canonical
     basis columns (zero power iterations) — pays the packed factor
     all-gather + einsum reconstruction + one GᵀP matmul;
   - ``rankdad-cold-1iter`` / ``-5iter`` (``dad_tol=0`` forces full trips)
     — the slope gives the per-power-iteration cost;
   - ``rankdad-warm-default`` — warm-started Ω with the stock tol, i.e.
     what the engine actually costs after round one.

   Phase costs are differences of interleaved-A/B marginals
   (``bench.interleaved_ab``), printed as JSON lines next to the ANALYTIC
   FLOP/byte count of each phase (exact, from the model's leaf shapes) — the
   "is the residual overhead irreducible compression FLOPs?" receipt.

Usage: python scripts/profile_epoch.py [--aot] [--epochs N] [--engine E]
       python scripts/profile_epoch.py --attribution [--small] [--obs N]
                                       [--epochs N]
  --aot    also apply compile_epoch_aot (the bench's resident-input layout)
  --small  harness-validation dims (CPU-friendly); records dims + backend
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

import bench
from dinunet_implementations_tpu.engines import make_engine
from dinunet_implementations_tpu.telemetry.xprof import (
    capture,
    summarize_device_ops,
    trace_files,
)
from dinunet_implementations_tpu.engines.base import Engine, register_engine
from dinunet_implementations_tpu.engines.lowrank import (
    from_matrix,
    is_compressible,
    to_matrix,
)
from dinunet_implementations_tpu.models import ICALstm
from dinunet_implementations_tpu.trainer import (
    FederatedTask,
    compile_epoch_aot,
    init_train_state,
    make_optimizer,
    make_train_epoch_fn,
)

TRACE_DIR = "/tmp/dinunet_epoch_trace"

ENGINE_KW = {
    "dSGD": {},
    "rankDAD": dict(dad_reduction_rank=10, dad_num_pow_iters=5, dad_tol=1e-3),
    "powerSGD": dict(dad_reduction_rank=10),
}


@register_engine("rankDAD-exchange-only")
def make_rankdad_exchange_only(
    dad_reduction_rank: int = 10, precision_bits="32", **_unused
) -> Engine:
    """rankDAD with the power iteration stubbed out: P = the first r columns
    of the identity, Q = GᵀP. Pays the packed factor gather, the einsum
    reconstruction, and ONE GᵀP matmul (the real engine's final-Q product) —
    so ``T(rankDAD) − T(this)`` isolates the power-iteration (compression)
    cost, and ``T(this) − T(dSGD)`` bounds gather+reconstruction. Attribution
    arm only; its "aggregate" is numerically meaningless. The grouping /
    dense-psum / packed-gather / einsum body deliberately MIRRORS
    engines/rankdad.py's exchange — keep the two in sync or the differential
    stops isolating the power iteration."""
    from dinunet_implementations_tpu.parallel.collectives import (
        payload_dtype,
        site_all_gather_packed,
        site_weight_scale,
    )

    pdtype = payload_dtype(precision_bits)

    def init(grads):
        return {}

    def aggregate(grads, state, weight, axis_name, live=None):
        from dinunet_implementations_tpu.engines.base import mask_dead_site

        # same liveness contract as the real engines (trainer/steps.py passes
        # live= unconditionally)
        grads, weight = mask_dead_site(grads, weight, live)
        scale = site_weight_scale(weight, axis_name)
        leaves, treedef = jax.tree.flatten(grads)
        out: list = [None] * len(leaves)
        groups: dict = {}
        for i, g in enumerate(leaves):
            if is_compressible(g):
                m, n = to_matrix(g).shape
                groups.setdefault(min(dad_reduction_rank, m, n), []).append(i)
            else:
                out[i] = jax.lax.psum(
                    g.astype(jnp.float32) * scale, axis_name
                ).astype(g.dtype)
        # one packed gather per rank class, exactly like the real engine
        for r, idxs in sorted(groups.items()):
            parts = []
            for i in idxs:
                G = to_matrix(leaves[i]).astype(jnp.float32)
                P = jnp.eye(G.shape[0], r, dtype=jnp.float32)
                parts.append(P.astype(pdtype))
                parts.append((G.T @ P * scale).astype(pdtype))
            gathered = site_all_gather_packed(parts, axis_name)
            for k, i in enumerate(idxs):
                G_hat = jnp.einsum(
                    "smr,snr->mn",
                    gathered[2 * k].astype(jnp.float32),
                    gathered[2 * k + 1].astype(jnp.float32),
                )
                out[i] = from_matrix(G_hat, leaves[i])
        return jax.tree.unflatten(treedef, out), state

    return Engine("rankDAD-exchange-only", init, aggregate)


def _compressible_shapes(dims=None):
    """(m, n, r) for every compressible leaf of the flagship (or --small)
    model — the basis of the analytic phase FLOP counts."""
    d = dict(windows=bench.WINDOWS, comps=bench.COMPS, wlen=bench.WLEN,
             enc_out=bench.ENC_OUT, hidden=bench.HIDDEN, batch=4)
    d.update(dims or {})
    model = ICALstm(input_size=d["enc_out"], hidden_size=d["hidden"],
                    num_comps=d["comps"], window_size=d["wlen"], num_cls=2)
    x = jnp.ones((2, d["windows"], d["comps"], d["wlen"]), jnp.float32)
    task = FederatedTask(model)
    params, _ = task.init_variables(jax.random.PRNGKey(0), x)
    shapes = []
    for g in jax.tree.leaves(params):
        if is_compressible(g):
            m, n = to_matrix(g).shape
            shapes.append((m, n, min(10, m, n)))
    return shapes


def analytic_phase_costs(dims, sites: int) -> dict:
    """Exact matmul FLOPs / wire bytes per federated ROUND per site for each
    rankDAD phase (2 FLOPs per MAC), from the leaf shapes."""
    shapes = _compressible_shapes(dims)
    per_iter = sum(4 * m * n * r for m, n, r in shapes)      # GᵀP + G(GᵀP)
    init_final = sum(4 * m * n * r for m, n, r in shapes)    # G@Ω + final GᵀP
    recon = sum(2 * sites * m * n * r for m, n, r in shapes)  # einsum over S
    gather_bytes = sum(4 * r * (m + n) for m, n, r in shapes)  # f32 payload
    return {
        "compressible_leaves": len(shapes),
        "power_iter_flops_per_iter_per_site": per_iter,
        "compression_fixed_flops_per_site": init_final,
        "reconstruction_flops_per_site": recon,
        "gather_bytes_per_site_f32": gather_bytes,
    }


def attribution(argv):
    obs = int(argv[argv.index("--obs") + 1]) if "--obs" in argv else 3
    small = "--small" in argv
    n = int(argv[argv.index("--epochs") + 1]) if "--epochs" in argv else (
        8 if small else 32
    )
    dims = dict(bench.SMALL_DIMS) if small else None
    dad = ENGINE_KW["rankDAD"]
    arms = {
        "dsgd": ("dSGD", {}),
        "exchange-only": ("rankDAD-exchange-only", dict(dad_reduction_rank=10)),
        "rankdad-cold-1iter": ("rankDAD", dict(
            dad, dad_num_pow_iters=1, dad_tol=0.0, dad_warm_start=False)),
        "rankdad-cold-5iter": ("rankDAD", dict(
            dad, dad_num_pow_iters=5, dad_tol=0.0, dad_warm_start=False)),
        "rankdad-warm-default": ("rankDAD", dict(dad, dad_warm_start=True)),
    }
    chains, samples = {}, None
    for arm, (engine, kw) in arms.items():
        chains[arm], samples = bench._setup_epoch(engine, kw, dims=dims)
        chains[arm](1)  # compile before any timing
    dists = bench.interleaved_ab(chains, n, obs=obs)
    marg = {k: v["marginal_seconds_per_epoch"] for k, v in dists.items()}
    sites = (dims or {}).get("sites", bench.NUM_SITES)
    rounds = (dims or {}).get("steps", bench.STEPS_PER_EPOCH)
    base = {
        "metric": "rankDAD per-phase cost attribution (differential epochs)",
        "backend": jax.default_backend(),
        "sites": sites,
        "rounds_per_epoch": rounds,
        "observations_per_arm": obs,
        "chain_epochs": n,
    }
    if dims:
        base["dims"] = dims
    full = marg["rankdad-cold-5iter"]
    phases = [
        ("model+optimizer (dSGD floor)", marg["dsgd"]),
        ("gather+reconstruction (exchange-only − dsgd)",
         marg["exchange-only"] - marg["dsgd"]),
        ("power-iteration, 5 cold trips (cold-5iter − exchange-only)",
         marg["rankdad-cold-5iter"] - marg["exchange-only"]),
        ("power-iteration, per trip ((cold-5iter − cold-1iter)/4)",
         (marg["rankdad-cold-5iter"] - marg["rankdad-cold-1iter"]) / 4),
        ("compression with warm-started Ω (warm-default − exchange-only)",
         marg["rankdad-warm-default"] - marg["exchange-only"]),
    ]
    for arm, dist in dists.items():
        print(json.dumps({
            **base, "kind": "arm", "arm": arm,
            "engine": arms[arm][0], "engine_kw": arms[arm][1],
            "samples_per_sec": bench.throughput_stats(dist, samples),
        }), flush=True)
    for name, sec in phases:
        print(json.dumps({
            **base, "kind": "phase", "phase": name,
            "seconds_per_epoch": round(sec, 6),
            "seconds_per_round": round(sec / rounds, 6),
            "fraction_of_cold_rankdad_epoch": round(sec / full, 4),
        }), flush=True)
    print(json.dumps({
        **base, "kind": "analytic",
        **analytic_phase_costs(dims, sites),
        "model_train_flops_per_sample": round(bench.flops_per_sample_dims(
            (dims or {}).get("windows", bench.WINDOWS),
            (dims or {}).get("comps", bench.COMPS)
            * (dims or {}).get("wlen", bench.WLEN),
            (dims or {}).get("enc_out", bench.ENC_OUT),
            (dims or {}).get("hidden", bench.HIDDEN),
        )),
    }), flush=True)


def main():
    if "--attribution" in sys.argv:
        attribution(sys.argv)
        return
    epochs = 10
    if "--epochs" in sys.argv:
        epochs = int(sys.argv[sys.argv.index("--epochs") + 1])
    engine_name = (sys.argv[sys.argv.index("--engine") + 1]
                   if "--engine" in sys.argv else "dSGD")
    S, steps, B = bench.NUM_SITES, bench.STEPS_PER_EPOCH, bench.BATCH_PER_SITE
    W, C, WL = bench.WINDOWS, bench.COMPS, bench.WLEN
    model = ICALstm(input_size=bench.ENC_OUT, hidden_size=bench.HIDDEN,
                    num_comps=C, window_size=WL, num_cls=2,
                    compute_dtype="bfloat16")
    task = FederatedTask(model)
    engine = make_engine(engine_name, **ENGINE_KW.get(engine_name, {}))
    opt = make_optimizer("adam", 1e-3)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(S, steps, B, W, C, WL)).astype(np.float32),
                    dtype=jnp.bfloat16)
    y = jnp.asarray((rng.random((S, steps, B)) > 0.5).astype(np.int32))
    w = jnp.ones((S, steps, B), jnp.float32)
    state0 = init_train_state(task, engine, opt, jax.random.PRNGKey(0),
                              x[0, 0], num_sites=S)
    epoch_fn = make_train_epoch_fn(task, engine, opt, mesh=None,
                                   local_iterations=1)
    if "--aot" in sys.argv:
        epoch_fn, put_x = compile_epoch_aot(epoch_fn, state0, x, y, w)
        x = put_x(x)

    s = state0
    for _ in range(2):
        s, _ = epoch_fn(s, x, y, w)
    jax.tree.map(np.asarray, s)

    # capture + summarize via telemetry/xprof.py — this script is a thin
    # consumer of the tracer layer, not an owner of trace-parsing code
    with capture(TRACE_DIR, fresh=True):
        s = state0
        for _ in range(epochs):
            s, _ = epoch_fn(s, x, y, w)
        jax.tree.map(np.asarray, s)

    print(f"top 25 device ops for {engine_name} "
          f"(us over {epochs} epochs; trace: {trace_files(TRACE_DIR)[0]})")
    for rec in summarize_device_ops(TRACE_DIR, top=25):
        print(f"{rec['total_us']:10.0f}  x{rec['count']:4d}  "
              f"{rec['name'][:80]}")


if __name__ == "__main__":
    main()
