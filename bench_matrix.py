"""Bench matrix: every BASELINE.json target config, one JSON line each.

Measures the full federated training round (per-site grad → engine
aggregation → Adam) for the five driver-specified configs:

1. FreeSurfer MLP, 2-site dSGD            (reference headline workload)
2. ICA-LSTM, 4-site dSGD
3. ICA-LSTM, 32-site rankDAD              (low-rank compression on ICI)
4. 3D-CNN sMRI, 8-site dSGD               (TPU-build extension)
5. Multimodal FS+ICA transformer, 64-site (TPU-build extension)

All sites fold onto the local chip via the vmapped site axis. Measurement
uses the honest lazy-backend recipe from bench.py: chain N epochs, fully
materialize the final state, report the marginal epoch cost.

Usage: python bench_matrix.py [--epochs N]
"""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from bench import chain_epochs, marginal_distribution, throughput_stats

from dinunet_implementations_tpu.engines import make_engine
from dinunet_implementations_tpu.models import (
    ICALstm,
    MSANNet,
    MultimodalNet,
    SMRI3DNet,
)
from dinunet_implementations_tpu.trainer import (
    FederatedTask,
    compile_epoch_aot,
    init_train_state,
    make_optimizer,
    make_train_epoch_fn,
)

TIMED_EPOCHS = 16
STEPS = 2

V5E_BF16_PEAK_FLOPS = 197e12


# --- per-config matmul-FLOP models (fwd ≈ listed matmuls; train ≈ 3× fwd
# for fwd+bwd). MFU = samples/sec × FLOPs/sample / v5e bf16 peak; the
# fs-mlp config streams f32, so its mfu reads low against the bf16 peak by
# construction (stated rather than rescaled).


def mlp_flops_per_sample(dims=(66, 256, 128, 64, 32, 2)) -> float:
    return 3.0 * sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))


def ica_flops_per_sample() -> float:
    from bench import flops_per_sample

    return flops_per_sample()


def smri_flops_per_sample(channels=(16, 32, 64, 128)) -> float:
    """space_to_depth path: 64³×1 → 32³×8, then four stride-2 3³ convs."""
    f, vox, cin = 0.0, 16**3, 8  # conv_0 output grid is 16³
    for c in channels:
        f += 2 * vox * 27 * cin * c
        cin, vox = c, vox // 8
    return 3.0 * f


def multimodal_flops_per_sample(
    T=100, E=256, L=4, mlp_ratio=4, enc_in=1000, n_ica=98, fs_in=66
) -> float:
    """1 CLS + 1 FS token + 98 ICA tokens through 4 pre-LN blocks."""
    per_tok = (2 * 3 * E * E) + (2 * E * E) + (2 * 2 * mlp_ratio * E * E)
    attn_per_tok = 4 * T * E  # logits + weighted sum over T keys
    embed = n_ica * 2 * enc_in * E + 2 * fs_in * E
    return 3.0 * (L * T * (per_tok + attn_per_tok) + embed)


def measure(name, model, x_shape, sites, engine_name, batch, engine_kw=None,
            timed_epochs=TIMED_EPOCHS, flops_sample=None):
    rng = np.random.default_rng(0)
    task = FederatedTask(model)
    engine = make_engine(engine_name, **(engine_kw or {}))
    opt = make_optimizer("adam", 1e-3)
    # inputs pre-cast to the model's compute dtype, as bench.py / the trainer
    x = jnp.asarray(
        rng.normal(size=(sites, STEPS, batch) + x_shape).astype(np.float32),
        dtype=getattr(model, "compute_dtype", None),
    )
    y = jnp.asarray((rng.random((sites, STEPS, batch)) > 0.5).astype(np.int32))
    w = jnp.ones((sites, STEPS, batch), jnp.float32)
    state0 = init_train_state(
        task, engine, opt, jax.random.PRNGKey(0), x[0, 0], num_sites=sites
    )
    epoch_fn = make_train_epoch_fn(task, engine, opt, mesh=None, local_iterations=1)
    # resident inputs in the executable's preferred layout, as bench.py
    epoch_fn, put_x = compile_epoch_aot(epoch_fn, state0, x, y, w)
    x = put_x(x)

    def run(n):
        return chain_epochs(epoch_fn, state0, x, y, w, n)

    run(1)
    # adaptive: grow N until the marginal compute dominates the fixed cost
    # of ending a chain (the host fetch), else fast configs read as noise
    t1 = min(run(1) for _ in range(2))
    n = max(timed_epochs, 4)
    while True:
        tN = run(n + 1)
        d = tN - t1
        if d > 1.5 or n >= 2048:
            break
        n *= 4
    record = {
        "config": name,
        "engine": engine_name,
        "sites": sites,
        "metric": "samples/sec/chip (full federated round)",
        "unit": "samples/sec/chip",
    }
    if engine_kw:
        record["engine_kw"] = engine_kw
    if d <= 0.2:
        # marginal time is inside the latency jitter even at the epoch cap —
        # refuse to print an inflated number (the failure mode this bench
        # methodology exists to eliminate)
        record.update(value=None, unreliable=True, marginal_seconds=round(d, 4))
    else:
        # final measurement: N paired (half, full) observations at the
        # calibrated chain length → least-contended headline + min/median/
        # spread distribution (bench.py marginal_distribution). The
        # calibration's full chain feeds the HEADLINE's endpoint minimum only
        # (valid for a min estimator; saves one chain) — pairing it with a
        # half chain run minutes later would mix contention windows inside
        # one "paired" observation.
        pairs = [(run(n // 2 + 1), run(n + 1)) for _ in range(3)]
        dist = marginal_distribution(pairs, n, pre_full=tN)
        dt = dist["marginal_seconds_per_epoch"]
        # the reliability gate must judge the estimate actually reported,
        # not the discarded calibration delta
        if dt * (n - n // 2) <= 0.2:
            record.update(
                value=None, unreliable=True,
                marginal_seconds=round(dt * (n - n // 2), 4),
            )
        else:
            stats = throughput_stats(dist, sites * STEPS * batch)
            record["value"] = stats["value"]
            record["samples_per_sec"] = stats
            if flops_sample and record["value"] is not None:
                record["mfu"] = round(
                    record["value"] * flops_sample / V5E_BF16_PEAK_FLOPS, 4
                )
                record["flops_per_sample"] = round(flops_sample)
    print(json.dumps(record), flush=True)
    return record.get("value")


def main():
    epochs = TIMED_EPOCHS
    if "--epochs" in sys.argv:
        epochs = int(sys.argv[sys.argv.index("--epochs") + 1])

    if "--sites" in sys.argv:
        # sites-scaling sweep at the flagship ICA dims (or --small): the
        # packed-mesh arm from bench.py, so the matrix and the headline
        # bench share one measurement path. JSON records sites /
        # sites_per_chip / pack_factor per line.
        from bench import SMALL_DIMS, _ensure_host_devices, measure_sites_scaling

        # jax is imported above but its backend initializes lazily — setting
        # the device-count flags here is still early enough
        _ensure_host_devices(
            int(sys.argv[sys.argv.index("--devices") + 1])
            if "--devices" in sys.argv else 8
        )
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
        for rec in measure_sites_scaling(
            sites_list, packs=packs, n=epochs,
            dims=SMALL_DIMS if "--small" in sys.argv else None,
        ):
            print(json.dumps(rec), flush=True)
        return

    dad = dict(dad_reduction_rank=10, dad_num_pow_iters=5, dad_tol=1e-3)

    # 1. FS MLP 2-site dSGD (compspec defaults: 66 → (256,128,64,32) → 2)
    measure("fs-mlp-2site", MSANNet(), (66,), 2, "dSGD", 16,
            timed_epochs=epochs, flops_sample=mlp_flops_per_sample())
    # 2. ICA-LSTM 4-site dSGD (HCP shape)
    ica = ICALstm(input_size=256, hidden_size=348, num_comps=100,
                  window_size=10, num_cls=2, compute_dtype="bfloat16")
    measure("ica-lstm-4site", ica, (98, 100, 10), 4, "dSGD", 16,
            timed_epochs=epochs, flops_sample=ica_flops_per_sample())
    # 3. ICA-LSTM 32-site rankDAD
    measure("ica-lstm-32site-rankdad", ica, (98, 100, 10), 32, "rankDAD", 16,
            engine_kw=dad, timed_epochs=epochs,
            flops_sample=ica_flops_per_sample())
    # 4. 3D-CNN sMRI 8-site dSGD (64³ T1w volumes; space-to-depth folded in
    #    the DATA PIPELINE as the runner does — pre-folded 32³×8 inputs, the
    #    model runs space_to_depth=False with identical params. Measured
    #    2.0–2.6× over the in-model per-step fold (r5,
    #    docs/bench_smri_s2d_ab_r5.jsonl); that fold itself was 3.7–6.9×
    #    over the naive single-channel layout (r3).
    measure("smri-3dcnn-8site",
            SMRI3DNet(num_cls=2, compute_dtype="bfloat16", space_to_depth=False),
            (32, 32, 32, 8), 8, "dSGD", 4, timed_epochs=max(epochs // 2, 2),
            flops_sample=smri_flops_per_sample())
    # 5. Multimodal transformer 64-site dSGD (fs 66 + 98 ICA windows of
    #    1000). bf16 like the other heavy configs: paired A/B measured
    #    1.8× over the f32 stream (docs/bench_mm_bf16_ab_r5.jsonl) —
    #    accuracy tracking pinned by tests/test_extensions.py
    #    (test_multimodal_bf16_tracks_f32).
    mm = MultimodalNet(fs_input_size=66, num_comps=100, window_size=10,
                       compute_dtype="bfloat16")
    measure("multimodal-64site", mm, (66 + 98 * 1000,), 64, "dSGD", 8,
            timed_epochs=max(epochs // 2, 2),
            flops_sample=multimodal_flops_per_sample())


if __name__ == "__main__":
    main()
