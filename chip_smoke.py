"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of the flagship model (ICA-LSTM, HCP inputspec: 100 components ×
980 timepoints in 98 windows, encoder 256, hidden 348, bidirectional, bf16),
with depth cut (32 sites × ~48 subjects, a few epochs) and random data and
weights made from a seed:

1. ``kernel/parity``   the Pallas LSTM kernel against the ``lax.scan`` path
                       of the same model, forward and gradient;
2. ``train/dSGD``      ``dinunet-tpu`` CLI → FedRunner → FederatedTrainer at
                       TrainConfig defaults, 3 epochs;
3. ``train/rankDAD``   the same at the rankDAD engine's defaults, 2 epochs;
4. ``serve``           ``python -m dinunet_implementations_tpu.serving`` on
                       the dSGD checkpoint, 50 requests;
5. ``train/dSGD-mesh`` with >= 4 devices: dSGD again, 8 sites a device on a
                       4-device site mesh.

Every phase calls the entry point's own ``main(argv)`` in THIS process (a
chip belongs to one process) and then checks what the run left on disk: the
telemetry manifest and metrics, the lowered epoch program. Any failed check
or exception ends the run non-zero. On success the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

``python chip_smoke.py`` refuses to run unless jax reports a TPU; the phase
functions take the dims and the expected platform so that
``tests/test_chip_smoke.py`` can drive them at toy size on the CPU.

Compile cache: ``JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache``
beside this file (core/jaxcompat.py). The fixture goes to
``chip_smoke_data/``, outputs and the report to ``chip_smoke_out/``; all
three are in ``.gitignore``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
TASK = "ICA-Classification"
MARKER = ".chip_smoke"


@dataclasses.dataclass(frozen=True)
class Dims:
    """The fixture and model sizes one smoke run uses."""

    sites: int
    subjects: int
    comps: int
    temporal: int
    window: int
    stride: int
    input_size: int
    hidden_size: int
    batch: int
    compute_dtype: str
    seed: int = 0

    @property
    def windows(self) -> int:
        return self.temporal // self.window


#: HCP width (reference datasets/icalstm/inputspec.json), depth cut
FLAGSHIP = Dims(
    sites=32, subjects=48, comps=100, temporal=980, window=10, stride=10,
    input_size=256, hidden_size=348, batch=16, compute_dtype="bfloat16",
)


class SmokeFailure(AssertionError):
    """A check of the smoke run did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def device_report() -> dict:
    """What jax runs on, printed before anything else happens."""
    import jax
    import jaxlib

    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "not installed"
    dev = jax.devices()[0]
    rep = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
    }
    say(
        f"device: platform={rep['platform']} kind={rep['kind']} "
        f"count={rep['count']} | jax {rep['jax']} jaxlib {rep['jaxlib']} "
        f"libtpu {rep['libtpu']}"
    )
    return rep


# ---------------------------------------------------------------------------
# fixture / directories
# ---------------------------------------------------------------------------


def _own_dir(path: str) -> str:
    """Create ``path`` as a directory this script owns (marked), emptying a
    previous run's; refuses a directory it did not mark."""
    if os.path.isdir(path) and os.listdir(path):
        check(
            os.path.exists(os.path.join(path, MARKER)),
            f"{path} exists and was not made by chip_smoke.py; move it away",
        )
        shutil.rmtree(path)
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, MARKER), "w") as fh:
        fh.write("made by chip_smoke.py; safe to delete\n")
    return path


def make_fixture(root: str, dims: Dims) -> str:
    """The seeded ICA simulator tree for ``dims`` under ``root``; a tree a
    previous run generated for the same dims is reused."""
    from dinunet_implementations_tpu.data.demo import make_ica_demo_tree

    stamp = os.path.join(root, MARKER)
    want = json.dumps(dataclasses.asdict(dims), sort_keys=True)
    if os.path.exists(stamp) and open(stamp).read() == want:
        return root
    _own_dir(root)
    make_ica_demo_tree(
        root, n_sites=dims.sites, subjects=dims.subjects, comps=dims.comps,
        temporal=dims.temporal, window=dims.window, stride=dims.stride,
        seed=dims.seed, input_size=dims.input_size,
        hidden_size=dims.hidden_size,
    )
    with open(stamp, "w") as fh:
        fh.write(want)
    return root


def cache_entries(cache_dir: str) -> int:
    """Executables in a jax persistent compile cache directory."""
    return len(glob.glob(os.path.join(cache_dir, "*-cache")))


# ---------------------------------------------------------------------------
# reading what a run left behind
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def lowered_programs(dirpath: str):
    """While active, jax writes the lowered text of every program it
    compiles under ``dirpath`` (jax's own ``jax_dump_ir_to``)."""
    import jax

    os.makedirs(dirpath, exist_ok=True)
    jax.config.update("jax_dump_ir_to", dirpath)
    try:
        yield dirpath
    finally:
        jax.config.update(
            "jax_dump_ir_to", os.environ.get("JAX_DUMP_IR_TO", "")
        )


def _programs(dirpath: str, fn_name: str) -> list[str]:
    paths = sorted(glob.glob(os.path.join(dirpath, f"*_jit_{fn_name}_compile.mlir")))
    return [open(p).read() for p in paths]


def _json(path: str):
    with open(path) as fh:
        return json.load(fh)


def _rows(path: str) -> list[dict]:
    with open(path) as fh:
        return [json.loads(ln) for ln in fh if ln.strip()]


def _check_manifest(manifest: dict, platform: str) -> None:
    import jax

    check(manifest["backend"] == platform,
          f"manifest backend {manifest['backend']!r}, expected {platform!r}")
    check(manifest["device_kind"] == jax.devices()[0].device_kind,
          f"manifest device_kind {manifest['device_kind']!r}")
    check(manifest["device_count"] == len(jax.devices()),
          f"manifest device_count {manifest['device_count']!r}")


def _model_overrides(dims: Dims) -> list[str]:
    # the widths ride the fixture's inputspec; the dtype is a run option and
    # must be the same for the trainer and the server that rebuilds the model
    return ["--set", f"compute_dtype={json.dumps(dims.compute_dtype)}"]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_kernel_parity(dims: Dims, platform: str, batch: int = 16) -> dict:
    """The model with the Pallas LSTM kernel against the same model on the
    ``lax.scan`` path: same weights, same input, logits and parameter
    gradient. Not an entry point — the one check of the kernel's numbers
    against the repo's reference path, and the first thing to fail if Mosaic
    refuses the kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dinunet_implementations_tpu.models import ICALstm

    kw = dict(
        input_size=dims.input_size, hidden_size=dims.hidden_size,
        num_comps=dims.comps, window_size=dims.window, num_cls=2,
        compute_dtype=dims.compute_dtype or None,
    )
    kernel, scan = ICALstm(use_pallas=True, **kw), ICALstm(use_pallas=False, **kw)
    x = jax.random.normal(
        jax.random.PRNGKey(dims.seed),
        (batch, dims.windows, dims.comps, dims.window), jnp.float32,
    )
    variables = scan.init(
        {"params": jax.random.PRNGKey(1), "dropout": jax.random.PRNGKey(2)},
        x, train=False,
    )

    def loss(model, params):
        logits = model.apply({**variables, "params": params}, x, train=False)
        return jnp.sum(jax.nn.log_softmax(logits)[:, 0]), logits

    out = {}
    for name, model in (("kernel", kernel), ("scan", scan)):
        (_, logits), grads = jax.jit(
            jax.value_and_grad(lambda p, m=model: loss(m, p), has_aux=True)
        )(variables["params"])
        flat = jnp.concatenate(
            [g.reshape(-1).astype(jnp.float32) for g in jax.tree.leaves(grads)]
        )
        out[name] = (np.asarray(logits, np.float32), np.asarray(flat))
        check(next(iter(logits.devices())).platform == platform,
              f"{name} logits on {logits.devices()}, expected {platform}")
    (lk, gk), (ls, gs) = out["kernel"], out["scan"]
    check(lk.shape == (batch, 2), f"logits shape {lk.shape}")
    check(np.isfinite(lk).all() and np.isfinite(gk).all(),
          "kernel path produced non-finite logits or gradients")
    # both paths round the matmul inputs to the compute dtype and accumulate
    # in f32; what remains is summation order over 98 recurrent steps
    tol = 5e-2 if dims.compute_dtype else 2e-3
    logit_err = float(np.abs(lk - ls).max())
    cos = float(gk @ gs / (np.linalg.norm(gk) * np.linalg.norm(gs)))
    check(logit_err < tol, f"kernel vs scan logits differ by {logit_err} (tol {tol})")
    check(cos > 0.99, f"kernel vs scan gradient cosine {cos}")
    return {"logit_max_abs_err": logit_err, "grad_cosine": cos}


def phase_train(
    data: str, out_dir: str, dims: Dims, engine: str, epochs: int,
    cache_dir: str, platform: str, *, mosaic_calls: int | None = None,
    sites_per_device: int | None = None, mesh_devices: int = 0,
) -> dict:
    """One federated fit through ``dinunet-tpu``'s ``main(argv)`` at
    TrainConfig defaults, then the checks on what it wrote.

    ``mosaic_calls``: Mosaic custom calls the lowered epoch program must
    hold (``None`` = do not count: off a TPU the kernels run interpreted and
    lower to plain HLO). ``mesh_devices`` > 0: the run must have sharded its
    per-site state over that many distinct devices and the program must
    hold all-reduces."""
    from dinunet_implementations_tpu.runner.cli import main as train_main

    argv = [
        "--data-path", data, "--task", TASK, "--engine", engine,
        "--epochs", str(epochs), "--batch-size", str(dims.batch),
        "--out-dir", out_dir, "--telemetry", "on", "--sanitize", "compile",
        "--compile-cache", cache_dir, *_model_overrides(dims),
    ]
    if sites_per_device is not None:
        argv += ["--sites-per-device", str(sites_per_device)]
    t0 = time.monotonic()
    with lowered_programs(os.path.join(out_dir, "ir")) as ir:
        rc = train_main(argv)
    seconds = time.monotonic() - t0
    check(rc == 0, f"dinunet-tpu exited {rc}")

    tel = os.path.join(out_dir, "telemetry", "fold_0")
    manifest = _json(os.path.join(tel, "manifest.json"))
    _check_manifest(manifest, platform)
    check(manifest["agg_engine"] == engine and manifest["num_sites"] == dims.sites,
          f"manifest ran {manifest['agg_engine']} on {manifest['num_sites']} sites")
    rows = _rows(os.path.join(tel, "metrics.jsonl"))
    ep = [r for r in rows if r["kind"] == "epoch"]
    summary = next(r for r in rows if r["kind"] == "summary")
    losses = [r["train_loss"] for r in ep]
    check(len(ep) == epochs, f"{len(ep)} epoch rows for {epochs} epochs")
    check(all(v is not None and math.isfinite(v) for v in losses),
          f"non-finite epoch loss: {losses}")
    check(losses[0] != losses[-1], f"loss did not move: {losses}")
    check(summary["epoch_compiles"] == 1,
          f"epoch program compiled {summary['epoch_compiles']} times")
    programs = _programs(ir, "epoch_fn_impl")
    check(len(programs) == 1, f"{len(programs)} epoch programs lowered")
    check(all(d.startswith(platform + ":") for d in summary["params_devices"]),
          f"params live on {summary['params_devices']}, expected {platform}")
    res = {
        "engine": engine, "epochs": epochs, "losses": losses,
        "epoch_seconds": [r["epoch_seconds"] for r in ep],
        "mesh": manifest["mesh"], "seconds": round(seconds, 2),
        "params_devices": summary["params_devices"],
    }
    if mosaic_calls is not None:
        n = programs[0].count("tpu_custom_call")
        check(n == mosaic_calls,
              f"epoch program holds {n} Mosaic custom calls, expected "
              f"{mosaic_calls}: a kernel gave way to another path")
        res["mosaic_calls"] = n
    if mesh_devices:
        placed = summary["site_state_devices"]
        check(len(set(placed)) == mesh_devices,
              f"per-site state on {placed}, expected {mesh_devices} devices")
        n = programs[0].count("all_reduce")
        check(n > 0, "sharded epoch program holds no all-reduce")
        res.update(site_state_devices=placed, all_reduces=n)
    return res


def phase_serve(
    data: str, out_dir: str, dims: Dims, cache_dir: str, platform: str,
    requests: int = 50,
) -> dict:
    """The serving CLI's ``main(argv)`` on the checkpoint a train phase
    wrote under ``out_dir``: warm-up compiles every bucket, the request path
    compiles nothing, every answered row is finite."""
    from dinunet_implementations_tpu.serving.__main__ import main as serve_main

    argv = [
        "--data-path", data, "--task", TASK, "--out-dir", out_dir,
        "--smoke", str(requests), "--sanitize", "compile",
        "--compile-cache", cache_dir, *_model_overrides(dims),
    ]
    t0 = time.monotonic()
    with lowered_programs(os.path.join(out_dir, "ir_serve")) as ir:
        rc = serve_main(argv)
    seconds = time.monotonic() - t0
    check(rc == 0, f"serving CLI exited {rc}")

    tel = os.path.join(out_dir, "telemetry", "serving")
    _check_manifest(_json(os.path.join(tel, "manifest.json")), platform)
    rows = _rows(os.path.join(tel, "metrics.jsonl"))
    summary = next(r for r in rows if r["kind"] == "serve_summary")
    buckets = summary["buckets"]["infer"]
    lowered = len(_programs(ir, "infer_fn"))
    check(lowered == len(buckets),
          f"warm-up lowered {lowered} programs for buckets {buckets}")
    check(summary["compiles_after_warmup"] == 0,
          f"{summary['compiles_after_warmup']} compiles after warm-up")
    check(summary["requests"] == requests,
          f"answered {summary['requests']} of {requests} requests")
    check(summary["samples"] > 0 and summary["nonfinite_rows"] == 0,
          f"{summary['nonfinite_rows']} of {summary['samples']} answered "
          "rows are not finite")
    used = {r["bucket"] for r in rows if r["kind"] == "dispatch"}
    check(used <= set(buckets), f"dispatched buckets {used} outside {buckets}")
    return {
        "requests": summary["requests"], "samples": summary["samples"],
        "buckets": buckets, "buckets_hit": sorted(used),
        "warmup_seconds": summary["warmup_seconds"],
        "latency_ms_p50": summary["latency_ms_p50"],
        "latency_ms_p99": summary["latency_ms_p99"],
        "seconds": round(seconds, 2),
    }


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def run(dims: Dims, platform: str, data_root: str, out_root: str,
        cache_dir: str, device: dict | None = None) -> dict:
    """All phases in order; returns the report (also written, phase by
    phase, to ``<out_root>/report.json`` — a failed run leaves what passed).
    ``cache_dir`` is where the compile cache goes unless
    ``JAX_COMPILATION_CACHE_DIR`` places it elsewhere."""
    import jax

    from dinunet_implementations_tpu.core.jaxcompat import enable_compile_cache

    # the directory the environment names, else the one passed in; enabled
    # here so that the parity phase's programs are cached like the rest
    cache_dir = enable_compile_cache(cache_dir)
    # forward + backward kernel, once per LSTM direction; off a TPU the
    # kernels are not in the program to count
    kernels = 4 if platform == "tpu" else None
    _own_dir(out_root)
    report: dict = {
        "device": device, "dims": dataclasses.asdict(dims),
        "cache_dir": cache_dir, "cache_entries_before": cache_entries(cache_dir),
        "phases": {},
    }

    t0 = time.monotonic()

    def record(name: str, result: dict) -> None:
        report["phases"][name] = result
        report["cache_entries_after"] = cache_entries(cache_dir)
        report["seconds"] = round(time.monotonic() - t0, 2)
        with open(os.path.join(out_root, "report.json"), "w") as fh:
            json.dump(report, fh, indent=1)
        if result.get("ran", True):
            say(f"phase {name}: PASS {json.dumps(result)}")
        else:
            say(f"phase {name}: DID NOT RUN — {result['why']}")

    data = make_fixture(data_root, dims)
    say(f"fixture: {dims.sites} sites x {dims.subjects} subjects, "
        f"{dims.comps}x{dims.temporal} in {dims.windows} windows "
        f"({time.monotonic() - t0:.1f}s) at {data}")

    record("kernel/parity", phase_kernel_parity(dims, platform))
    dsgd_out = os.path.join(out_root, "dsgd")
    record("train/dSGD", phase_train(
        data, dsgd_out, dims, "dSGD", 3, cache_dir, platform,
        mosaic_calls=kernels,
    ))
    record("train/rankDAD", phase_train(
        data, os.path.join(out_root, "rankdad"), dims, "rankDAD", 2,
        cache_dir, platform,
    ))
    record("serve", phase_serve(data, dsgd_out, dims, cache_dir, platform))
    n_dev = len(jax.devices())
    if n_dev >= 4 and dims.sites % 4 == 0:
        record("train/dSGD-mesh", phase_train(
            data, os.path.join(out_root, "dsgd_mesh"), dims, "dSGD", 3,
            cache_dir, platform, mosaic_calls=kernels,
            sites_per_device=dims.sites // 4, mesh_devices=4,
        ))
    else:
        record("train/dSGD-mesh", {
            "ran": False,
            "why": f"jax reports {n_dev} device(s); the phase needs 4 and a "
                   "site count they divide",
        })
    return report


def main() -> int:
    device = device_report()
    if device["platform"] != "tpu":
        say(f"refusing to run: jax found platform {device['platform']!r}, "
            "not a TPU. This script proves the chip path; tests/ covers the "
            "CPU.")
        return 2
    report = run(
        FLAGSHIP, "tpu", os.path.join(HERE, "chip_smoke_data"),
        os.path.join(HERE, "chip_smoke_out"),
        os.path.join(HERE, ".jax_cache"), device,
    )
    say(f"all phases passed in {report['seconds']}s; compile cache "
        f"{report['cache_dir']}: {report['cache_entries_before']} -> "
        f"{report['cache_entries_after']} entries")
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"],
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
