"""Job kind ``train``: one federated training job through ``FederatedTrainer``.

What a run does (ISSUE 22): set-up (compile cache, TrainConfig from the cell's
two data files, model through the registry, seeded site arrays, mesh through
``auto_site_mesh``, trainer, state, inventory upload, warm-up epochs until the
epoch program's jit cache stops growing), then either the timed window or a
short profiled stretch, then the comparison with the plain reference. The
comparison is the benchmark's own work, so it runs after the window's last
timestamp and after the memory peak is read: it is in neither ``setup_s`` nor
``peak_hbm_gib``, which then hold only what the program does.

The window trains epochs the way ``trainer/loop.py _fit_impl`` trains them:
plans from an ``EpochPlanPrefetcher`` over ``_build_epoch_payload``, then
``run_epoch(..., plan=prefetch.get(epoch))``, which ends in the loss fetch (the
synchronisation). ``fit()`` itself cannot be used: it refuses an empty test
split and cannot be bounded in time. Names of the program this file depends
on are listed in PERF.md section 3.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import json
import math
import os
import shutil
import time

import numpy as np

from benchmarks.lib import cells

TRACE_EPOCHS = (3, 300)  # fewest and most epochs a traced stretch holds
WARMUP_EPOCHS = (3, 6)  # fewest and most; the last one's time sizes a traced stretch


def say(**kw) -> None:
    """An earlier line: anything worth keeping that the last line may not
    hold. One JSON object per line."""
    print(json.dumps(kw, default=float), flush=True)


def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def _memory(devices, key: str) -> int:
    vals = [(d.memory_stats() or {}).get(key, 0) for d in devices]
    return int(max(vals)) if vals else 0


class EpochLoop:
    """Epochs as the fit loop runs them, one ``step()`` an epoch."""

    def __init__(self, trainer, sites, state, batch: int):
        from dinunet_implementations_tpu.data.batching import epoch_steps
        from dinunet_implementations_tpu.trainer.prefetch import (
            EpochPlanPrefetcher,
        )

        self.trainer, self.sites, self.state, self.batch = (
            trainer, sites, state, batch)
        self.rounds_per_epoch = epoch_steps(sites, batch) // max(
            trainer.cfg.local_iterations, 1)
        round0 = int(state.round)
        self.prefetch = EpochPlanPrefetcher(
            lambda e: trainer._build_epoch_payload(
                sites, e, batch, round0 + (e - 1) * self.rounds_per_epoch),
            1, 10 ** 9,
        )
        self.epoch = 0

    def step(self, span=None):
        """One epoch. ``(start, end, losses)`` on the harness clock; ``span``
        wraps the epoch and the two calls in profiler annotations."""
        span = span or (lambda name, **kw: contextlib.nullcontext())
        self.epoch += 1
        start = time.perf_counter()
        with span("bench/epoch", epoch=self.epoch):
            with span("bench/prefetch.get"):
                plan = self.prefetch.get(self.epoch)
            with span("bench/run_epoch"):
                self.state, losses = self.trainer.run_epoch(
                    self.state, self.sites, self.epoch, batch_size=self.batch,
                    plan=plan)
        return start, time.perf_counter(), np.asarray(losses)

    def close(self) -> None:
        self.prefetch.close()


def configure(cell, rehearse=None, seed: int = 0):
    """``(cfg, task, model)`` from the cell's two files: ``TrainConfig``
    defaults apart from what they state, the model through the registry."""
    from dinunet_implementations_tpu.core.config import TrainConfig
    from dinunet_implementations_tpu.runner.registry import get_task

    cfg = TrainConfig(
        task_id=cell.config["task_id"], num_sites=cell.num_sites(rehearse),
        seed=seed, epochs=10 ** 9,
    ).with_overrides(cell.train_config(rehearse))
    task = get_task(cfg.task_id)
    return cfg, task, task.build_model(cfg)


def build(cell, args):
    """``(cfg, model, sites)`` from the cell's files and ``--seed``."""
    from dinunet_implementations_tpu.data.api import SiteArrays

    cfg, task, model = configure(cell, args.rehearse, args.seed)
    spec = cell.data_spec(args.rehearse)
    recipe = importlib.import_module("benchmarks.data." + spec["recipe"])
    sites = [
        SiteArrays(x, y, np.arange(len(y), dtype=np.int32))
        for x, y in recipe.make_sites(
            spec, task.serving.sample_shape(cfg), cfg.num_sites, args.seed)
    ]
    return cfg, model, sites


def run(cell, args, t0: float, device: dict, out_dir: str) -> dict:
    import jax
    import jax.numpy as jnp

    from dinunet_implementations_tpu.checks.sanitize import jit_cache_size
    from dinunet_implementations_tpu.core.jaxcompat import enable_compile_cache
    from dinunet_implementations_tpu.parallel.mesh import pack_factor
    from dinunet_implementations_tpu.runner.fed_runner import auto_site_mesh
    from dinunet_implementations_tpu.trainer.loop import FederatedTrainer

    from benchmarks.lib import refcheck

    cache_dir = enable_compile_cache(os.path.join(cells.ROOT, ".jax_cache"))
    cache0 = _cache_entries(cache_dir)
    t_mark = time.perf_counter()
    cfg, model, sites = build(cell, args)
    cfg = cfg.replace(compile_cache_dir=cache_dir)
    num_sites, batch = len(sites), cfg.batch_size
    t_data = time.perf_counter()

    mesh = auto_site_mesh(cfg, num_sites)
    used = list(mesh.devices.flat) if mesh is not None else jax.devices()[:1]
    if len(used) != cell.chips and not args.rehearse:
        raise SystemExit(
            f"cell {cell.name} asks for {cell.chips} chip(s) but the mesh "
            f"uses {len(used)}")
    trainer = FederatedTrainer(cfg, model, mesh)
    state = trainer.init_state(
        jnp.ones((batch,) + sites[0].inputs.shape[1:], jnp.float32),
        num_sites=num_sites)
    params0 = jax.device_get(state.params)
    stats0 = jax.device_get(state.batch_stats)
    loop = EpochLoop(trainer, sites, state, batch)
    rounds = loop.rounds_per_epoch
    slots = num_sites * batch * rounds * max(cfg.local_iterations, 1)

    losses: list[np.ndarray] = []
    warm_ms: list[float] = []
    try:
        # warm-up through the same calls the window uses, until the epoch
        # program's jit cache stops growing (the first call uploads the
        # inventory and compiles or loads the program)
        for i in range(WARMUP_EPOCHS[1]):
            start, end, epoch_losses, grew = _counted_step(loop)
            losses.append(epoch_losses)
            warm_ms.append((end - start) * 1e3)
            if i + 1 >= WARMUP_EPOCHS[0] and not grew:
                break
        else:
            raise SystemExit("the epoch program kept compiling during warm-up")
        t_warm = time.perf_counter()
        after_setup_bytes = _memory(used, "bytes_in_use")

        size0 = jit_cache_size(trainer.epoch_fn)
        # Set-up leaves a heap of a few hundred thousand long-lived objects
        # (jaxprs, lowered modules); a full collection over it was timed at
        # about 0.1 s (CPU rehearsal). Collect once here and freeze what
        # survives, so that collections inside the window look only at what
        # the window allocates. This rules the collector out as a source of
        # stalled epochs; it did not remove the one stalled epoch that 40-50 %
        # of one-chip runs show (PERF.md section 6, PR 22), whose cause is open.
        gc.collect()
        gc.freeze()
        if args.trace:
            window = _traced(loop, cell, warm_ms, out_dir)
        else:
            window = _timed(loop, args.seconds)
        compiles = (jit_cache_size(trainer.epoch_fn) or 0) - (size0 or 0)
    finally:
        loop.close()
    peak_bytes = _memory(used, "peak_bytes_in_use")

    t_window = time.perf_counter()
    check = refcheck.run(cell, cfg, model, sites, params0, stats0,
                         len(used), trainer.engine)
    t_check = time.perf_counter()
    say(reference_check=check)

    flops = importlib.import_module("benchmarks.flops." + cell.config["flops"])
    facts = {  # what the per-layer readers are handed (ctx.facts)
        "chips": len(used),
        "rounds_per_epoch": rounds,
        "slots_per_epoch": slots,
        "peak": device.get("peak"),
        "train_flops_per_sample": flops.train_flops_per_sample(cfg),
        "kernel_model": flops.kernel_model(cfg, num_sites * batch // len(used)),
        "engine_payload_bytes_per_round": float(trainer.engine.wire_bytes(
            params0,
            pack=pack_factor(mesh, num_sites) if mesh is not None else 1)),
    }

    epochs = window["epochs"]  # [(start, end, losses, compiled)]
    first, last = epochs[0][0], epochs[-1][1]
    losses += [e[2] for e in epochs]
    attempted = rounds * len(epochs)
    failed = int(sum(
        rounds if e[3] else int((~np.isfinite(e[2])).sum()) for e in epochs))
    samples_per_s = slots * len(epochs) / (last - first)
    epoch_ms = [(e[1] - e[0]) * 1e3 for e in epochs]
    facts.update(
        epoch_ms=epoch_ms, rounds_traced=attempted,
        samples_per_s=samples_per_s, compiles_in_window=compiles)

    band = _loss_band(cell, np.concatenate(losses), args)
    say(
        cell=cell.name, seed=args.seed, versions={"jax": jax.__version__},
        setup_split_s={
            "imports_and_device": t_mark - t0, "data": t_data - t_mark,
            "trainer_upload_warmup": t_warm - t_data,
        },
        reference_check_s=t_check - t_window,
        warmup_epoch_ms=warm_ms, epochs_in_window=len(epochs),
        epoch_ms_median=float(np.median(epoch_ms)),
        epoch_ms_max=float(np.max(epoch_ms)),
        epoch_ms_max_at=int(np.argmax(epoch_ms)),
        bytes_in_use_after_setup=after_setup_bytes, peak_bytes_in_use=peak_bytes,
        compile_cache={"dir": cache_dir, "entries_before": cache0,
                       "entries_after": _cache_entries(cache_dir)},
        prefetch=loop.prefetch.stats(), loss_band=band,
        transfer_bytes_per_epoch=trainer._last_transfer_bytes,
        facts={k: v for k, v in facts.items()
               if k not in ("epoch_ms", "peak")},
    )

    values = {
        "train_samples_per_s": samples_per_s / len(used),
        "peak_hbm_gib": peak_bytes / 2 ** 30,
        "setup_s": first - t0,
    }
    return {
        "correct": bool(check["ok"] and failed == 0 and band["ok"]),
        "attempted": int(attempted), "failed": failed,
        "values": values, "facts": facts, "trace_dir": window.get("trace_dir"),
        "memory_peak_bytes": peak_bytes,
    }


def _counted_step(loop, span=None) -> tuple:
    """``(start, end, losses, compiled again)`` of one epoch."""
    from dinunet_implementations_tpu.checks.sanitize import jit_cache_size

    size0 = jit_cache_size(loop.trainer.epoch_fn)
    start, end, epoch_losses = loop.step(span)
    return (start, end, epoch_losses,
            jit_cache_size(loop.trainer.epoch_fn) != size0)


def _timed(loop, seconds: float) -> dict:
    """Whole epochs until ``seconds`` have passed since the first one began."""
    epochs = []
    while True:
        epochs.append(_counted_step(loop))
        if epochs[-1][1] - epochs[0][0] >= seconds:
            return {"epochs": epochs}


def _traced(loop, cell, warm_ms, out_dir: str) -> dict:
    """A profiled stretch of about ``trace_seconds``: a number of epochs fixed
    from the warm-up's epoch time, each under the harness's annotations."""
    import jax

    steady = warm_ms[-1] * 1e-3
    n = int(min(max(math.ceil(float(cell.traffic.get("trace_seconds", 2.5))
                              / max(steady, 1e-6)), TRACE_EPOCHS[0]),
                TRACE_EPOCHS[1]))
    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the harness's own spans are enough
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        epochs = [_counted_step(loop, jax.profiler.TraceAnnotation)
                  for _ in range(n)]
    finally:
        jax.profiler.stop_trace()
    return {"epochs": epochs, "trace_dir": trace_dir}


def _loss_band(cell, losses: np.ndarray, args) -> dict:
    """Mean loss over rounds 1..K against the band the cell's file records
    for this seed family. A rehearsal has no band."""
    band = cell.facts.get("loss_band")
    if args.rehearse or not band:
        return {"ok": bool(args.rehearse), "checked": False,
                "mean_first_rounds": float(np.mean(losses[:64]))}
    k = int(band["rounds"])
    if len(losses) < k:
        return {"ok": False, "checked": False,
                "why": f"only {len(losses)} rounds ran, the band needs {k}"}
    mean = float(np.mean(losses[:k]))
    return {"ok": bool(band["lo"] <= mean <= band["hi"]), "checked": True,
            "rounds": k, "mean": mean, "lo": band["lo"], "hi": band["hi"]}
