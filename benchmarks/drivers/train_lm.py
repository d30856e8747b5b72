"""Job kind ``train_lm``: one federated NEXT-TOKEN training job through
``FederatedTrainer`` (ISSUE 28).

``run`` is ``drivers/train.py``'s, built from that file's parts (``build``,
``EpochLoop``, ``_counted_step``, ``_timed``, ``_traced``, ``_loss_band``,
``say``): the same set-up, warm-up, window and last line. What differs follows
from the model's size (half a billion parameters, 5.6 GB of state):

- the state is initialised with an int32 sample (token ids);
- the comparison with the plain reference (``lib/refcheck_lm.py``) cannot
  build a second trainer beside the first: it checks what the timed path
  itself produced. The driver keeps on the HOST what that needs (the initial
  parameters, the loop's own batch order of the first epoch, the parameters
  and losses after the FIRST warm-up epoch: a 2 GB fetch inside ``setup_s``),
  reads the memory peak when the window ends, RELEASES the trained state and
  the inventory, and only then runs the check;
- where the model routes (the check's ``routing``, from the ``held_counts``
  its expert layers sow), it hands the readers
  ``moe_expert_load_max_over_mean`` and prints the held assignments a token,
  both from the model's own routing of round 1's batch; a model without
  expert layers hands them None, which a reader reports as nothing to read.

Names of the program this file depends on beyond ``drivers/train.py``'s:
``data.batching.plan_epoch_positions`` (with the arguments
``trainer/loop.py _build_epoch_payload`` gives it), ``trainer._inventory``.
"""

from __future__ import annotations

import gc
import importlib
import os
import time

import numpy as np

from benchmarks.drivers.train import (
    WARMUP_EPOCHS,
    EpochLoop,
    _cache_entries,
    _counted_step,
    _loss_band,
    _memory,
    _timed,
    _traced,
    build,
    say,
)
from benchmarks.lib import cells


def first_epoch_positions(cfg, sites, batch: int) -> np.ndarray:
    """``[S, rounds, B]``: the samples the loop's FIRST epoch trains on, in
    its order (``trainer/loop.py _build_epoch_payload``: epoch 1)."""
    from dinunet_implementations_tpu.data.batching import plan_epoch_positions

    return plan_epoch_positions(
        sites, batch, seed=cfg.seed * 100003 + 1, pad_mode="wrap", steps=None,
    ).positions


def run(cell, args, t0: float, device: dict, out_dir: str) -> dict:
    import jax
    import jax.numpy as jnp

    from dinunet_implementations_tpu.checks.sanitize import jit_cache_size
    from dinunet_implementations_tpu.core.jaxcompat import enable_compile_cache
    from dinunet_implementations_tpu.parallel.mesh import pack_factor
    from dinunet_implementations_tpu.runner.fed_runner import auto_site_mesh
    from dinunet_implementations_tpu.trainer.loop import FederatedTrainer

    from benchmarks.lib import refcheck_lm

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
        jnp.ones((batch,) + sites[0].inputs.shape[1:], jnp.int32),
        num_sites=num_sites)
    params0 = jax.device_get(state.params)
    loop = EpochLoop(trainer, sites, state, batch)
    del state  # the loop owns it (and donates it to every epoch)
    rounds = loop.rounds_per_epoch
    slots = num_sites * batch * rounds * max(cfg.local_iterations, 1)
    first = {"positions": first_epoch_positions(cfg, sites, batch)}

    losses: list[np.ndarray] = []
    warm_ms: list[float] = []
    try:
        for i in range(WARMUP_EPOCHS[1]):
            start, end, epoch_losses, grew = _counted_step(loop)
            losses.append(epoch_losses)
            warm_ms.append((end - start) * 1e3)
            if i == 0:
                t_fetch = time.perf_counter()
                first.update(losses=epoch_losses,
                             params=jax.device_get(loop.state.params))
                first_fetch_s = time.perf_counter() - t_fetch
            if i + 1 >= WARMUP_EPOCHS[0] and not grew:
                break
        else:
            raise SystemExit("the epoch program kept compiling during warm-up")
        t_warm = time.perf_counter()
        after_setup_bytes = _memory(used, "bytes_in_use")

        size0 = jit_cache_size(trainer.epoch_fn)
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
    payload = float(trainer.engine.wire_bytes(
        params0, pack=pack_factor(mesh, num_sites) if mesh is not None else 1))

    # the check needs the chip's memory: release what the window held
    t_window = time.perf_counter()
    loop.state = None
    trainer._inventory = None
    gc.collect()
    check = refcheck_lm.run(cell, cfg, model, sites, params0, first,
                            args.rehearse)
    t_check = time.perf_counter()
    say(reference_check=check)

    routing = check["routing"] or {}
    flops = importlib.import_module("benchmarks.flops." + cell.config["flops"])
    facts = {  # what the per-layer readers are handed (ctx.facts)
        "chips": len(used),
        "rounds_per_epoch": rounds,
        "slots_per_epoch": slots,
        "peak": device.get("peak"),
        "train_flops_per_sample": flops.train_flops_per_sample(cfg),
        "kernel_model": flops.kernel_model(cfg, num_sites * batch // len(used)),
        "engine_payload_bytes_per_round": payload,
        "moe_expert_load_max_over_mean": routing.get("load_max_over_mean"),
    }

    epochs = window["epochs"]  # [(start, end, losses, compiled)]
    first_t, last_t = epochs[0][0], epochs[-1][1]
    losses += [e[2] for e in epochs]
    attempted = rounds * len(epochs)
    failed = int(sum(
        rounds if e[3] else int((~np.isfinite(e[2])).sum()) for e in epochs))
    samples_per_s = slots * len(epochs) / (last_t - first_t)
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
            "of_which_first_epoch_fetch": first_fetch_s,
        },
        reference_check_s=t_check - t_window,
        **({"held_assignments_per_token": routing["held_per_token"]}
           if routing else {}),
        warmup_epoch_ms=warm_ms, epochs_in_window=len(epochs),
        epoch_ms_median=float(np.median(epoch_ms)),
        epoch_ms_max=float(np.max(epoch_ms)),
        epoch_ms_max_at=int(np.argmax(epoch_ms)),
        first_rounds_losses=[float(x) for x in np.concatenate(losses)[:12]],
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
        "setup_s": first_t - t0,
    }
    return {
        "correct": bool(check["ok"] and failed == 0 and band["ok"]),
        "attempted": int(attempted), "failed": failed,
        "values": values, "facts": facts, "trace_dir": window.get("trace_dir"),
        "memory_peak_bytes": peak_bytes,
    }
