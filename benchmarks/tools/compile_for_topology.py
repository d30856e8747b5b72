"""Rehearsal 3 of the on-chip-measurement guide: compile a cell's epoch program
at REAL size for a described ``v5e:2x2`` topology, here, without the chip, and
print the compiler's ``memory_analysis()``. Nothing runs; no time or rate comes
out of this. Run by hand before a chip call that a refused program or an
out-of-memory would waste:

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_for_topology.py <cell>

The model is cloned with ``use_pallas=True`` where it has the field, because
the program asks ``jax.default_backend()`` and would take its CPU branch here.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from benchmarks.drivers import train
    from benchmarks.lib import cells
    from dinunet_implementations_tpu.parallel.mesh import MODEL_AXIS, SITE_AXIS
    from dinunet_implementations_tpu.trainer.loop import FederatedTrainer
    from dinunet_implementations_tpu.trainer.steps import (
        _state_specs,
        init_train_state,
    )

    jax.config.update("jax_enable_compilation_cache", False)
    cell = cells.load_cell(args.cell)
    cfg, task, model = train.configure(cell)
    num_sites = cfg.num_sites
    if hasattr(model, "use_pallas"):
        model = model.clone(use_pallas=True)
        # the kernels would lower in interpret mode on this CPU backend:
        # steer them here, in the tool, not through an option of the program
        from dinunet_implementations_tpu.ops import lstm_pallas

        lstm_pallas._interpret = lambda: False
    spec = cell.data_spec(None)
    n = int(spec["subjects_per_site"])
    sample = tuple(task.serving.sample_shape(cfg))
    batch, steps = cfg.batch_size, n // cfg.batch_size

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    if cell.chips == 1:
        mesh = None
        put = lambda spec_: SingleDeviceSharding(topo.devices[0])
    else:
        devs = np.asarray(topo.devices[: cell.chips]).reshape(cell.chips, 1)
        mesh = Mesh(devs, (SITE_AXIS, MODEL_AXIS))
        put = lambda spec_: NamedSharding(mesh, spec_)
    trainer = FederatedTrainer(cfg, model, mesh)
    state = jax.eval_shape(lambda: init_train_state(
        trainer.task, trainer.engine, trainer.optimizer,
        jax.random.PRNGKey(0), jnp.ones((batch,) + sample, jnp.float32),
        num_sites=num_sites))
    specs = _state_specs(state, SITE_AXIS)
    sds = lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=put(s))
    state = jax.tree.map(sds, state, specs)
    in_dtype = trainer._input_dtype or jnp.float32
    site = P(SITE_AXIS)
    inv_x = jax.ShapeDtypeStruct((num_sites, n) + sample, in_dtype, sharding=put(site))
    inv_y = jax.ShapeDtypeStruct((num_sites, n), jnp.int32, sharding=put(site))
    idx = jax.ShapeDtypeStruct((num_sites, steps, batch), jnp.int32, sharding=put(site))
    compiled = trainer.epoch_fn.lower(
        state, inv_x, inv_y, idx, None, None, None, None).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    gib = 2 ** 30
    print(f"cell {cell.name}: {num_sites} sites x {n} subjects, {steps} rounds "
          f"an epoch, compiled for {cell.chips} x {topo.devices[0].device_kind}")
    print(f"  arguments {mem.argument_size_in_bytes / gib:.3f} GiB, outputs "
          f"{mem.output_size_in_bytes / gib:.3f} GiB, temporaries "
          f"{mem.temp_size_in_bytes / gib:.3f} GiB, aliased "
          f"{mem.alias_size_in_bytes / gib:.3f} GiB (per device)")
    print(f"  tpu_custom_call: {text.count('tpu_custom_call')}, all-reduce: "
          f"{text.count('all-reduce(') + text.count('all-reduce-start(')}, "
          f"all-gather: {text.count('all-gather(') + text.count('all-gather-start(')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
