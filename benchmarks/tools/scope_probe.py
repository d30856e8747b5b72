"""Which stat of a device event carries a ``jax.named_scope``, and what a
Pallas kernel's ``name=`` looks like in the event text: the one fact the
scope metrics (``layer_metrics/*_ms_per_round.json`` with a scope pattern)
rest on. Runs a small jitted program with the scopes and kernel names the
epoch program uses, profiles it with the harness's profiler options, and
prints, for every distinct leaf operation of the device, the ``Op.text``
that ``trace/extract.py`` builds (what a pattern is matched against) and
every stat of the event in full.

Then the compile cache: the same program under other scope names, and under
other kernel names, against one fresh cache directory. A scope rename that
adds no entry means an executable loaded from a cache carries the metadata
of whoever filled it (``jax_compilation_cache_include_metadata_in_key``
false, the default).

    python3 benchmarks/tools/scope_probe.py [out_dir]     # on the chip

Re-run after a jax upgrade, before trusting a scope metric that reads 0.0.
"""

from __future__ import annotations

import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def program_names():
    """``(scopes, kernels)`` as the program spells them today."""
    from dinunet_implementations_tpu.ops import lstm_pallas
    from dinunet_implementations_tpu.telemetry import scopes

    return ((scopes.GATHER, scopes.MODEL, scopes.ENGINE, scopes.POWERITER,
             scopes.OPTIMIZER),
            (lstm_pallas.LSTM_FWD, lstm_pallas.BILSTM_FWD))


def build(scopes, kernels):
    """The probe program: the epoch program's structure in small (a rounds
    scan; a gather; a vmapped value_and_grad whose forward calls two named
    Pallas kernels; a while loop under two nested scopes; an update)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    gather, model, engine, poweriter, optimizer = scopes

    def _double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def kernel(name, x):
        return pl.pallas_call(
            _double, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=jax.default_backend() != "tpu", name=name)(x)

    @jax.custom_vjp
    def doubled(x):
        return kernel(kernels[0], x) + kernel(kernels[1], x)

    doubled.defvjp(lambda x: (doubled(x), None),
                   lambda _, g: (kernel(kernels[0], g) * 2.0,))

    def loss(w, x):
        return jnp.tanh(doubled(x @ w)).sum()

    @jax.jit
    def probe(w, inv, ixs):
        def round_(w, ix):
            with jax.named_scope(gather):
                x = jnp.take(inv, ix, axis=1)
                x = jnp.where(ix[None, :, None] >= 0, x, 0.0)
            with jax.named_scope(model):
                _, g = jax.vmap(jax.value_and_grad(loss), (None, 0))(w, x)
            with jax.named_scope(engine):
                q = g.mean(0)
                with jax.named_scope(poweriter):
                    _, q = jax.lax.while_loop(
                        lambda c: c[0] < 3,
                        lambda c: (c[0] + 1, jnp.tanh(c[1] @ c[1].T @ c[1])),
                        (0, q))
            with jax.named_scope(optimizer):
                w = w - 1e-3 * q
            return w, q.sum()

        return jax.lax.scan(round_, w, ixs)

    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (256, 256), jnp.float32) * 0.05
    inv = jax.random.normal(key, (4, 1024, 256), jnp.float32)
    ixs = jax.random.randint(key, (6, 512), 0, 1024)
    return probe, (w, inv, ixs)


def trace_once(fn, args, trace_dir):
    import jax

    jax.block_until_ready(fn(*args))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench/epoch"):
            jax.block_until_ready(fn(*args))
    jax.profiler.stop_trace()


def describe(trace_dir, out):
    """Per distinct leaf op of every device: extract's text, then each stat
    of the raw event in full."""
    from jax.profiler import ProfileData

    from benchmarks.trace import extract

    trace = extract.load(trace_dir)
    print(f"rehearsal={trace.rehearsal} devices={list(trace.devices)}",
          file=out)
    for dev, ops in trace.devices.items():
        seen = set()
        for o in ops:
            if o.name in seen or o.lane != "sync":
                continue
            seen.add(o.name)
            print(f"OP leaf={o.leaf} {o.text}", file=out)
    for path in extract.xplane_files(trace_dir):
        for plane in ProfileData.from_file(path).planes:
            if not extract.DEVICE_PLANE.match(plane.name):
                continue
            for line in plane.lines:
                if line.name not in extract.OP_LINES:
                    continue
                seen = set()
                for e in line.events:
                    if e.name in seen:
                        continue
                    seen.add(e.name)
                    print(f"EVENT[{line.name}] {e.name}", file=out)
                    for k, v in e.stats:
                        print(f"    {k} = {v}", file=out)


def cache_entries(path):
    return sorted(f for f in os.listdir(path) if not f.endswith("-atime"))


def main() -> int:
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from benchmarks.trace.extract import xplane_files

    out_dir = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        "chiprun_out", "scope_probe")
    os.makedirs(out_dir, exist_ok=True)
    tmp = tempfile.mkdtemp(dir=os.environ.get("TMPDIR"))
    cache = os.path.join(tmp, "cache")
    os.makedirs(cache)
    cc.set_cache_dir(cache)
    cc.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    with open(os.path.join(out_dir, "report.txt"), "w") as out:
        d = jax.devices()[0]
        names, kernel_names = program_names()
        print(f"jax {jax.__version__} {d.platform} {d.device_kind} "
            f"include_metadata_in_key="
            f"{jax.config.jax_compilation_cache_include_metadata_in_key}",
            file=out)
        variants = [
            ("scopes and kernel names as the program has them", names,
             kernel_names),
            ("scopes renamed", tuple("x" + s for s in names), kernel_names),
            ("kernels renamed", names, tuple(k + "_x" for k in kernel_names)),
        ]
        for i, (what, scopes, kernels) in enumerate(variants):
            jax.clear_caches()
            before = cache_entries(cache)
            fn, args = build(scopes, kernels)
            tdir = os.path.join(tmp, f"trace{i}")
            trace_once(fn, args, tdir)
            added = [e for e in cache_entries(cache) if e not in before]
            print(f"\n=== variant {i}: {what}; cache entries added: "
                  f"{len(added)} {added}", file=out)
            describe(tdir, out)
            if i == 0:  # the raw file, for a look at what ProfileData hides
                shutil.copy(xplane_files(tdir)[0],
                            os.path.join(out_dir, "probe.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    sys.stdout.write(open(os.path.join(out_dir, "report.txt")).read()[-20000:])
    return 0


if __name__ == "__main__":
    sys.exit(main())
