"""Record the tiny trace ``tests/fixtures/tiny.xplane.pb`` (a few KB): three
annotated epochs of a small jitted program on the CPU backend, python tracer
off. It has no device plane, so ``trace/extract.py`` reads it as a rehearsal
(pseudo-device ``host-xla``); tests/test_reduce.py checks the reduction on it
against a brute-force count. Re-record only with a reason: the test's numbers
come from the file.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/record_fixture.py
"""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        y = jnp.tanh(x @ x)
        return (y @ y.T).sum() + jnp.cumsum(y, axis=0).mean()

    x = jnp.ones((192, 192))
    step(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    tmp = tempfile.mkdtemp(dir=os.environ.get("TMPDIR"))
    jax.profiler.start_trace(tmp, profiler_options=options)
    for epoch in range(3):
        with jax.profiler.TraceAnnotation("bench/epoch", epoch=epoch):
            with jax.profiler.TraceAnnotation("bench/prefetch.get"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("bench/run_epoch"):
                step(x).block_until_ready()
        time.sleep(0.001)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    dst = os.path.join(HERE, "tests", "fixtures", "tiny.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    print(dst, os.path.getsize(dst), "bytes")


if __name__ == "__main__":
    main()
