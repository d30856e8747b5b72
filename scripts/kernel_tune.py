"""Tune the fused LSTM kernel's batch tile on the real chip.

Times fwd+bwd of lstm_recurrence_fused at the bench's folded shape
(T=98, rows=32 sites x 16 batch = 512, D=256, H=174, bf16 streams) for a
range of B_TILE values, using the chained-iteration methodology from
bench.py (a long dependent chain ended by a host fetch of every output,
so that the fixed cost of ending the chain cancels in the marginal).

Usage: python scripts/kernel_tune.py [--tiles 128,256,512]
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from dinunet_implementations_tpu.ops import lstm_pallas

T, ROWS, D, H = 98, 512, 256, 174
CHAIN = 60


def make_step(cdt):
    def loss(x, wih4, b4, whh4, h0, c0):
        hs, (hT, cT) = lstm_pallas.lstm_recurrence_fused(
            x, wih4, b4, whh4, h0, c0, cdt
        )
        return (hs.astype(jnp.float32).sum() + hT.sum() + cT.sum())

    g = jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))

    def step(x, wih4, b4, whh4, h0, c0):
        dx, dwih, db, dwhh, dh0, dc0 = g(x, wih4, b4, whh4, h0, c0)
        # chain: feed gradient signal back into the inputs so iterations
        # depend on each other and the lazy backend cannot skip any
        return (
            x + dx.astype(x.dtype) * 1e-6,
            wih4 + dwih * 1e-6,
            b4 + db * 1e-6,
            whh4 + dwhh * 1e-6,
            h0 + dh0 * 1e-6,
            c0 + dc0 * 1e-6,
        )

    return jax.jit(step)


def run(tile, cdt="bfloat16", chain=CHAIN, repeats=3):
    lstm_pallas.B_TILE = tile
    lstm_pallas._fwd_fused_callable.cache_clear()
    lstm_pallas._bwd_callable.cache_clear()
    rng = np.random.default_rng(0)
    args = (
        jnp.asarray(rng.normal(size=(T, ROWS, D)).astype(np.float32)),
        jnp.asarray(rng.normal(size=(4, D, H)).astype(np.float32) * 0.05),
        jnp.asarray(rng.normal(size=(4, H)).astype(np.float32) * 0.05),
        jnp.asarray(rng.normal(size=(4, H, H)).astype(np.float32) * 0.05),
        jnp.zeros((ROWS, H), jnp.float32),
        jnp.zeros((ROWS, H), jnp.float32),
    )
    step = make_step(cdt)

    def chain_run(n):
        a = args
        t0 = time.time()
        for _ in range(n):
            a = step(*a)
        jax.tree.map(np.asarray, a)
        return time.time() - t0

    chain_run(2)  # compile
    from bench import least_contended_marginal  # shared clamped estimator

    dt = least_contended_marginal(chain_run, chain, repeats=repeats)
    sps = ROWS / dt
    print(f"B_TILE={tile:4d} cdt={cdt}: {dt*1e3:8.3f} ms/iter  "
          f"({sps:,.0f} rows/s)", flush=True)
    return dt


def main():
    tiles = [128, 256, 512]
    if "--tiles" in sys.argv:
        tiles = [int(t) for t in sys.argv[sys.argv.index("--tiles") + 1].split(",")]
    for tile in tiles:
        run(tile)


if __name__ == "__main__":
    main()
