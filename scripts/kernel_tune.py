"""Sweep the block a grid step of the LSTM kernels carries, on the real chip.

Times the forward call and the backward call ALONE at the benchmark's folded
shape (T=98, rows=32 sites x 16 batch = 512, D=256, H=174, bf16 streams) for
a list of ``(Tb, R)`` blocks, handed to the kernels through
``lstm_pallas.lstm_block``'s ``override`` argument (the ``block=`` of
``_fwd_fused_call`` / ``_bwd_call``). ``auto`` is what ``lstm_block`` chooses
itself. Every block's outputs are compared with the first block's.

A call is timed inside ONE jitted ``fori_loop`` that feeds the call's carry
outputs (``hT, cT`` / ``dh0, dc0``) back as its carry inputs, so the device
runs the iterations back to back with no host in between; the marginal
between a long and a short loop cancels what starting and ending one costs.

Usage: python scripts/kernel_tune.py [--blocks 1x128,7x256,auto]
           [--shape T,rows,D,H] [--dtype bfloat16|float32]
"""

import functools
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from dinunet_implementations_tpu.ops import lstm_pallas

SHAPE = (98, 512, 256, 174)
BLOCKS = [(1, 128), (2, 128), (7, 128), (10, 128), (14, 128), (1, 256), (2, 256),
          (4, 256), (7, 256), (1, 512), (2, 512), (3, 512), None]
ITERS = 200


@functools.lru_cache(maxsize=1)
def make_inputs(T, rows, D, H, cdt):
    rng = np.random.default_rng(0)

    def arr(*shape, scale=1.0, dtype=jnp.float32):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32) * scale, dtype)

    fwd = (arr(T, rows, D, dtype=cdt), arr(4, D, H, scale=0.05),
           arr(4, H, scale=0.05), arr(4, H, H, scale=0.05),
           arr(rows, H, scale=0.3), arr(rows, H, scale=0.3))
    cots = (arr(T, rows, H, dtype=cdt), arr(rows, H), arr(rows, H))
    return fwd, cots


def looped(call, iters):
    """``call(*fixed, *carry) -> outs`` whose last two outputs replace the
    carry: ``iters`` dependent calls in one device program."""
    def run(fixed, carry):
        def body(_, carry):
            return tuple(call(*fixed, *carry)[-2:])

        return jax.lax.fori_loop(0, iters, body, carry)

    return jax.jit(run)


def marginal_ms(call, fixed, carry, iters=ITERS, repeats=5):
    def wall(fn):
        jax.block_until_ready(fn(fixed, carry))  # compile, warm
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(fixed, carry))
            best = min(best, time.perf_counter() - t0)
        return best

    full, half = wall(looped(call, iters)), wall(looped(call, iters // 2))
    return (full - half) / (iters - iters // 2) * 1e3


def run(block, shape=SHAPE, cdt=jnp.bfloat16, reference=None):
    """Time one block; returns ``(fwd_ms, bwd_ms, outputs)``."""
    T, rows, D, H = shape
    (x, wih, b, whh, h0, c0), (dhs, dhT, dcT) = make_inputs(T, rows, D, H, cdt)

    def fwd(x, wih, b, whh, h0, c0):
        return lstm_pallas._fwd_fused_call(x, wih, b, whh, h0, c0, cdt, block=block)

    def bwd(ai, af, ao, ag, cs, whh, c0, dhs, dhT, dcT):
        return lstm_pallas._bwd_call(
            (ai, af, ao, ag), cs, whh, c0, dhs, dhT, dcT, cdt, block=block)

    hs, cs, ai, af, ao, ag, hT, cT = outs = jax.jit(fwd)(x, wih, b, whh, h0, c0)
    grads = jax.jit(bwd)(ai, af, ao, ag, cs, whh, c0, dhs, dhT, dcT)
    got = [np.asarray(a, np.float32) for a in (*outs, *grads)]
    err = (max(float(np.abs(a - r).max()) for a, r in zip(got, reference))
           if reference is not None else 0.0)

    fwd_ms = marginal_ms(fwd, (x, wih, b, whh), (h0, c0))
    # the backward's carry outputs (dh0, dc0) re-enter as (dhT, dcT)
    bwd_ms = marginal_ms(bwd, (ai, af, ao, ag, cs, whh, c0, dhs), (dhT, dcT))

    dtype = jnp.dtype(cdt)

    def describe(ms, d):  # d: D for the forward call, None for the backward
        Tb, R = lstm_pallas.lstm_block(T, rows, d, H, dtype, override=block)
        steps = (rows // R) * -(-T // Tb)
        vmem = lstm_pallas.block_vmem_bytes(Tb, R, d, H, dtype) / 2**20
        return (f"{ms:.4f} ms  Tb={Tb:2d} R={R:3d} {steps:3d} grid steps of "
                f"{ms * 1e3 / steps:6.2f} us, vmem {vmem:4.1f} MiB")

    print(f"block={'auto' if block is None else block}: fwd {describe(fwd_ms, D)} | "
          f"bwd {describe(bwd_ms, None)} | sum {fwd_ms + bwd_ms:.4f} ms  "
          f"max|diff| vs first {err:.3g}", flush=True)
    return fwd_ms, bwd_ms, got


def parse_block(text):
    return None if text == "auto" else tuple(int(v) for v in text.split("x"))


def main(argv):
    def option(name, default):
        return argv[argv.index(name) + 1] if name in argv else default

    blocks = BLOCKS
    if "--blocks" in argv:
        blocks = [parse_block(t) for t in option("--blocks", "").split(",")]
    shape = tuple(int(v) for v in option("--shape", ",".join(map(str, SHAPE))).split(","))
    cdt = jnp.dtype(option("--dtype", "bfloat16"))
    d = jax.devices()[0]
    print(f"device={d.platform} {d.device_kind}  T,rows,D,H={shape} {cdt.name}", flush=True)
    reference = None
    for block in blocks:
        try:
            _, _, got = run(block, shape, cdt, reference)
        except Exception as e:  # a block the compiler refuses (VMEM) is a result
            print(f"block={block}: REFUSED {str(e).splitlines()[0][:300]}", flush=True)
            continue
        reference = reference or got


if __name__ == "__main__":
    main(sys.argv[1:])
