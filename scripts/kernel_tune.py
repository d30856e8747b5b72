"""Sweep the block geometry of the Pallas kernels on the real chip.

**LSTM (default).** Times the forward call and the backward call ALONE at the
benchmark's folded shape (T=98, rows=32 sites x 16 batch = 512, D=256, H=174,
bf16 streams) for a list of ``(Tb, R)`` blocks, handed to the kernels through
``lstm_pallas.lstm_block``'s ``override`` argument (the ``block=`` of
``_fwd_fused_call`` / ``_bwd_call``). ``auto`` is what ``lstm_block`` chooses
itself. Every block's outputs are compared with the first block's.

**Attention (``--attention``).** Times the splash-attention forward, dq and
dkv kernels ALONE, as ``afmoe.kernel_attention`` calls them under the
trainer's vmap over two sites, at one of the shapes the language-model cells
run (``--shape a|b|c|d|e`` or ``T,kv_heads,heads_per_kv,head_dim,window|none``),
for a list of ``block_q x block_kv x compute`` geometries (``--blocks``;
``auto`` is what ``afmoe.attention_blocks`` chooses itself, each kernel its
own). The three kernels' block sizes are independent fields, so one geometry
is given to all three and each is timed in its own loop (the dq kernel has no
compute block: it is timed once a ``block_q x block_kv``). ``--layouts qkv``
with each letter ``h`` (head width minor, the default) or ``s`` (sequence
minor) sets the operand layouts. A strided sample of every point's outputs is
compared with the first point's.

A call is timed inside ONE jitted loop that feeds the call's outputs back into
its inputs, so the device runs the iterations back to back with no host in
between; the marginal between a long and a short loop cancels what starting
and ending one costs.

Usage: python scripts/kernel_tune.py [--blocks 1x128,7x256,auto]
           [--shape T,rows,D,H] [--dtype bfloat16|float32]
       python scripts/kernel_tune.py --attention [--shape a]
           [--blocks 512x512x512,1024x1024x512,auto] [--layouts hhh]
"""

import functools
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from dinunet_implementations_tpu.models import afmoe
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


# -- the attention kernels ------------------------------------------------------

# (T, key-value heads, query heads a key-value head, head width, window)
ATTN_SHAPES = {
    "a": (8192, 20, 1, 256, None),  # latent attention: full causal, width 256
    "b": (8192, 4, 8, 128, None),  # grouped-query attention, a full layer
    "c": (8192, 4, 8, 128, 2048),  # the same, a sliding layer
    # smallthinker at its full context: seven query heads a key-value head
    "d": (16384, 4, 7, 128, 4096),  # a sliding layer, 4 windows long
    "e": (16384, 4, 7, 128, None),  # the full layer
}
ATTN_SIDES = (512, 1024, 2048)
SITES = 2  # the trainer's fold
ATTN_ITERS = 12


def attention_grid(sides=ATTN_SIDES):
    """``(block_q, block_kv, compute)`` over ``sides`` squared, the compute
    block 256, 512 or the whole key block; the 512 point first."""
    grid = [(bq, bkv, c) for bq in sides for bkv in sides
            for c in sorted({256, 512, bkv}) if c <= bkv]
    grid.remove((512, 512, 512))
    return [(512, 512, 512)] + grid


def attention_sizes(geometry, layouts="hhh"):
    """One ``(block_q, block_kv, compute)`` for all three kernels; ``layouts``
    gives the query's, keys' and values': ``h`` head width minor, ``s``
    sequence minor."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    q, k, v = (sk.QKVLayout.SEQ_MINOR if ch == "s" else sk.QKVLayout.HEAD_DIM_MINOR
               for ch in layouts)
    return afmoe.block_sizes(geometry, geometry, geometry,
                             q_layout=q, k_layout=k, v_layout=v)


@functools.lru_cache(maxsize=1)
def attention_inputs(shape, cdt):
    t, g, n, d, _ = shape
    rng = np.random.default_rng(0)

    def arr(*dims):
        return jnp.asarray(rng.normal(size=dims).astype(np.float32), cdt)

    # [sites, sequences, key-value heads, (query heads,) T, d], as
    # kernel_attention hands them to the kernel under the trainer's vmap
    q = arr(SITES, 1, g, n, t, d) * (d ** -0.5)
    return q, arr(SITES, 1, g, t, d), arr(SITES, 1, g, t, d), arr(SITES, 1, g, n, t, d)


def attention_loop(shape, sizes, kernel):
    """``run(q, k, v, do, n)``: ``n`` dependent calls of ONE kernel in one
    device program, returning a strided sample of the last call's outputs.
    The forward runs as the backward needs it (it writes the log-sum-exp too);
    the backward's two kernels are told apart by what is used of the
    cotangents: the compiler drops the call nobody reads. Their one forward
    outside the loop falls out of the marginal."""
    t, g, n, d, window = shape
    fold = jax.vmap(jax.vmap(jax.vmap(
        afmoe._splash(t, n, window, sizes, afmoe._interpret()))))

    def sample(a):
        return a[..., ::67, :].astype(jnp.float32)

    def chain(call, x, n):
        """``x`` moves by one element of the outputs a call: a real
        dependence that costs nothing to write."""
        first = (0,) * (x.ndim - 1)

        def body(_, carry):
            x, _ = carry
            outs = call(x)
            nudge = sum(o[first] for o in outs) * 1e-3
            return x.at[first].add(nudge.astype(x.dtype)), tuple(map(sample, outs))

        shapes = jax.eval_shape(lambda x: tuple(map(sample, call(x))), x)
        zeros = tuple(jnp.zeros(s.shape, s.dtype) for s in shapes)
        return jax.lax.fori_loop(0, n, body, (x, zeros))[1]

    def run(q, k, v, do, n):
        if kernel == "fwd":
            return chain(lambda q: (jax.vjp(fold, q, k, v)[0],), q, n)
        vjp = jax.vjp(fold, q, k, v)[1]
        used = slice(0, 1) if kernel == "dq" else slice(1, 3)
        return chain(lambda do: vjp(do)[used], do, n)

    return jax.jit(run)


def time_attention(shape, sizes, kernel, iters=ATTN_ITERS, repeats=3):
    """``(ms a call, sample)`` of one kernel; the first line of the refusal
    where the compiler refuses the geometry (VMEM): that is a result."""
    args = attention_inputs(shape, jnp.bfloat16)
    run = attention_loop(shape, sizes, kernel)

    def wall(n):
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            got = jax.block_until_ready(run(*args, n))
            best = min(best, time.perf_counter() - t0)
        return best, got

    try:
        jax.block_until_ready(run(*args, 1))  # compile, warm
    except Exception as e:
        return str(e).strip().splitlines()[0][:200]
    short = iters // 3
    (full, got), (part, _) = wall(iters), wall(short)
    return (full - part) / (iters - short) * 1e3, [np.asarray(a) for a in got]


def attention_main(option, argv):
    shape = option("--shape", "a")
    shape = ATTN_SHAPES.get(shape) or tuple(
        None if v == "none" else int(v) for v in shape.split(","))
    t, g, n, d, window = shape
    layouts = option("--layouts", "hhh")
    points = attention_grid()
    if "--blocks" in argv:
        points = [None if p == "auto" else tuple(int(v) for v in p.split("x"))
                  for p in option("--blocks", "").split(",")]
    dev = jax.devices()[0]
    print(f"device={dev.platform} {dev.device_kind}  T,kv_heads,heads_per_kv,d,window="
          f"{shape} bfloat16 sites={SITES} layouts={layouts}", flush=True)
    auto = afmoe.attention_blocks(t, n, d, window, jnp.bfloat16)
    seen, first = {}, {}
    for point in points:
        sizes = auto if point is None else attention_sizes(point, layouts)
        row = [f"{'auto' if point is None else 'x'.join(map(str, point)):>14}:"]
        for kernel, geometry in afmoe.kernel_blocks(sizes).items():
            key = (kernel, geometry)
            if key not in seen:
                seen[key] = time_attention(shape, sizes, kernel)
            got = seen[key]
            label = kernel + " " + "x".join(map(str, geometry[:2 if kernel == "dq" else 3]))
            if isinstance(got, str):
                row.append(f"{label} REFUSED {got}")
                continue
            ms, sample = got
            ref = first.setdefault(kernel, sample)
            err = max(float(np.abs(a - r).max()) for a, r in zip(sample, ref))
            row.append(f"{label} {ms:8.4f} ms max|diff| {err:.2g}")
        print(" | ".join(row), flush=True)


def main(argv):
    def option(name, default):
        return argv[argv.index(name) + 1] if name in argv else default

    if "--attention" in argv:
        return attention_main(option, argv)
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
