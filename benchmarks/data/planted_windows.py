"""Recipe ``planted_windows``: seeded synthetic ICA subjects, cheap on the host.

A site draws one small block of Gaussian noise (``base_rows`` subjects of
``[windows, components, window_size]``); even base rows are class 0, odd rows
class 1, and class 1 carries a planted signal that does not depend on the
window (a fixed offset per component and timepoint, so a roll along the
window axis keeps it). Subject ``j`` is base row ``j % base_rows`` rolled by
``(j // base_rows) * 7`` windows: distinct subjects from one memcpy-speed pass
over the output, not one fresh normal per element (800 M normals a run would be
minutes of set-up on every run of every later check).

The program receives only the arrays: ``[n, windows, components, window_size]``
float32 inputs and int32 labels, which is what ``data/ica.py`` hands the
trainer.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np


def _site(seed: int, site: int, n: int, shape: tuple, base_rows: int,
          amplitude: float):
    windows = shape[0]
    rng = np.random.default_rng([seed, site, 0x1CA])
    base = rng.standard_normal((base_rows,) + shape, dtype=np.float32)
    signal = amplitude * rng.standard_normal(shape[1:], dtype=np.float32)
    base[1::2] += signal  # odd rows are class 1
    groups = -(-n // base_rows)
    if groups > windows:
        raise ValueError(
            f"{n} subjects need {groups} distinct rolls of {base_rows} base "
            f"rows but a subject has only {windows} windows: raise base_rows"
        )
    out = np.empty((n,) + shape, np.float32)
    for g in range(groups):
        lo = g * base_rows
        hi = min(lo + base_rows, n)
        out[lo:hi] = np.roll(base[: hi - lo], (g * 7) % windows, axis=1)
    labels = (np.arange(n) % base_rows % 2).astype(np.int32)
    return out, labels


def make_sites(spec: dict, sample_shape: tuple, num_sites: int, seed: int,
               threads: int = 8):
    """``[(inputs, labels), ...]``, one pair per site."""
    n = int(spec["subjects_per_site"])
    base_rows = int(spec.get("base_rows", 16))
    amplitude = float(spec.get("signal_amplitude", 0.25))
    if base_rows % 2:
        raise ValueError("base_rows must be even (classes alternate)")
    with ThreadPoolExecutor(max_workers=threads) as pool:
        return list(pool.map(
            lambda s: _site(seed, s, n, tuple(sample_shape), base_rows,
                            amplitude),
            range(num_sites),
        ))
