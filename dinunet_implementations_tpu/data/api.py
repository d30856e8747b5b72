"""Dataset / data-handle abstraction surface.

Keeps the reference's framework contract (SURVEY.md §2.3: ``COINNDataset`` with
``cache``/``state``/``indices``/``path()`` + hooks ``load_index`` /
``_load_indices`` / ``__getitem__``; ``COINNDataHandle`` with ``list_files``)
so reference workloads port 1:1 — but adds the TPU-first path: every dataset
can **materialize** to dense numpy arrays once (:class:`SiteArrays`), which the
trainer stacks across sites and ships to the mesh. The reference re-reads files
per item per epoch (``comps/fs/__init__.py:33-39``); we pay I/O once.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import numpy as np


@dataclass
class SiteArrays:
    """One site's full dataset as dense arrays (the unit of SPMD feeding)."""

    inputs: np.ndarray  # [n, ...] float32 (token ids: int32)
    labels: np.ndarray  # [n] int32
    indices: np.ndarray  # [n] int32 — position in the site's sample inventory

    def __len__(self):
        return len(self.labels)

    def take(self, ix) -> "SiteArrays":
        ix = np.asarray(ix)
        return SiteArrays(self.inputs[ix], self.labels[ix], self.indices[ix])


# The device keeps an array's two minor dimensions in tiles of SUBLANE_TILE
# rows by LANE_TILE columns (for 2- and 4-byte elements alike on the chips
# this repo meets), each dimension padded up to whole tiles.
LANE_TILE = 128
SUBLANE_TILE = 8


def merged_sample_shape(sample_shape) -> tuple:
    """One sample with its trailing dimensions merged into the minor one
    while the minor one is narrower than a lane tile: ICA ``[98, 100, 10]`` →
    ``[98, 1000]``; FreeSurfer ``[66]`` and anything whose minor dimension
    fills a tile stay as they are. A view of the sample's own memory."""
    shape = tuple(int(d) for d in sample_shape)
    while len(shape) > 1 and shape[-1] < LANE_TILE:
        shape = shape[:-2] + (shape[-2] * shape[-1],)
    return shape


def stored_sample_shape(sample_shape) -> tuple:
    """The shape ONE sample takes in the device-resident inventory: its
    :func:`merged_sample_shape` with the rows (the dimension before the
    minor one) rounded up to whole sublane tiles, ICA ``[98, 1000]`` →
    ``[104, 1000]``; a sample of one dimension, or one the padding would
    grow by more than an eighth (``[3, 128]``), is left alone.

    Why: a stored sample is then a whole number of tiles, so the device's
    default layout for ``[sites, rows, 104, 1000]`` is row-major, the
    in-program row gather reads and writes whole tiles, and its ``[sites *
    batch, 104, 1000]`` result is a bitcast of the ``[sites, batch, ...]``
    operand the model's first contraction reads. With 98 rows the device
    tiles the SITE axis with the features instead and the epoch program
    relayouts the whole inventory before its first gather, every epoch, and
    the gathered batch twice a round (PERF.md §5–6, PR 27). On the device
    the pad rows are free: 98 rows occupy 104 either way."""
    shape = merged_sample_shape(sample_shape)
    if len(shape) >= 2:
        rows = -(-shape[-2] // SUBLANE_TILE) * SUBLANE_TILE
        if (rows - shape[-2]) * 8 <= shape[-2]:
            shape = shape[:-2] + (rows, shape[-1])
    return shape


def stored_data_index(sample_shape) -> tuple:
    """Index (after any leading axes) of a sample's own data inside its
    :func:`stored_sample_shape`: everything but the pad rows."""
    merged = merged_sample_shape(sample_shape)
    if len(merged) < 2:
        return (Ellipsis,)
    return (Ellipsis, slice(0, merged[-2]), slice(None))


@dataclass
class SiteInventory:
    """Every site's full dataset stacked on a common grid — the unit of
    DEVICE residency (uploaded to the mesh once per fit; each epoch then
    gathers its batches on-device from a compact index plan,
    trainer/steps.py). It is kept in the form the round's gather writes and
    the model's first contraction reads (the RESIDENT FORM):

    - ``inputs [S, rows + 1, *stored_sample_shape]``: a sample occupies its
      :func:`stored_sample_shape` (``sample_shape`` keeps the true one, which
      the epoch program restores on the gathered batch);
    - the grid has ONE MORE ROW than ``rows``: the last, all zeros in inputs
      and labels, is where a plan's ``-1`` padding slots point, so padding
      needs no mask pass over the gathered batch;
    - sites smaller than ``rows`` are zero-padded; a plan never points a live
      slot at such a row (``counts`` bounds the valid prefix).

    Every element that is not a subject's data is zero, and is WRITTEN so
    again at upload (:meth:`clear_padding`)."""

    inputs: np.ndarray  # [S, rows + 1, *stored] float32 (cast to compute dtype at upload) or int32 (as it is)
    labels: np.ndarray  # [S, rows + 1] int32
    counts: np.ndarray  # [S] int32 — valid rows per site
    sample_shape: tuple  # one sample as the model takes it

    @property
    def num_sites(self):
        return self.inputs.shape[0]

    @property
    def rows(self) -> int:
        """Subject rows per site (``N_max`` or the pinned budget): also the
        index of the zero row."""
        return self.inputs.shape[1] - 1

    @property
    def nbytes(self) -> int:
        return self.inputs.nbytes + self.labels.nbytes

    def clear_padding(self, inputs: np.ndarray, labels: np.ndarray) -> None:
        """Write zeros, in place, into everything of ``inputs`` / ``labels``
        (arrays of this inventory's shape, e.g. its cast copy on the way to
        the device) that is not a subject's data: the zero row, the rows past
        each site's count, the pad rows of every stored sample. What reaches
        the device is then written by the upload, whatever the arrays held."""
        merged = merged_sample_shape(self.sample_shape)
        if len(merged) >= 2 and merged[-2] != inputs.shape[-2]:
            inputs[..., merged[-2]:, :] = 0
        for si, n in enumerate(self.counts):
            inputs[si, n:] = 0
            labels[si, n:] = 0


def stack_site_inventory(
    sites: list["SiteArrays"], rows: int | None = None
) -> SiteInventory:
    """Pad heterogeneous sites (73–120 subjects in the FS fixture) onto one
    dense grid in the resident form (:class:`SiteInventory`): ``[S, N_max +
    1, *stored_sample_shape]``, the last row all zeros. Host-side and cheap:
    one copy of the dataset, paid once per fit instead of once per epoch
    (merging a sample's trailing dimensions is a view of the site's array).

    ``rows`` PINS ``N_max`` (elastic rounds, r13): the daemon-mode runner
    re-stacks the inventory on every membership change, and a joining site
    larger than any predecessor would otherwise grow the resident grid's
    traced shape and retrace the epoch. Must cover the largest site (the
    daemon enforces this at admission); the zero row then sits at ``rows``."""
    n_max = max((len(s) for s in sites), default=0)
    assert n_max > 0, "all sites empty"
    if rows is not None:
        assert rows >= n_max, (
            f"pinned inventory rows ({rows}) below the largest site "
            f"({n_max} samples)"
        )
        n_max = rows
    feat_shape = tuple(next(s.inputs.shape[1:] for s in sites if len(s)))
    merged = merged_sample_shape(feat_shape)
    data = stored_data_index(feat_shape)
    S = len(sites)
    # integer samples (token ids) stay integers, host to gather: an id above
    # 256 does not survive the upload's cast to bfloat16
    first = next(s.inputs for s in sites if len(s))
    dtype = np.int32 if np.issubdtype(first.dtype, np.integer) else np.float32
    inputs = np.zeros((S, n_max + 1) + stored_sample_shape(feat_shape), dtype)
    labels = np.zeros((S, n_max + 1), np.int32)
    counts = np.zeros((S,), np.int32)
    for si, s in enumerate(sites):
        n = len(s)
        counts[si] = n
        if n:
            inputs[si, :n][data] = s.inputs.reshape((n,) + merged)
            labels[si, :n] = s.labels
    return SiteInventory(inputs, labels, counts, feat_shape)


class SiteDataset:
    """Base dataset (capability parity with ``COINNDataset``, reconstructed
    from call sites — see SURVEY.md §2.3).

    Parameters
    ----------
    cache: dict-like task configuration (the reference's flat cache dict; here
        usually ``dataclasses.asdict`` of a task-args block merged with the
        train config).
    state: dict with at least ``baseDirectory`` — the site's data root
        (reference ``comps/fs/__init__.py:19``).
    mode: 'train' | 'test' (parity field).
    """

    def __init__(self, cache=None, state=None, mode: str = "train", **kw):
        self.cache = dict(cache or {})
        self.state = dict(state or {})
        self.mode = mode
        self.indices: list = []

    # -- reference API ---------------------------------------------------

    def path(self, cache_key: str = "data_file") -> str:
        """Resolve a cache key to a path under the site's base directory
        (reference ``comps/fs/__init__.py:35``, ``comps/icalstm/__init__.py:27``).
        With no/empty cache value, returns the base directory itself."""
        base = self.state.get("baseDirectory", "")
        name = self.cache.get(cache_key) or ""
        return os.path.join(base, name) if name else base

    def load_index(self, file):
        """Register one inventory entry. Subclasses override (reference hook)."""
        self.indices.append(file)

    def _load_indices(self, files, **kw):
        """Bulk variant (reference hook, ``comps/icalstm/__init__.py:26``)."""
        for f in files:
            self.load_index(f)

    def __getitem__(self, ix) -> dict:
        raise NotImplementedError

    def __len__(self):
        return len(self.indices)

    # -- TPU-first API ---------------------------------------------------

    def as_arrays(self) -> SiteArrays:
        """Materialize the whole site to dense arrays. Default implementation
        stacks ``__getitem__`` outputs; subclasses override with a vectorized
        loader when they can."""
        items = [self[i] for i in range(len(self))]
        inputs = np.stack([np.asarray(it["inputs"], np.float32) for it in items])
        labels = np.asarray([int(it["labels"]) for it in items], np.int32)
        ixs = np.asarray([int(it.get("ix", i)) for i, it in enumerate(items)], np.int32)
        return SiteArrays(inputs, labels, ixs)


class DataHandle:
    """Base data handle (capability parity with ``COINNDataHandle``): defines a
    site's sample inventory via ``list_files`` (reference
    ``comps/fs/__init__.py:66-71``, ``comps/icalstm/__init__.py:73-77``)."""

    def __init__(self, cache=None, state=None, **kw):
        self.cache = dict(cache or {})
        self.state = dict(state or {})

    def list_files(self) -> list:
        raise NotImplementedError


def build_site_dataset(
    dataset_cls, handle_cls, cache: dict, state: dict, mode: str = "train"
) -> SiteDataset:
    """Wire a (Dataset, DataHandle) pair the way ``COINNLocal`` does on the
    first round (SURVEY.md §3.2): handle.list_files → dataset._load_indices."""
    handle = handle_cls(cache=cache, state=state)
    ds = dataset_cls(cache=cache, state=state, mode=mode)
    ds._load_indices(handle.list_files())
    return ds
