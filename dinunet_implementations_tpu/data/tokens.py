"""Token-sequence dataset for the next-token task (``LM-NextToken``).

A site's inventory is one ``.npy`` file of token ids, ``[n, seq_len + 1]``
integers (``data_file`` under the site's base directory): the model reads the
first ``seq_len`` ids of a row, the loss the last ``seq_len``. Samples stay
``int32`` from here to the on-device gather (data/api.py
``stack_site_inventory``): a token id does not survive a cast to bfloat16.
"""

from __future__ import annotations

import numpy as np

from .api import DataHandle, SiteArrays, SiteDataset


class TokenDataHandle(DataHandle):
    def list_files(self) -> list:
        rows = np.load(SiteDataset(cache=self.cache, state=self.state).path(),
                       mmap_mode="r").shape[0]
        return list(range(rows))


class TokenDataset(SiteDataset):
    def _rows(self) -> np.ndarray:
        return np.load(self.path(), mmap_mode="r")

    def __getitem__(self, ix) -> dict:
        row = self.indices[ix]
        return {"inputs": np.asarray(self._rows()[row], np.int32),
                "labels": 0, "ix": ix}

    def as_arrays(self) -> SiteArrays:
        n = len(self.indices)
        rows = np.asarray(self._rows()[np.asarray(self.indices, np.int64)],
                          np.int32)
        want = int(self.cache.get("seq_len", rows.shape[1] - 1)) + 1
        if n and rows.shape[1] != want:
            raise ValueError(
                f"{self.path()}: rows of {rows.shape[1]} ids, the task reads "
                f"seq_len + 1 = {want}")
        # the task's targets come from its own input: labels are unused
        return SiteArrays(rows, np.zeros((n,), np.int32),
                          np.arange(n, dtype=np.int32))
