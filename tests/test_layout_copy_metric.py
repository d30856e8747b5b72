"""``layout_copy_ms_per_round`` (ISSUE 27): the per-layer metric that says
whether the resident inventory engaged. It ships as data files only, so the
pattern is held here, against instruction names recorded on the chip, and the
number against the reader on hand-made ops.
"""

import re

import pytest

from benchmarks.lib import cells
from benchmarks.run import Ctx
from benchmarks.trace.extract import Op, Span, Trace
from benchmarks.trace.readers import device_ops_matching

METRIC = "layout_copy_ms_per_round"

# Leaf-op names of traced runs on the v5e (icalstm-hcp32.dsgd / .rankdad; the
# accepted tree's from the ledger's PR 24 breakdown, the others from the
# chip-run logs quoted in ISSUE 27): the relayouts of the gathered batch and
# of the whole inventory argument, the scan's small copies, an unnumbered one.
COPIES = ["copy.764", "copy.765", "copy.603", "copy.1791", "copy.2293",
          "copy.2294", "copy.751", "copy"]
# What only carries "copy" in its name: asynchronous moves between memory
# spaces, a fusion that holds a copy, and the rest of the round.
NOT_COPIES = ["copy-start.3", "copy-done.12", "copy-done", "copy_bitcast_fusion.1",
              "copy_bitcast_fusion", "select_select_fusion.2", "fusion.400",
              "lstm_fwd.22", "slice-done.4", "while.526", "copy.764.clone",
              "xcopy.1", "copy.1a"]


def spec():
    return cells.layer_metric(METRIC)


def test_the_file_and_its_per_layer_entry_agree():
    entries = [m for m in cells.benchmark_json()["per_layer"]
               if m["name"] == METRIC]
    assert len(entries) == 1
    for key, val in entries[0].items():
        assert spec()[key] == val, key
    # appended to what PR 24 left (later PRs append after it)
    names = [m["name"] for m in cells.benchmark_json()["per_layer"]]
    assert names.index(METRIC) == names.index("lstm_bwd_kernel_ms_per_round") + 1
    assert spec()["layer"] == cells.layer_metric("device_ms_per_round")["layer"]
    assert spec()["reader"] == "device_ops_matching"
    assert spec()["args"] == {"pattern": r"^copy(\.\d+)?$", "field": "name",
                              "per": "rounds_traced", "how": "sum"}
    assert "workloads" not in spec()  # every training cell's program has copies


@pytest.mark.parametrize("name", COPIES)
def test_pattern_takes_the_compilers_plain_copies(name):
    assert re.search(spec()["args"]["pattern"], name)


@pytest.mark.parametrize("name", NOT_COPIES)
def test_pattern_leaves_what_only_names_a_copy(name):
    assert not re.search(spec()["args"]["pattern"], name)


def test_through_the_reader_on_hand_made_ops():
    """Two rounds under a rounds scan: three copies count; the asynchronous
    pair, the fusion, the select and the scan itself do not."""
    def op(name, lo, hi, leaf=True):
        o = Op(name, lo, hi, f"%{name} = bf16[8]{{0}} something(...)")
        o.leaf = leaf
        return o

    ops = [op("while.1", 0.0, 10.0, leaf=False),
           op("copy.603", 0.0, 2.0), op("copy.764", 2.0, 2.5),
           op("select_select_fusion.2", 2.5, 3.0), op("copy", 3.0, 3.25),
           op("copy-start.3", 3.25, 3.5), op("copy-done.12", 4.0, 5.0),
           op("copy_bitcast_fusion.1", 5.0, 6.0)]
    trace = Trace(devices={"d0": ops}, spans=[Span("bench/epoch", 0.0, 10.0)])
    ctx = Ctx(trace, trace.window(), {"rounds_traced": 2})
    got = device_ops_matching.read(ctx, **spec()["args"])
    assert got == pytest.approx((2.0 + 0.5 + 0.25) / 2 * 1e3)
    # a run with no device trace reports nothing and does not raise
    assert device_ops_matching.read(Ctx(None, None, {}), **spec()["args"]) is None
