"""The language-model harness takes a decoder as its configuration describes it:
one without routed experts goes through ``drivers/train_lm`` to a correct last
line with no routing read, a kernel family's roofline sums its own calls, and
the reference check's parameter groups can be named by the configuration."""

import argparse
import gc
import json
import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells, refcheck_lm
from benchmarks.run import Ctx
from benchmarks.trace import extract, reduce
from benchmarks.trace.readers import fact, roofline_share

TRINITY = "trinity-mini-ep16.dsgd-fold2"
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def dense_trinity():
    """Trinity's cell at its ``tiny`` rehearsal size with every layer dense,
    and the dense MLP checked as a group the configuration names."""
    cell = cells.load_cell(TRINITY)
    tiny = cell.config["rehearse"]["tiny"]
    layers = cell.config["train_config"]["lm_args"]["num_hidden_layers"]
    tiny["train_config"]["lm_args"]["num_dense_layers"] = layers
    tiny["check"]["groups"] = [["mlp", "feed_forward"]]
    limits = tiny["check"]["grad_cosine_min"]
    limits["feed_forward"] = limits["dense_mlp"]
    return cell


def test_a_decoder_without_routed_experts_runs_to_a_correct_line(tmp_path, capsys):
    from benchmarks.drivers import train_lm

    cell = dense_trinity()
    args = argparse.Namespace(workload=cell.name, seed=3000000041, seconds=1.0,
                              trace=0, rehearse="tiny")
    device = {"platform": "cpu", "kind": "cpu", "count": 1, "peak": None}
    try:
        result = train_lm.run(cell, args, time.perf_counter(), device,
                              str(tmp_path))
    finally:
        gc.unfreeze()
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    check = next(l["reference_check"] for l in lines if "reference_check" in l)
    assert result["correct"] and result["failed"] == 0 and check["ok"]
    assert check["routing"] is None
    assert not any("held_assignments_per_token" in l for l in lines)
    # no expert layer, so no group of the experts' parameters; the dense MLP
    # is the configuration's group and held to its limit
    assert set(check["grad_cosine"]) == {
        "attention", "feed_forward", "embedding", "head", "norms", "all"}
    facts = result["facts"]
    assert "moe_expert_load_max_over_mean" in facts
    assert facts["moe_expert_load_max_over_mean"] is None
    spec = cells.layer_metric("moe_expert_load_max_over_mean")
    assert spec["reader"] == "fact"
    assert fact.read(Ctx(None, None, facts), **spec["args"]) is None


# -- a kernel family's roofline ----------------------------------------------


def two_families():
    """A hybrid's kernel model: a scan family and an attention family, the
    first bound by bytes, the second by flops on a v5e."""
    return {"calls": [
        {"name": "scan_fwd", "count": 2, "flops": 1.0e9, "bytes": 4.0e8},
        {"name": "scan_bwd", "count": 2, "flops": 2.0e9, "bytes": 8.0e8},
        {"name": "attention_fwd", "count": 3, "flops": 9.0e12, "bytes": 1.0e8},
        {"name": "attention_dkv", "count": 1, "flops": 1.6e13, "bytes": 2.0e8},
    ]}


def by_hand(model, peak, names):
    return sum(c["count"] * max(c["flops"] / peak["bf16_flops_per_s"],
                                c["bytes"] / peak["hbm_bytes_per_s"])
               for c in model["calls"] if c["name"] in names)


def test_each_kernel_family_reads_its_own_least_time():
    trace = extract.load(FIXTURES)
    window = trace.window()
    peak = cells.peaks()["TPU v5 lite"]
    model, rounds, pattern = two_families(), 3, "dot_general"
    ctx = Ctx(trace, window, {"kernel_model": model, "rounds_traced": rounds,
                              "peak": peak})
    per_round = np.mean(list(reduce.matching_seconds(
        trace, window, pattern).values())) / rounds
    assert per_round > 0
    scan, attention = ("scan_fwd", "scan_bwd"), ("attention_fwd", "attention_dkv")
    read = {part: roofline_share.read(ctx, pattern, calls=list(part))
            for part in (scan, attention)}
    for part, value in read.items():
        assert value == pytest.approx(100 * by_hand(model, peak, part) / per_round)
    assert by_hand(model, peak, scan) != by_hand(model, peak, attention)
    # no calls: every call, the sum of the two families, as before
    whole = roofline_share.read(ctx, pattern)
    assert whole == pytest.approx(read[scan] + read[attention])
    assert whole == 100.0 * roofline_share.least_seconds(model, peak) / per_round
    with pytest.raises(ValueError):
        roofline_share.read(ctx, pattern, calls=["no_such_call"])


def test_the_accepted_roofline_files_sum_every_call():
    for name in ("lstm_kernel_roofline", "attention_kernel_roofline",
                 "mla_attention_kernel_roofline",
                 "smallthinker_attention_kernel_roofline",
                 "lfm2_attention_kernel_roofline"):
        spec = cells.layer_metric(name)
        assert spec["reader"] == "roofline_share" and "calls" not in spec["args"]


# -- the check's parameter groups ----------------------------------------------


PATHS = [("layer_0", "attn", "wq"), ("layer_0", "attn", "q_norm", "scale"),
         ("layer_1", "mixer", "A_log"), ("layer_1", "mixer", "dt_proj"),
         ("layer_1", "mlp", "w1"), ("layer_2", "moe", "router"),
         ("layer_2", "moe", "expert_bias"), ("embed",)]


def test_group_of_takes_the_configurations_groups_first():
    rule = [refcheck_lm.group_of(p) for p in PATHS]
    assert rule == ["attention", "norms", "norms", "norms", "dense_mlp",
                    "router", None, "embedding"]
    assert [refcheck_lm.group_of(p, ()) for p in PATHS] == rule
    named = [("mixer", "state_space"), ("q_norm", "attention_norms")]
    assert [refcheck_lm.group_of(p, named) for p in PATHS] == [
        "attention", "attention_norms", "state_space", "state_space",
        "dense_mlp", "router", None, "embedding"]
    # the first pair that matches wins
    assert refcheck_lm.group_of(PATHS[2], [("layer_1", "one"), ("mixer", "two")]) \
        == "one"


def test_group_cosines_name_the_configurations_groups():
    ones = lambda *s: jnp.ones(s, jnp.float32)
    a = {"layer_0": {"attn": {"wq": ones(4, 4)}},
         "layer_1": {"mixer": {"A_log": ones(4), "dt_proj": ones(4, 2)}},
         "embed": ones(8, 4)}
    b = {"layer_0": {"attn": {"wq": ones(4, 4)}},
         "layer_1": {"mixer": {"A_log": -ones(4), "dt_proj": ones(4, 2)}},
         "embed": ones(8, 4)}
    plain = refcheck_lm.group_cosines(a, b)
    assert list(plain) == ["attention", "embedding", "norms", "all"]
    assert plain["norms"] == pytest.approx(4 / 12)  # the scan's leaves hidden there
    named = refcheck_lm.group_cosines(a, b, [("mixer", "state_space")])
    assert list(named) == ["attention", "embedding", "state_space", "all"]
    assert named["state_space"] == pytest.approx(4 / 12)
    assert named["all"] == plain["all"] and named["attention"] == 1.0
