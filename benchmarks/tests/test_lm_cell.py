"""The next-token cell (ISSUE 28): its files agree with each other and with
the published configuration, the rehearsal ends in the contract's last line,
and the reference's stage-by-stage gradient chain is its own loss's gradient."""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.reference import afmoe as ref
from benchmarks.tests.test_harness import check_last_line, run_cell

CELL = "trinity-mini-ep16.dsgd-fold2"
NEW_METRICS = (
    "moe_expert_load_max_over_mean", "moe_grouped_matmul_ms_per_round",
    "attention_kernel_ms_per_round", "attention_kernel_roofline",
    "attention_fwd_kernel_ms_per_round", "attention_dq_kernel_ms_per_round",
    "attention_dkv_kernel_ms_per_round")
#: instruction texts as a v5e trace of the cell carries them (my chip runs, PR 28)
CHIP_LINES = {
    "fwd": '%splash_mqa_fwd_residuals.105 = (f32[2,4,512,128]{3,2,1,0:T(8,128)}) '
           'custom-call(s8[1,16,16]{2,1,0:T(8,128)(4,1)} %remat2.1726), '
           'custom_call_target="tpu_custom_call"',
    "dq": '%splash_mqa_dq_no_residuals.26 = (f32[2,4,512,128]{3,2,1,0:T(8,128)}) '
          'custom-call(s8[1,16,16]{2,1,0:T(8,128)(4,1)} %remat2.1726), '
          'custom_call_target="tpu_custom_call"',
    "dkv": '%splash_mqa_dkv_no_residuals.27 = (f32[2,4,512,128]{3,2,1,0:T(8,128)}) '
           'custom-call(s8[1,5,16]{2,1,0:T(8,128)(4,1)} %remat2.1857), '
           'custom_call_target="tpu_custom_call"',
    "ragged": '%ragged-dot-none.12 = f32[8192,1024]{1,0:T(8,128)} custom-call('
              's32[1]{0:T(128)} %get-tuple-element.3), '
              'custom_call_target="tpu_custom_call"',
}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.mark.parametrize("trace", ["0"])  # traced: 8 minutes of CPU, by hand
def test_rehearsal_of_the_new_cell_prints_the_contracts_last_line(trace):
    out = run_cell("--workload", CELL, "--seed", "3000000001", "--seconds", "1",
                   "--trace", trace, "--rehearse", "tiny")
    check_last_line(out, trace == "1")
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.startswith("{")]
    check = next(l["reference_check"] for l in lines if "reference_check" in l)
    assert check["ok"] and check["rounds"] == 4 and check["sites"] == 2
    assert any("held_assignments_per_token" in l for l in lines)
    routing = check["routing"]
    assert set(routing) == {"load_max_over_mean", "held_per_token",
                            "held_per_expert"}
    assert routing["load_max_over_mean"] >= 1.0
    # the tiny size's four expert layers (layer 0 is dense), 4 held experts
    assert np.shape(routing["held_per_expert"]) == (4, 4)
    facts = next(l["facts"] for l in lines if "facts" in l)
    assert facts["moe_expert_load_max_over_mean"] == routing["load_max_over_mean"]
    if trace == "1":
        last = lines[-1]["metrics"]
        assert last["moe_expert_load_max_over_mean"]["value"] >= 1.0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_file_agrees_with_its_per_layer_entry(name):
    entry = next(m for m in cells.benchmark_json()["per_layer"]
                 if m["name"] == name)
    spec = cells.layer_metric(name)
    for key in ("name", "unit", "better", "source", "layer", "moves", "workloads"):
        assert spec[key] == entry[key], key
    assert entry["workloads"] == [CELL]
    assert entry["layer"] in open(os.path.join(cells.ROOT, "PERF.md")).read()


@pytest.mark.parametrize("metric,hits", [
    ("attention_kernel_ms_per_round", {"fwd", "dq", "dkv"}),
    ("attention_kernel_roofline", {"fwd", "dq", "dkv"}),
    ("attention_fwd_kernel_ms_per_round", {"fwd"}),
    ("attention_dq_kernel_ms_per_round", {"dq"}),
    ("attention_dkv_kernel_ms_per_round", {"dkv"}),
])
def test_attention_pattern_matches_its_chip_lines_and_no_other(metric, hits):
    from dinunet_implementations_tpu.models import afmoe

    pattern = cells.layer_metric(metric)["args"]["pattern"]
    assert {k for k, line in CHIP_LINES.items() if re.search(pattern, line)} == hits
    consts = {"fwd": afmoe.ATTN_FWD, "dq": afmoe.ATTN_DQ, "dkv": afmoe.ATTN_DKV}
    for k in hits:  # every name in a metric file is a constant of the program
        assert consts[k].startswith("splash_mqa") and (
            consts[k] in pattern or "splash_mqa(" in pattern
            or "splash_mqa(?!" in pattern)


def test_kernel_model_counts_the_calls_the_program_makes():
    from benchmarks.drivers import train
    from benchmarks.flops import afmoe as flops
    from benchmarks.trace.readers.roofline_share import least_seconds

    cfg, _, _ = train.configure(cells.load_cell(CELL))
    model = flops.kernel_model(cfg, 2)
    assert [c["count"] for c in model["calls"]] == [10, 5, 5]
    fwd = flops.forward_flops_per_sequence(cfg)["attention"]
    # forward twice (the block's recomputation), dq 6/4 and dkv 8/4 of it
    assert model["flops"] == pytest.approx(2 * fwd * (2 + 1.5 + 2.0))
    least = least_seconds(model, cells.peaks()["TPU v5 lite"])
    assert 0.05 < least < 0.12  # seconds a round at the chip's peak


def test_grouped_matmul_pattern_reads_the_compilers_instruction_names():
    pattern = cells.layer_metric("moe_grouped_matmul_ms_per_round")["args"]["pattern"]
    for name in ("ragged-dot-none", "ragged-dot-none.7", "ragged-dot-metadata.3"):
        assert re.search(pattern, name)
    for name in ("fusion.12", "copy.3", "lstm_fwd.22", "dot.4"):
        assert not re.search(pattern, name)


def test_lstm_metrics_are_listed_for_the_cells_that_run_an_lstm():
    for m in cells.benchmark_json()["per_layer"]:
        if m["layer"] == "lstm kernels":
            assert m["workloads"] == ["icalstm-hcp32.dsgd", "icalstm-hcp32.rankdad"]
    assert not [m["name"] for m in cells.load_cell(CELL).per_layer
                if m["layer"] == "lstm kernels"]


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_configuration_holds_the_published_numbers_apart_from_the_cuts():
    cell = cells.load_cell(CELL)
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "Trinity-Mini")
    assert cell.config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cell.config["reduced"]:
            assert cell.config[key] != value
            assert cell.config["published"][key] == value
        else:
            assert cell.config[key] == value, key


def test_the_run_configuration_is_the_published_one_cut_as_stated():
    from benchmarks.drivers import train
    from dinunet_implementations_tpu.core.config import AFMoEArgs

    cell = cells.load_cell(CELL)
    cfg, _, model = train.configure(cell)
    a, published = cfg.lm_args, AFMoEArgs()
    cut = {"seq_len", "vocab_rows", "experts_held", "num_hidden_layers",
           "num_dense_layers", "layer_types", "compute_dtype"}
    for f in dataclasses.fields(a):
        if f.name not in cut:
            assert getattr(a, f.name) == getattr(published, f.name), f.name
    assert (a.experts_held, a.vocab_rows, a.num_hidden_layers) == (
        cell.config["num_experts"], cell.config["vocab_size"],
        cell.config["num_hidden_layers"])
    kept = cell.config["published"]["kept_layers"]
    assert list(a.layer_types) == [cell.config["layer_types"][i] for i in kept]
    assert a.num_experts == 128 and a.num_experts_per_tok == 8
    assert model.dims.experts_held == 8 and model.vocab_rows == 25024
    assert cell.data_spec(None)["vocab_rows"] == a.vocab_rows


def test_flops_of_the_cell_are_the_issues_count():
    from benchmarks.drivers import train
    from benchmarks.flops import afmoe as flops

    cfg, _, _ = train.configure(cells.load_cell(CELL))
    parts = flops.forward_flops_per_sequence(cfg)
    per_token = sum(parts.values()) / cfg.lm_args.seq_len
    assert 712e6 < per_token < 714e6
    assert abs(flops.train_flops_per_sample(cfg) - 17.5e12) < 0.1e12


def _toy():
    kinds = ("sliding_attention",) * 4 + ("full_attention",)
    dims = ref.Dims(num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                    sliding_window=8, layer_types=kinds, num_dense_layers=1,
                    num_experts_per_tok=4, first_expert=4, q_block=8,
                    head_block=8)
    h, f, v, e = 32, 16, 48, 16
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 200))
    mat = lambda *shape: 0.3 * jax.random.normal(next(keys), shape)
    norm = lambda n: {"scale": 1.0 + 0.1 * jax.random.normal(next(keys), (n,))}
    params = {"embed": mat(v, h), "final_norm": norm(h)["scale"],
              "lm_head": mat(h, v)}
    for i in range(len(kinds)):
        layer = {"input_norm": norm(h), "post_attn_norm": norm(h),
                 "pre_mlp_norm": norm(h), "post_mlp_norm": norm(h),
                 "attn": {"wq": mat(h, 64), "wk": mat(h, 32), "wv": mat(h, 32),
                          "wg": mat(h, 64), "wo": mat(64, h),
                          "q_norm": norm(16), "k_norm": norm(16)}}
        if i < 1:
            layer["mlp"] = {"w1": mat(h, 24), "w3": mat(h, 24), "w2": mat(24, h)}
        else:
            layer["moe"] = {
                "router": mat(h, e), "expert_bias": jnp.zeros((e,)),
                "w1": mat(4, h, f), "w3": mat(4, h, f), "w2": mat(4, f, h),
                "shared": {"w1": mat(h, f), "w3": mat(h, f), "w2": mat(f, h)}}
        params[f"layer_{i}"] = layer
    sample = jax.random.randint(next(keys), (33,), 0, v)
    return params, sample, dims


def test_reference_gradient_chain_is_the_gradient_of_its_loss():
    params, sample, dims = _toy()
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(
            lambda p: ref.loss(p, sample, dims))(params)
        loss, got = ref.grads(params, sample, dims)
        logits = ref.logits(params, sample[:-1], dims)
        whole = ref.forward(params, sample[:-1], dims)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert float(jnp.abs(logits - whole).max()) < 1e-5
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert float(jnp.abs(g - w).max()) <= 1e-4 * max(
            float(jnp.abs(w).max()), 1e-3), jax.tree_util.keystr(path)


def test_reference_imports_nothing_from_the_package():
    src = open(os.path.join(cells.HERE, "reference", "afmoe.py")).read()
    assert "dinunet_implementations_tpu" not in src.split('"""', 2)[2]
