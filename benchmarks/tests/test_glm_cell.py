"""The latent-attention next-token cell (ISSUE 32): its files agree with each
other and with the published configuration, its FLOP and kernel counts are
the issue's and the program's, the rehearsal ends in the contract's last line,
and the reference's stage-by-stage gradient chain (the second prediction
depth included) is its own loss's gradient."""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from benchmarks.lib import cells
from benchmarks.reference import glm4_moe_lite as ref
from benchmarks.tests.test_harness import check_last_line, run_cell

CELL = "glm-4.7-flash-ep8.dsgd-fold2"
CONFIG = "glm-4.7-flash-ep8"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "num_nextn_predict_layers"]
NEW_METRICS = {
    "mla_attention_kernel_ms_per_round": {"fwd", "dq", "dkv"},
    "mla_attention_kernel_roofline": {"fwd", "dq", "dkv"},
    "mla_attention_fwd_kernel_ms_per_round": {"fwd"},
    "mla_attention_dq_kernel_ms_per_round": {"dq"},
    "mla_attention_dkv_kernel_ms_per_round": {"dkv"},
}
#: instruction texts as a v5e trace of the cell carries them (my chip runs, PR 32)
CHIP_LINES = {
    "fwd": '%splash_mqa_fwd_residuals.50 = (f32[2,20,512,128]{3,2,1,0:T(8,128)}, '
           'bf16[2,20,1,8192,256]{4,3,2,1,0:T(8,128)(2,1)}) custom-call('
           's8[1,16,16]{2,1,0:T(8,128)(4,1)S(1)} %copy-done.851), '
           'custom_call_target="tpu_custom_call"',
    "dq": '%splash_mqa_dq_no_residuals.20 = (f32[2,20,512,256]{3,2,1,0:T(8,128)}, '
          'bf16[2,20,1,8192,256]{4,3,2,1,0:T(8,128)(2,1)}) custom-call('
          's8[1,16,16]{2,1,0:T(8,128)(4,1)S(1)} %copy-done.856), '
          'custom_call_target="tpu_custom_call"',
    "dkv": '%splash_mqa_dkv_no_residuals.20 = (f32[2,20,512,256]{3,2,1,0:T(8,128)}, '
           'bf16[2,20,8192,256]{3,2,1,0:T(8,128)(2,1)}) custom-call('
           's8[1,16,16]{2,1,0:T(8,128)(4,1)S(1)} %copy-done.836), '
           'custom_call_target="tpu_custom_call"',
    "ragged": '%ragged-dot-none.1 = f32[8192,1536]{1,0:T(8,128)S(1)} custom-call('
              's32[1]{0:T(128)} %get-tuple-element.8850), '
              'custom_call_target="tpu_custom_call"',
}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _configured():
    from benchmarks.drivers import train

    return train.configure(cells.load_cell(CELL))


def test_benchmark_json_lists_the_configuration_and_its_one_cell():
    bench = cells.benchmark_json()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED
    mine = [w for w in bench["workloads"] if w["config"] == CONFIG]
    assert [(w["name"], w["traffic"], w["chips"]) for w in mine] == [
        (CELL, "dsgd-fold2", 1)]
    assert len(mine[0]["why"]) <= 200
    # the accepted attention_* and moe_* lists stay Trinity's
    for m in bench["per_layer"]:
        if m["name"].startswith(("attention_", "moe_")):
            assert m["workloads"] == ["trinity-mini-ep16.dsgd-fold2"]


@pytest.mark.parametrize("trace", ["0"])  # traced: minutes of CPU, by hand
def test_rehearsal_of_the_new_cell_prints_the_contracts_last_line(trace):
    out = run_cell("--workload", CELL, "--seed", "3000000001", "--seconds", "1",
                   "--trace", trace, "--rehearse", "tiny")
    check_last_line(out, trace == "1")
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.startswith("{")]
    check = next(l["reference_check"] for l in lines if "reference_check" in l)
    assert check["ok"] and check["rounds"] == 4 and check["sites"] == 2
    assert any("held_assignments_per_token" in l for l in lines)
    # the rehearsal carries the second prediction depth: six blocks' kernels
    facts = next(l["facts"] for l in lines if "facts" in l)
    assert [c["count"] for c in facts["kernel_model"]["calls"]] == [6, 6, 6]


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_file_agrees_with_its_per_layer_entry(name):
    entry = next(m for m in cells.benchmark_json()["per_layer"]
                 if m["name"] == name)
    spec = cells.layer_metric(name)
    for key in ("name", "unit", "better", "source", "layer", "moves", "workloads"):
        assert spec[key] == entry[key], key
    assert entry["workloads"] == [CELL] and entry["layer"] == "attention kernels"
    assert name in {m["name"] for m in cells.load_cell(CELL).per_layer}
    assert entry["layer"] in open(os.path.join(cells.ROOT, "PERF.md")).read()


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_pattern_matches_its_chip_lines_and_no_other(metric):
    pattern = cells.layer_metric(metric)["args"]["pattern"]
    hits = {k for k, line in CHIP_LINES.items() if re.search(pattern, line)}
    assert hits == NEW_METRICS[metric]
    # and it is the accepted attention_* file's pattern, letter for letter
    old = cells.layer_metric(metric[len("mla_"):])
    assert old["args"] == cells.layer_metric(metric)["args"]
    assert old["reader"] == cells.layer_metric(metric)["reader"]


def test_the_cell_reports_every_metric_without_a_workloads_list():
    mine = {m["name"] for m in cells.load_cell(CELL).per_layer}
    for m in cells.benchmark_json()["per_layer"]:
        if "workloads" not in m:
            assert m["name"] in mine
    assert not [n for n in mine if n.startswith(("lstm_", "attention_", "moe_"))]


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_configuration_holds_the_published_numbers_apart_from_the_cuts():
    cell = cells.load_cell(CELL)
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "GLM-4.7-Flash")
    assert cell.config["source"] == row["source_url"]
    assert cell.config["reduced"] == REDUCED
    for key, value in row["config"].items():
        if key in cell.config["reduced"]:
            assert cell.config[key] != value
            assert cell.config["published"][key] == value
        else:
            assert cell.config[key] == value, key
    assert cell.config["published"]["kept_layers"] == [0, 1, 2, 3, 4]
    assert "8-chip" in cell.config["deployment"]


def test_the_run_configuration_is_the_published_one_cut_as_stated():
    cell = cells.load_cell(CELL)
    cfg, _, model = _configured()
    a, c = cfg.lm_args, cell.config
    assert a.model_type == c["model_type"] == "glm4_moe_lite"
    # every width as published, under the program's names
    for ours, theirs in [
            ("hidden_size", "hidden_size"), ("intermediate_size", "intermediate_size"),
            ("moe_intermediate_size", "moe_intermediate_size"),
            ("num_attention_heads", "num_attention_heads"),
            ("num_key_value_heads", "num_key_value_heads"),
            ("q_lora_rank", "q_lora_rank"), ("kv_lora_rank", "kv_lora_rank"),
            ("qk_nope_head_dim", "qk_nope_head_dim"),
            ("qk_rope_head_dim", "qk_rope_head_dim"), ("v_head_dim", "v_head_dim"),
            ("num_experts_per_tok", "num_experts_per_tok"),
            ("num_shared_experts", "n_shared_experts"),
            ("num_dense_layers", "first_k_dense_replace"),
            ("route_norm", "norm_topk_prob"), ("route_scale", "routed_scaling_factor"),
            ("rope_theta", "rope_theta"), ("rms_norm_eps", "rms_norm_eps")]:
        assert getattr(a, ours) == c[theirs], ours
    # the cuts: the held share under the published counts
    assert (a.num_experts, a.experts_held, a.first_expert) == (
        c["published"]["n_routed_experts"], c["n_routed_experts"], 0) == (64, 8, 0)
    assert (a.vocab_size, a.vocab_rows) == (
        c["published"]["vocab_size"], c["vocab_size"]) == (154880, 19360)
    assert a.num_hidden_layers == c["num_hidden_layers"] == 5
    assert a.num_nextn_predict_layers == c["num_nextn_predict_layers"] == 0
    assert (a.seq_len, cfg.batch_size, cfg.num_sites) == (8192, 1, 2)
    # no head_dim in config.json: a program that drops the keys it does not
    # know cannot build the other family's attention from the rest
    assert a.head_dim == 0 and "head_dim" not in c
    assert model.dims.experts_held == 8 and model.vocab_rows == 19360
    assert model.dims.model_type == "glm4_moe_lite" and not model.mup_enabled
    assert cell.data_spec(None)["vocab_rows"] == a.vocab_rows
    # what a chip holds: the issue's table
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 9), jnp.int32), train=True))["params"]
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))
    assert count(shapes["layer_0"]["attn"]) == 21_759_232
    assert count(shapes["layer_0"]) == 84_677_888
    assert count(shapes["layer_1"]) == 106_829_120
    assert count(shapes) == 591_294_976


def test_flops_of_the_cell_are_the_issues_count():
    from benchmarks.flops import glm4_moe_lite as flops

    cfg, _, _ = _configured()
    parts = flops.forward_flops_per_sequence(cfg)
    t = cfg.lm_args.seq_len
    assert parts["projections"] / t / 5 == pytest.approx(43.5e6, rel=2e-3)
    assert parts["attention"] / t == pytest.approx(419e6, rel=2e-3)
    assert (sum(parts.values()) - parts["attention"]) / t == pytest.approx(
        537e6, rel=2e-3)
    assert sum(parts.values()) / t == pytest.approx(956e6, rel=2e-3)
    assert flops.train_flops_per_sample(cfg) == pytest.approx(23.5e12, rel=2e-3)
    assert parts["next_depth_projection"] == 0
    # with the second depth: one more block, head and projection
    deeper = cfg.with_overrides({"lm_args": {"num_nextn_predict_layers": 1}})
    more = flops.forward_flops_per_sequence(deeper)
    assert more["head"] == 2 * parts["head"]
    assert more["projections"] * 5 == parts["projections"] * 6
    assert more["next_depth_projection"] == t * 2 * 4096 * 2048


def test_kernel_model_counts_the_calls_the_program_makes():
    from benchmarks.flops import glm4_moe_lite as flops
    from benchmarks.trace.readers.roofline_share import least_seconds

    cfg, _, _ = _configured()
    model = flops.kernel_model(cfg, 2)
    assert [c["count"] for c in model["calls"]] == [5, 5, 5]  # forward ONCE
    fwd = flops.forward_flops_per_sequence(cfg)["attention"]
    assert model["flops"] == pytest.approx(2 * fwd * (1 + 1.5 + 2.0))
    assert model["flops"] == pytest.approx(30.9e12, rel=2e-3)
    peak = cells.peaks()["TPU v5 lite"]
    assert least_seconds(model, peak) == pytest.approx(0.157, rel=5e-3)
    for call in model["calls"]:  # the flops bound applies to every call
        assert call["flops"] / peak["bf16_flops_per_s"] > (
            call["bytes"] / peak["hbm_bytes_per_s"])


def _toy(depths: int):
    dims = ref.Dims(num_attention_heads=4, q_lora_rank=12, kv_lora_rank=8,
                    qk_nope_head_dim=6, qk_rope_head_dim=4, v_head_dim=10,
                    num_dense_layers=1, num_experts_per_tok=4, first_expert=4,
                    num_nextn_predict_layers=depths, q_block=8, head_block=8)
    h, f, v, e = 32, 16, 48, 16
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 200))
    mat = lambda *shape: 0.3 * jax.random.normal(next(keys), shape)
    norm = lambda n: {"scale": 1.0 + 0.1 * jax.random.normal(next(keys), (n,))}

    def layer(dense: bool):
        out = {"input_norm": norm(h), "pre_mlp_norm": norm(h),
               "attn": {"wq_a": mat(h, 12), "q_a_norm": norm(12),
                        "wq_b": mat(12, 40), "wkv_a": mat(h, 12),
                        "kv_a_norm": norm(8), "wkv_b": mat(8, 64),
                        "wo": mat(40, h)}}
        if dense:
            out["mlp"] = {"w1": mat(h, 24), "w3": mat(h, 24), "w2": mat(24, h)}
        else:
            out["moe"] = {
                "router": mat(h, e), "expert_bias": jnp.zeros((e,)),
                "w1": mat(4, h, f), "w3": mat(4, h, f), "w2": mat(4, f, h),
                "shared": {"w1": mat(h, f), "w3": mat(h, f), "w2": mat(f, h)}}
        return out

    params = {"embed": mat(v, h), "final_norm": norm(h)["scale"],
              "lm_head": mat(h, v), "layer_0": layer(True),
              "layer_1": layer(False), "layer_2": layer(False)}
    if depths:
        params["mtp"] = {"enorm": norm(h), "hnorm": norm(h),
                         "eh_proj": mat(2 * h, h), "block": layer(False),
                         "norm": norm(h)["scale"]}
    sample = jax.random.randint(next(keys), (33,), 0, v)
    return params, sample, dims


@pytest.mark.parametrize("depths", [0, 1])
def test_reference_gradient_chain_is_the_gradient_of_its_loss(depths):
    params, sample, dims = _toy(depths)
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(
            lambda p: ref.loss(p, sample, dims))(params)
        loss, got = ref.grads(params, sample, dims)
        logits = ref.logits(params, sample[:-1], dims)
        whole = ref.forward(params, sample[:-1], dims)
        main, deeper = ref.losses(params, sample, dims)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert abs(float(main + dims.mtp_loss_weight * deeper) - float(loss)) < 1e-5
    assert (float(deeper) > 1.0) == bool(depths)
    assert float(jnp.abs(logits - whole).max()) < 1e-5
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert float(jnp.abs(g - w).max()) <= 1e-4 * max(
            float(jnp.abs(w).max()), 1e-3), jax.tree_util.keystr(path)


def test_reference_dims_take_the_programs_argument_names():
    from dinunet_implementations_tpu.core.config import AFMoEArgs

    args = {f.name for f in dataclasses.fields(AFMoEArgs)}
    own = {"q_block", "head_block"}  # the reference's own blocking
    assert {f.name for f in dataclasses.fields(ref.Dims)} - own <= args


def test_reference_imports_nothing_from_the_package():
    src = open(os.path.join(cells.HERE, "reference", "glm4_moe_lite.py")).read()
    code = src.split('"""', 2)[2]
    assert "dinunet_implementations_tpu" not in code
    assert "pallas" not in code and "checkpoint" not in code
    imports = re.findall(r"^(?:from|import) (\S+)", code, re.M)
    assert set(imports) <= {"__future__", "dataclasses", "functools", "math",
                            "jax", "jax.numpy", "benchmarks.reference.afmoe"}
