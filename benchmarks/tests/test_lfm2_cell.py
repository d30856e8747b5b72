"""The lfm2_moe next-token cell (ISSUE 38): its files agree with each other and
with the published configuration, the program's parameter count is the file's
arithmetic, the pair count of ``flops/lfm2_moe.py`` is a brute-force mask
count, every new metric file names a reader that exists and the new cell only,
and the reference's stage-by-stage gradient chain is its own loss's gradient."""

import dataclasses
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells
from benchmarks.reference import lfm2_moe as ref

CELL = "lfm2-8b-a1b-ep4.dsgd-fold2"
CONFIG = "lfm2-8b-a1b-ep4"
REDUCED = ["num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
KEPT = ["conv", "full_attention", "conv", "conv", "conv"]
#: the accepted file each new metric file copies reader and pattern from, and
#: the kernels (or instruction) its pattern reads
NEW_METRICS = {
    "lfm2_attention_kernel_ms_per_round": (
        "attention_kernel_ms_per_round", {"fwd", "dq", "dkv"}),
    "lfm2_attention_kernel_roofline": (
        "attention_kernel_roofline", {"fwd", "dq", "dkv"}),
    "lfm2_attention_fwd_kernel_ms_per_round": (
        "attention_fwd_kernel_ms_per_round", {"fwd"}),
    "lfm2_attention_dq_kernel_ms_per_round": (
        "attention_dq_kernel_ms_per_round", {"dq"}),
    "lfm2_attention_dkv_kernel_ms_per_round": (
        "attention_dkv_kernel_ms_per_round", {"dkv"}),
    "lfm2_moe_grouped_matmul_ms_per_round": (
        "moe_grouped_matmul_ms_per_round", {"ragged"}),
    "lfm2_moe_expert_load_max_over_mean": (
        "moe_expert_load_max_over_mean", set()),
}
#: instruction texts and names as a v5e trace of this cell carries them (my
#: chip runs, PR 38): the kernels' operands are 64 lanes wide
CHIP_LINES = {
    "fwd": '%splash_mqa_fwd_residuals.5 = (f32[2,8,4,1024,128]{4,3,2,1,0:T(8,128)}, '
           'bf16[2,8,4,8192,64]{4,3,2,1,0:T(8,128)(2,1)}) custom-call('
           's8[1,8,8]{2,1,0:T(8,128)(4,1)S(1)} %copy-done.85), '
           'custom_call_target="tpu_custom_call"',
    "dq": '%splash_mqa_dq_no_residuals.2 = (f32[2,8,4,1024,128]{4,3,2,1,0:T(8,128)}, '
          'bf16[2,8,4,8192,64]{4,3,2,1,0:T(8,128)(2,1)}) custom-call('
          's8[1,8,8]{2,1,0:T(8,128)(4,1)S(1)} %copy-done.86), '
          'custom_call_target="tpu_custom_call"',
    "dkv": '%splash_mqa_dkv_no_residuals.2 = (f32[2,8,1024,128]{3,2,1,0:T(8,128)}, '
           'bf16[2,8,8192,64]{3,2,1,0:T(8,128)(2,1)}) custom-call('
           's8[1,8,8]{2,1,0:T(8,128)(4,1)S(1)} %copy-done.83), '
           'custom_call_target="tpu_custom_call"',
    "ragged": 'ragged-dot-none.1',
}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _configured():
    from benchmarks.drivers import train

    return train.configure(cells.load_cell(CELL))


def test_benchmark_json_lists_the_configuration_and_its_one_cell():
    bench = cells.benchmark_json()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    mine = [w for w in bench["workloads"] if w["config"] == CONFIG]
    assert [(w["name"], w["traffic"], w["chips"]) for w in mine] == [
        (CELL, "dsgd-fold2", 1)]
    assert len(mine[0]["why"]) <= 200
    cell = cells.load_cell(CELL)
    assert cell.traffic["kind"] == "train_lm" and cell.facts["loss_band"]["rounds"] == 8
    # the traffic file is the accepted one, shared with two accepted cells
    assert sorted(w["name"] for w in bench["workloads"]
                  if w["traffic"] == "dsgd-fold2") == sorted([
                      CELL, "trinity-mini-ep16.dsgd-fold2",
                      "glm-4.7-flash-ep8.dsgd-fold2"])


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_file_names_a_reader_that_exists_and_the_new_cell_only(name):
    entry = next(m for m in cells.benchmark_json()["per_layer"]
                 if m["name"] == name)
    spec = cells.layer_metric(name)
    for key in ("name", "unit", "better", "source", "layer", "moves", "workloads"):
        assert spec[key] == entry[key], key
    assert entry["workloads"] == [CELL] and entry["moves"] == "train_samples_per_s"
    reader = importlib.import_module("benchmarks.trace.readers." + spec["reader"])
    assert callable(reader.read)
    assert name in {m["name"] for m in cells.load_cell(CELL).per_layer}
    assert entry["layer"] in open(os.path.join(cells.ROOT, "PERF.md")).read()
    # reader and pattern are the accepted file's, letter for letter
    old = cells.layer_metric(NEW_METRICS[name][0])
    assert (old["reader"], old["args"]) == (spec["reader"], spec["args"])
    assert old["workloads"] == ["trinity-mini-ep16.dsgd-fold2"]
    if "pattern" in spec["args"]:
        field = spec["args"].get("field", "text")
        assert field == ("name" if NEW_METRICS[name][1] == {"ragged"} else "text")
        hits = {k for k, line in CHIP_LINES.items()
                if re.search(spec["args"]["pattern"], line)}
        assert hits == NEW_METRICS[name][1]


def test_the_short_convolutions_metric_reads_its_own_instruction_name():
    """The one new metric with a pattern of its own: the accepted reader over
    the instruction NAME the operator's shift-and-multiply passes compile to
    at this cell's shapes (the traced run of seed 3800000041, PR 38), told
    from XLA's rotary, from the gates' other fusions and from longer names."""
    name = "lfm2_short_conv_ms_per_round"
    entry = next(m for m in cells.benchmark_json()["per_layer"] if m["name"] == name)
    spec = cells.layer_metric(name)
    for key in ("name", "unit", "better", "source", "layer", "moves", "workloads"):
        assert spec[key] == entry[key], key
    assert entry["workloads"] == [CELL] and entry["layer"] == "epoch program"
    accepted = cells.layer_metric("rotary_slice_negate_ms_per_round")
    assert spec["reader"] == accepted["reader"] == "device_ops_matching"
    assert {k: v for k, v in spec["args"].items() if k != "pattern"} == {
        k: v for k, v in accepted["args"].items() if k != "pattern"}
    pattern = spec["args"]["pattern"]
    for hit in ("slice_multiply_fusion", "slice_multiply_fusion.52"):
        assert re.search(pattern, hit)
    for miss in ("slice_negate_fusion.17", "broadcast_multiply_fusion.19",
                 "bitcast_multiply_fusion.37", "slice_multiply_fusion.5.clone",
                 "pad_slice_multiply_fusion", "convolution_bitcast_fusion.20"):
        assert not re.search(pattern, miss)
    assert "3800000041" in spec["what"] and "short_conv" in spec["what"]
    assert name in {m["name"] for m in cells.load_cell(CELL).per_layer}


def test_the_cell_reports_every_metric_without_a_workloads_list():
    mine = {m["name"] for m in cells.load_cell(CELL).per_layer}
    for m in cells.benchmark_json()["per_layer"]:
        if "workloads" not in m:
            assert m["name"] in mine
        elif not m["name"].startswith("lfm2_"):
            assert CELL not in m["workloads"]  # the accepted lists are as they were
    assert mine >= set(NEW_METRICS)
    assert not [n for n in mine if n.startswith((
        "lstm_", "attention_", "moe_", "mla_", "smallthinker_", "rotary_"))]


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_configuration_holds_the_published_numbers_apart_from_the_cuts():
    cell = cells.load_cell(CELL)
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "LFM2-8B-A1B")
    assert cell.config["source"] == row["source_url"]
    assert cell.config["reduced"] == REDUCED
    for key, value in row["config"].items():
        if key in cell.config["reduced"]:
            assert cell.config[key] != value
            assert cell.config["published"][key] == value
        else:
            assert cell.config[key] == value, key  # the list of 24 kinds whole
    kept = cell.config["published"]["kept_layers"]
    assert kept == [0, 2, 3, 4, 5]
    assert [row["config"]["layer_types"][i] for i in kept] == KEPT
    assert "4-chip" in cell.config["deployment"]
    for key in ("tie_word_embeddings", "dense_mlp", "short_conv", "attention",
                "rotary", "routing", "left_out", "initialisation",
                "compute_dtype", "optimizer", "data"):
        assert cell.config["assumed"][key], key


def test_the_run_configuration_is_the_published_one_cut_as_stated():
    cell = cells.load_cell(CELL)
    cfg, _, model = _configured()
    a, c = cfg.lm_args, cell.config
    assert a.model_type == c["model_type"] == "lfm2_moe"
    # every width as published, under the program's names
    for ours, theirs in [
            ("hidden_size", "hidden_size"),
            ("intermediate_size", "intermediate_size"),
            ("moe_intermediate_size", "moe_intermediate_size"),
            ("num_attention_heads", "num_attention_heads"),
            ("num_key_value_heads", "num_key_value_heads"),
            ("num_experts_per_tok", "num_experts_per_tok"),
            ("conv_L_cache", "conv_L_cache"), ("rope_theta", "rope_theta"),
            ("rms_norm_eps", "norm_eps"), ("route_norm", "norm_topk_prob"),
            ("route_scale", "routed_scaling_factor")]:
        assert getattr(a, ours) == c[theirs], ours
    assert a.head_dim * a.num_attention_heads == a.hidden_size and a.head_dim == 64
    # the cuts: the held share under the published counts
    assert (a.num_experts, a.experts_held, a.first_expert) == (
        c["published"]["num_experts"], c["num_experts"], 0) == (32, 8, 0)
    assert (a.vocab_size, a.vocab_rows) == (
        c["published"]["vocab_size"], c["vocab_size"]) == (65536, 16384)
    assert a.vocab_size == 4 * a.vocab_rows
    assert a.num_hidden_layers == c["num_hidden_layers"] == 5
    assert (a.num_dense_layers, a.num_shared_experts) == (1, 0)
    assert list(a.layer_types) == KEPT == [
        c["layer_types"][i] for i in c["published"]["kept_layers"]]
    assert a.tie_word_embeddings and a.seq_len == 8192
    assert (cfg.batch_size, cfg.num_sites) == (1, 2)
    assert model.dims.experts_held == 8 and model.vocab_rows == 16384
    assert model.dims.model_type == "lfm2_moe" and not model.mup_enabled
    assert model.dims.layer_types == tuple(KEPT) and model.tie_word_embeddings
    assert cell.data_spec(None)["vocab_rows"] == a.vocab_rows
    # what a chip holds: the issue's table and the file's arithmetic
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 9), jnp.int32), train=True))["params"]
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))
    bias = 32  # the expert_bias buffer of an expert layer: in the tree, no parameter
    assert "lm_head" not in shapes
    assert count(shapes["layer_0"]["attn"]) == 16_783_360
    assert count(shapes["layer_1"]["attn"]) == 10_485_888
    assert count(shapes["layer_0"]) == 60_827_648
    assert count(shapes["layer_1"]) - bias == 98_635_904
    assert count(shapes["layer_2"]) - bias == 104_933_376
    assert count(shapes["layer_1"]["moe"]) - bias - 65_536 == 8 * 11_010_048
    assert count(shapes["embed"]) == 33_554_432
    held = c["published"]["parameters_held"]
    assert count(shapes) - 4 * bias == held["total"] == 507_820_160
    for line in (v for v in held.values() if isinstance(v, str)):
        said = int(line.rsplit("= ", 1)[1].replace(",", "")) \
            if "=" in line and ";" not in line else None
        if said:  # "a x b + c x d = n": the sum is n
            terms = line.rsplit(" = ", 1)[0].split(" + ")
            assert sum(int(np.prod([int(f.replace(",", "")) for f in
                                    re.findall(r"[\d,]+", t)])) for t in terms
                       ) == said, line


@pytest.mark.parametrize("t", [1, 48, 64, 96])
def test_pair_count_is_a_brute_force_mask_count(t):
    from benchmarks.flops import lfm2_moe as flops

    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    assert flops.causal_pairs(t) == int((j <= i).sum())


def test_flops_and_kernel_model_of_the_cell_are_the_issues_count():
    from benchmarks.flops import lfm2_moe as flops
    from benchmarks.trace.readers.roofline_share import least_seconds

    cfg, _, _ = _configured()
    a = cfg.lm_args
    assert flops.causal_pairs(a.seq_len) == 33_558_528
    assert flops.attention_layers(a) == 1
    parts = flops.forward_flops_per_sequence(cfg)
    assert set(parts) == {"short_conv", "projections", "attention", "dense_mlp",
                          "router", "routed_experts", "head"}
    assert parts["short_conv"] / a.seq_len == 4 * 2 * 2048 * 4 * 2048
    assert parts["routed_experts"] / (4 * a.seq_len) == pytest.approx(
        1.0 * 3 * 2 * 2048 * 1792)  # 1.0 held assignment a token
    assert parts["dense_mlp"] / a.seq_len == 3 * 2 * 2048 * 7168
    assert parts["attention"] / a.seq_len == 33_558_528  # 32 heads x 4 x 64 = T
    assert sum(parts.values()) / a.seq_len == pytest.approx(432.5e6, rel=1e-3)
    assert flops.train_flops_per_sample(cfg) == pytest.approx(10.63e12, rel=1e-3)
    model = flops.kernel_model(cfg, 2)
    assert [c["count"] for c in model["calls"]] == [1, 1, 1]  # ONE layer, forward once
    assert model["flops"] == pytest.approx(2 * parts["attention"] * (1 + 1.5 + 2.0))
    # 4 d, 6 d and 8 d a pair and head at d = 64 AS PUBLISHED
    assert model["flops"] == 2 * 33_558_528 * 32 * 18 * 64
    peak = cells.peaks()["TPU v5 lite"]
    assert least_seconds(model, peak) == pytest.approx(0.01256, rel=5e-3)
    for call in model["calls"]:  # the flops bound applies to every call
        assert call["flops"] / peak["bf16_flops_per_s"] > (
            call["bytes"] / peak["hbm_bytes_per_s"])


def _toy():
    dims = ref.Dims(num_attention_heads=4, num_key_value_heads=2, head_dim=8,
                    layer_types=("conv", "full_attention", "conv"),
                    num_dense_layers=1, num_experts_per_tok=3, first_expert=4,
                    q_block=8, head_block=8)
    h, f, v, e = 32, 16, 48, 16
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 200))
    mat = lambda *shape: 0.3 * jax.random.normal(next(keys), shape)
    norm = lambda n: {"scale": 1.0 + 0.1 * jax.random.normal(next(keys), (n,))}

    def layer(kind, dense):
        attn = ({"w_in": mat(h, 3 * h), "filter": mat(h, 3), "w_out": mat(h, h)}
                if kind == "conv" else
                {"wq": mat(h, 32), "wk": mat(h, 16), "wv": mat(h, 16),
                 "wo": mat(32, h), "q_norm": norm(8), "k_norm": norm(8)})
        ffn = ({"mlp": {"w1": mat(h, 24), "w3": mat(h, 24), "w2": mat(24, h)}}
               if dense else
               {"moe": {"router": mat(h, e), "expert_bias": jnp.zeros((e,)),
                        "w1": mat(4, h, f), "w3": mat(4, h, f), "w2": mat(4, f, h)}})
        return {"input_norm": norm(h), "pre_mlp_norm": norm(h), "attn": attn, **ffn}

    params = {"embed": mat(v, h), "final_norm": norm(h)["scale"],
              **{f"layer_{i}": layer(kind, i == 0)
                 for i, kind in enumerate(dims.layer_types)}}
    sample = jax.random.randint(next(keys), (41,), 0, v)
    return params, sample, dims


def test_reference_gradient_chain_is_the_gradient_of_its_loss():
    params, sample, dims = _toy()
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(
            lambda p: ref.loss(p, sample, dims))(params)
        loss, got = ref.grads(params, sample, dims)
        logits = ref.logits(params, sample[:-1], dims)
        whole = ref.forward(params, sample[:-1], dims)
        # and the blocks are no approximation: one block over all keys
        one = ref.forward(params, sample[:-1], dataclasses.replace(dims, q_block=40))
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert float(jnp.abs(logits - whole).max()) < 1e-5
    assert float(jnp.abs(one - whole).max()) < 1e-4
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert float(jnp.abs(g - w).max()) <= 1e-4 * max(
            float(jnp.abs(w).max()), 1e-3), jax.tree_util.keystr(path)
    assert float(jnp.abs(want["layer_2"]["attn"]["filter"]).max()) > 1e-5
    assert float(jnp.abs(want["layer_1"]["moe"]["router"]).max()) > 1e-5


def test_the_references_convolution_is_the_equation_position_by_position():
    params, _, dims = _toy()
    p = params["layer_2"]["attn"]
    a = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (11, 32)), np.float64)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.short_conv(p, jnp.asarray(a, jnp.float32), dims))
    bcx = a @ np.asarray(p["w_in"], np.float64)
    b, c, x = bcx[:, :32], bcx[:, 32:64], bcx[:, 64:]
    u, f = b * x, np.asarray(p["filter"], np.float64)
    want = np.stack([
        (c[t] * sum(f[:, j] * u[t - 2 + j] for j in range(3) if t - 2 + j >= 0))
        @ np.asarray(p["w_out"], np.float64) for t in range(11)])
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


def test_reference_dims_take_the_programs_argument_names():
    from dinunet_implementations_tpu.core.config import AFMoEArgs

    args = {f.name for f in dataclasses.fields(AFMoEArgs)}
    own = {"q_block", "head_block"}  # the reference's own blocking
    assert {f.name for f in dataclasses.fields(ref.Dims)} - own <= args


def test_reference_imports_nothing_from_the_package():
    src = open(os.path.join(cells.HERE, "reference", "lfm2_moe.py")).read()
    code = src.split('"""', 2)[2]
    assert "dinunet_implementations_tpu" not in code
    assert "pallas" not in code and "checkpoint" not in code and "vmap" not in code
    assert "ragged" not in code and "lm_head" not in code
    imports = re.findall(r"^(?:from|import) (\S+)", code, re.M)
    assert set(imports) <= {"__future__", "dataclasses", "functools", "math",
                            "jax", "jax.numpy", "benchmarks.reference.afmoe"}
