"""The smallthinker next-token cell (ISSUE 34): its files agree with each other
and with the published configuration, the program's parameter count is the
file's arithmetic, the pair counts of ``flops/smallthinker.py`` are a
brute-force mask count, every new metric file names a reader that exists and
the new cell only, and the reference's stage-by-stage gradient chain is its
own loss's gradient."""

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
from benchmarks.reference import smallthinker as ref

CELL = "smallthinker-21b-ep8.dsgd-fold2-long"
CONFIG = "smallthinker-21b-ep8"
REDUCED = ["num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
#: the accepted file each new metric file copies reader and pattern from, and
#: the kernels (or instruction) its pattern reads
NEW_METRICS = {
    "smallthinker_attention_kernel_ms_per_round": (
        "attention_kernel_ms_per_round", {"fwd", "dq", "dkv"}),
    "smallthinker_attention_kernel_roofline": (
        "attention_kernel_roofline", {"fwd", "dq", "dkv"}),
    "smallthinker_attention_fwd_kernel_ms_per_round": (
        "attention_fwd_kernel_ms_per_round", {"fwd"}),
    "smallthinker_attention_dq_kernel_ms_per_round": (
        "attention_dq_kernel_ms_per_round", {"dq"}),
    "smallthinker_attention_dkv_kernel_ms_per_round": (
        "attention_dkv_kernel_ms_per_round", {"dkv"}),
    "smallthinker_moe_grouped_matmul_ms_per_round": (
        "moe_grouped_matmul_ms_per_round", {"ragged"}),
    "smallthinker_moe_expert_load_max_over_mean": (
        "moe_expert_load_max_over_mean", set()),
}
#: instruction texts and names as a v5e trace of the language-model cells
#: carries them (my chip runs, PRs 32 and 34)
CHIP_LINES = {
    "fwd": '%splash_mqa_fwd_residuals.50 = (f32[2,4,7,1024,128]{4,3,2,1,0:T(8,128)}, '
           'bf16[2,4,7,16384,128]{4,3,2,1,0:T(8,128)(2,1)}) custom-call('
           's8[1,16,16]{2,1,0:T(8,128)(4,1)S(1)} %copy-done.851), '
           'custom_call_target="tpu_custom_call"',
    "dq": '%splash_mqa_dq_no_residuals.20 = (f32[2,4,7,1024,128]{4,3,2,1,0:T(8,128)}, '
          'bf16[2,4,7,16384,128]{4,3,2,1,0:T(8,128)(2,1)}) custom-call('
          's8[1,16,16]{2,1,0:T(8,128)(4,1)S(1)} %copy-done.856), '
          'custom_call_target="tpu_custom_call"',
    "dkv": '%splash_mqa_dkv_no_residuals.20 = (f32[2,4,1024,128]{3,2,1,0:T(8,128)}, '
           'bf16[2,4,16384,128]{3,2,1,0:T(8,128)(2,1)}) custom-call('
           's8[1,16,16]{2,1,0:T(8,128)(4,1)S(1)} %copy-done.836), '
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
        (CELL, "dsgd-fold2-long", 1)]
    assert len(mine[0]["why"]) <= 200
    assert bench["workloads"][-1]["name"] == CELL  # appended, nothing moved
    cell = cells.load_cell(CELL)
    assert cell.traffic["kind"] == "train_lm" and cell.facts["loss_band"]["rounds"] == 8
    # the traffic file is dsgd-fold2 as it is, but for the sequence
    short = cells.read_json(os.path.join(cells.HERE, "traffic", "dsgd-fold2.json"))
    long = dict(cell.traffic, train_config=dict(cell.traffic["train_config"]))
    assert long["train_config"].pop("lm_args") == {"seq_len": 16384}
    for key in ("kind", "chips", "train_config", "data", "trace_seconds", "rehearse"):
        assert long[key] == short[key], key


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


def test_the_cell_reports_every_metric_without_a_workloads_list():
    mine = {m["name"] for m in cells.load_cell(CELL).per_layer}
    for m in cells.benchmark_json()["per_layer"]:
        if "workloads" not in m:
            assert m["name"] in mine
        elif m["name"] not in NEW_METRICS:
            assert CELL not in m["workloads"]  # the accepted lists are as they were
    assert mine >= set(NEW_METRICS)
    assert not [n for n in mine if n.startswith(("lstm_", "attention_", "moe_", "mla_"))]


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_configuration_holds_the_published_numbers_apart_from_the_cuts():
    cell = cells.load_cell(CELL)
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert cell.config["source"] == row["source_url"]
    assert cell.config["reduced"] == REDUCED
    for key, value in row["config"].items():
        if key in cell.config["reduced"]:
            assert cell.config[key] != value
            assert cell.config["published"][key] == value
        else:
            assert cell.config[key] == value, key  # the two lists of 52 whole
    assert cell.config["published"]["kept_layers"] == [0, 1, 2, 3]
    assert "8-chip" in cell.config["deployment"]
    for key in ("router_input", "activation", "secondary_experts", "dense_layer",
                "rotary", "initialisation", "compute_dtype", "optimizer", "data"):
        assert cell.config["assumed"][key], key


def test_the_run_configuration_is_the_published_one_cut_as_stated():
    cell = cells.load_cell(CELL)
    cfg, _, model = _configured()
    a, c = cfg.lm_args, cell.config
    assert a.model_type == c["model_type"] == "smallthinker"
    # every width as published, under the program's names
    for ours, theirs in [
            ("hidden_size", "hidden_size"), ("head_dim", "head_dim"),
            ("moe_intermediate_size", "moe_ffn_hidden_size"),
            ("num_attention_heads", "num_attention_heads"),
            ("num_key_value_heads", "num_key_value_heads"),
            ("num_experts_per_tok", "moe_num_active_primary_experts"),
            ("sliding_window", "sliding_window_size"),
            ("rope_theta", "rope_theta"), ("rms_norm_eps", "rms_norm_eps")]:
        assert getattr(a, ours) == c[theirs], ours
    # the cuts: the held share under the published counts
    assert (a.num_experts, a.experts_held, a.first_expert) == (
        c["published"]["moe_num_primary_experts"], c["moe_num_primary_experts"],
        0) == (64, 8, 0)
    assert (a.vocab_size, a.vocab_rows) == (
        c["published"]["vocab_size"], c["vocab_size"]) == (151936, 18992)
    assert a.vocab_size == 8 * a.vocab_rows
    assert a.num_hidden_layers == c["num_hidden_layers"] == 4
    # the kept layers are the published lists' first period
    kept = c["published"]["kept_layers"]
    assert list(a.sliding_window_layout) == [c["sliding_window_layout"][i] for i in kept]
    assert list(a.rope_layout) == [c["rope_layout"][i] for i in kept] == [0, 1, 1, 1]
    assert (a.num_dense_layers, a.num_shared_experts) == (0, 0)
    # the traffic file's sequence overrides the configuration's: the model's
    # own full context, four windows long
    assert a.seq_len == c["max_position_embeddings"] == 4 * a.sliding_window
    assert (cfg.batch_size, cfg.num_sites) == (1, 2)
    assert model.dims.experts_held == 8 and model.vocab_rows == 18992
    assert model.dims.model_type == "smallthinker" and not model.mup_enabled
    assert model.dims.layer_types == (
        "full_attention",) + ("sliding_attention",) * 3
    assert cell.data_spec(None)["vocab_rows"] == a.vocab_rows
    # what a chip holds: the issue's table and the file's arithmetic
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 9), jnp.int32), train=True))["params"]
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))
    assert count(shapes["layer_0"]["attn"]) == 20_971_520
    assert count(shapes["layer_0"]["moe"]) - 163_840 == 8 * 5_898_240
    assert count(shapes["layer_0"]) == 68_326_400
    assert count(shapes["embed"]) + count(shapes["lm_head"]) == 97_239_040
    held = c["published"]["parameters_held"]
    assert count(shapes) == held["total"] == 370_547_200
    for line in (v for v in held.values() if isinstance(v, str)):
        said = int(line.rsplit("= ", 1)[1].replace(",", "")) if "=" in line else None
        if said and ";" not in line:  # "a x b + c x d = n": the sum is n
            terms = line.rsplit(" = ", 1)[0].split(" + ")
            assert sum(int(np.prod([int(f.replace(",", "")) for f in
                                    re.findall(r"[\d,]+", t)])) for t in terms) == said


def _brute_force_pairs(t: int, window) -> int:
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    keep = j <= i
    if window is not None:
        keep &= j > i - window
    return int(keep.sum())


@pytest.mark.parametrize("t,window", [(64, None), (64, 16), (64, 64), (48, 100),
                                      (1, None), (96, 1)])
def test_pair_counts_are_a_brute_force_mask_count(t, window):
    from benchmarks.flops import smallthinker as flops

    assert flops.layer_pairs(t, window) == _brute_force_pairs(t, window)
    cfg, _, _ = _configured()
    small = cfg.with_overrides({"lm_args": {"seq_len": t, "sliding_window": window or 7}})
    want = _brute_force_pairs(t, None) + 3 * _brute_force_pairs(t, window or 7)
    assert flops.unmasked_pairs(small.lm_args) == want


def test_flops_and_kernel_model_of_the_cell_are_the_issues_count():
    from benchmarks.flops import smallthinker as flops
    from benchmarks.trace.readers.roofline_share import least_seconds

    cfg, _, _ = _configured()
    a = cfg.lm_args
    assert flops.layer_pairs(a.seq_len, None) == 134_225_920  # 134.2 M
    assert flops.layer_pairs(a.seq_len, a.sliding_window) == 58_722_304  # 58.7 M
    parts = flops.forward_flops_per_sequence(cfg)
    assert set(parts) == {"projections", "attention", "router", "routed_experts",
                          "head"}  # no dense layer, no shared expert
    assert parts["routed_experts"] / (4 * a.seq_len) == pytest.approx(
        0.75 * 3 * 2 * 2560 * 768)  # 0.75 held assignments a token
    assert sum(parts.values()) / a.seq_len == pytest.approx(573.3e6, rel=1e-3)
    assert flops.train_flops_per_sample(cfg) == pytest.approx(28.18e12, rel=1e-3)
    model = flops.kernel_model(cfg, 2)
    assert [c["count"] for c in model["calls"]] == [4, 4, 4]  # forward ONCE
    assert model["flops"] == pytest.approx(2 * parts["attention"] * (1 + 1.5 + 2.0))
    assert model["flops"] == pytest.approx(40.0e12, rel=2e-3)
    peak = cells.peaks()["TPU v5 lite"]
    assert least_seconds(model, peak) == pytest.approx(0.203, rel=5e-3)
    for call in model["calls"]:  # the flops bound applies to every call
        assert call["flops"] / peak["bf16_flops_per_s"] > (
            call["bytes"] / peak["hbm_bytes_per_s"])


def _toy():
    dims = ref.Dims(num_attention_heads=14, num_key_value_heads=2, head_dim=4,
                    sliding_window=12, layer_types=(
                        "full_attention", "sliding_attention", "sliding_attention"),
                    num_experts_per_tok=3, first_expert=4, q_block=8, head_block=8)
    h, f, v, e = 32, 16, 48, 16
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 200))
    mat = lambda *shape: 0.3 * jax.random.normal(next(keys), shape)
    norm = lambda n: {"scale": 1.0 + 0.1 * jax.random.normal(next(keys), (n,))}

    def layer():
        return {"input_norm": norm(h), "pre_mlp_norm": norm(h),
                "attn": {"wq": mat(h, 56), "wk": mat(h, 8), "wv": mat(h, 8),
                         "wo": mat(56, h)},
                "moe": {"router": mat(h, e), "w1": mat(4, h, f),
                        "w3": mat(4, h, f), "w2": mat(4, f, h)}}

    params = {"embed": mat(v, h), "final_norm": norm(h)["scale"],
              "lm_head": mat(h, v), "layer_0": layer(), "layer_1": layer(),
              "layer_2": layer()}
    sample = jax.random.randint(next(keys), (41,), 0, v)
    return params, sample, dims


def test_reference_gradient_chain_is_the_gradient_of_its_loss():
    """40 positions in query blocks of 8 with a window of 12: sliding blocks
    are held against ``window + block`` keys, clipped at both ends."""
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
    assert float(jnp.abs(want["layer_0"]["moe"]["router"]).max()) > 1e-5


def test_reference_dims_take_the_programs_argument_names():
    from dinunet_implementations_tpu.core.config import AFMoEArgs

    args = {f.name for f in dataclasses.fields(AFMoEArgs)}
    own = {"q_block", "head_block"}  # the reference's own blocking
    assert {f.name for f in dataclasses.fields(ref.Dims)} - own <= args


def test_reference_imports_nothing_from_the_package():
    src = open(os.path.join(cells.HERE, "reference", "smallthinker.py")).read()
    code = src.split('"""', 2)[2]
    assert "dinunet_implementations_tpu" not in code
    assert "pallas" not in code and "checkpoint" not in code and "vmap" not in code
    imports = re.findall(r"^(?:from|import) (\S+)", code, re.M)
    assert set(imports) <= {"__future__", "dataclasses", "functools", "math",
                            "jax", "jax.numpy", "benchmarks.reference.afmoe"}
