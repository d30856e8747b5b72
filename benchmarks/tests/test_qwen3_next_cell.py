"""The qwen3_next next-token cell: its files agree with each other and with
the published configuration, the program's parameter count is the file's
arithmetic, the pair count and the kernel model of ``flops/qwen3_next.py``
are the published shapes' counts, every new metric file names a reader
that exists and the new cell only, the accepted metrics' lists are as they
were, the rehearsal ends in the contract's last line and ``correct``, and the
reference's stage-by-stage gradient chain is its own loss's gradient."""

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
from benchmarks.reference import qwen3_next as ref
from benchmarks.tests.test_harness import check_last_line, run_cell

CELL = "qwen3-next-80b-a3b-ep32.dsgd-fold2"
CONFIG = "qwen3-next-80b-a3b-ep32"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
KEPT = ["linear_attention"] * 3 + ["full_attention"]
#: the accepted file each new metric file copies reader and pattern from
COPIED = {
    "qwen3_next_attention_kernel_ms_per_round": "lfm2_attention_kernel_ms_per_round",
    "qwen3_next_attention_kernel_roofline": "lfm2_attention_kernel_roofline",
    "qwen3_next_moe_grouped_matmul_ms_per_round": "lfm2_moe_grouped_matmul_ms_per_round",
    "qwen3_next_moe_expert_load_max_over_mean": "lfm2_moe_expert_load_max_over_mean",
}
NEW_METRICS = sorted(COPIED) + ["qwen3_next_delta_rule_ms_per_round"]
SOURCE = "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json"
#: the published config.json's keys that give the model's shape
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}


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
    assert bench["workloads"][-1]["name"] == CELL  # appended, not inserted
    cell = cells.load_cell(CELL)
    assert cell.traffic["kind"] == "train_lm" and cell.facts["loss_band"]["rounds"] == 8


@pytest.mark.parametrize("name", NEW_METRICS)
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
    if name in COPIED:  # reader and pattern are the accepted file's
        old = cells.layer_metric(COPIED[name])
        args = dict(spec["args"])
        if name.endswith("_roofline"):  # the family's calls, by name
            assert args.pop("calls") == ["forward", "backward_dq", "backward_dkv"]
        assert (old["reader"], old["args"]) == (spec["reader"], args)


def test_the_delta_rules_metric_reads_names_of_its_scope_alone():
    """The accepted reader over the instruction NAMES that the traced run's
    scope table put under ``model/delta_chunks`` and under no other scope of
    the cell's program; told from the names other layers' work wears."""
    spec = cells.layer_metric("qwen3_next_delta_rule_ms_per_round")
    accepted = cells.layer_metric("lfm2_short_conv_ms_per_round")
    assert spec["reader"] == accepted["reader"] == "device_ops_matching"
    assert {k: v for k, v in spec["args"].items() if k != "pattern"} == {
        k: v for k, v in accepted["args"].items() if k != "pattern"}
    assert "delta_chunks" in spec["what"] and "MISSES" in spec["what"]
    pattern = spec["args"]["pattern"]
    for miss in ("ragged-dot-none.1", "fusion", "fusion.12", "copy.3",
                 "slice_multiply_fusion", "convolution_bitcast_fusion.20",
                 "add", "splash_mqa_fwd_residuals.5"):
        assert not re.search(pattern, miss), miss


def test_the_cell_reports_every_metric_without_a_workloads_list():
    mine = {m["name"] for m in cells.load_cell(CELL).per_layer}
    for m in cells.benchmark_json()["per_layer"]:
        if "workloads" not in m:
            assert m["name"] in mine
        elif not m["name"].startswith("qwen3_next_"):
            assert CELL not in m["workloads"]  # the accepted lists are as they were
    assert mine >= set(NEW_METRICS)


def test_configuration_holds_the_published_numbers_apart_from_the_cuts():
    cell = cells.load_cell(CELL)
    assert cell.config["source"] == SOURCE
    assert cell.config["reduced"] == REDUCED
    for key, value in PUBLISHED.items():
        if key in cell.config["reduced"]:
            assert cell.config[key] != value
            assert cell.config["published"][key] == value
        else:
            assert cell.config[key] == value, key
    assert cell.config["published"]["kept_layers"] == [0, 1, 2, 3]
    assert "32-chip" in cell.config["deployment"]
    for key in ("norms", "gated_deltanet_layout", "gated_deltanet", "attention",
                "rotary", "routing", "left_out", "initialisation",
                "compute_dtype", "optimizer", "data"):
        assert cell.config["assumed"][key], key


def test_the_run_configuration_is_the_published_one_cut_as_stated():
    cell = cells.load_cell(CELL)
    cfg, _, model = _configured()
    a, c = cfg.lm_args, cell.config
    assert a.model_type == c["model_type"] == "qwen3_next"
    for ours, theirs in [
            ("hidden_size", "hidden_size"), ("head_dim", "head_dim"),
            ("moe_intermediate_size", "moe_intermediate_size"),
            ("num_attention_heads", "num_attention_heads"),
            ("num_key_value_heads", "num_key_value_heads"),
            ("num_experts_per_tok", "num_experts_per_tok"),
            ("linear_num_key_heads", "linear_num_key_heads"),
            ("linear_num_value_heads", "linear_num_value_heads"),
            ("linear_key_head_dim", "linear_key_head_dim"),
            ("linear_value_head_dim", "linear_value_head_dim"),
            ("linear_conv_kernel_dim", "linear_conv_kernel_dim"),
            ("partial_rotary_factor", "partial_rotary_factor"),
            ("rope_theta", "rope_theta"), ("rms_norm_eps", "rms_norm_eps"),
            ("route_norm", "norm_topk_prob"),
            ("global_attn_every_n_layers", "full_attention_interval"),
            ("tie_word_embeddings", "tie_word_embeddings")]:
        assert getattr(a, ours) == c[theirs], ours
    assert a.moe_intermediate_size * a.num_shared_experts == c[
        "shared_expert_intermediate_size"]
    # the cuts: the held share under the published counts
    assert (a.num_experts, a.experts_held, a.first_expert) == (
        c["published"]["num_experts"], c["num_experts"], 0) == (512, 16, 0)
    assert (a.vocab_size, a.vocab_rows) == (
        c["published"]["vocab_size"], c["vocab_size"]) == (151936, 18992)
    assert a.vocab_size == 8 * a.vocab_rows
    assert a.num_hidden_layers == c["num_hidden_layers"] == 4
    assert model.dims.layer_types == tuple(KEPT)
    assert (cfg.batch_size, cfg.num_sites, a.seq_len) == (1, 2, 8192)
    assert cell.data_spec(None)["vocab_rows"] == a.vocab_rows
    # what a chip holds: the file's arithmetic
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 9), jnp.int32), train=True))["params"]
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))  # noqa: E731
    assert count(shapes["layer_0"]["linear_attn"]) == 33_718_464
    assert count(shapes["layer_3"]["attn"]) == 27_263_488
    assert count(shapes["layer_0"]["moe"]) == 1_048_576 + 16 * 3_145_728 + 3_147_776
    assert [count(shapes[f"layer_{i}"]) for i in range(4)] == [88_250_560] * 3 + [
        81_795_584]
    assert count(shapes["embed"]) == count(shapes["lm_head"]) == 38_895_616
    held = c["published"]["parameters_held"]
    assert count(shapes) == held["total"] == 424_340_544
    for line in (v for v in held.values() if isinstance(v, str)):
        if "=" in line and ";" not in line:  # "a x b + c x d = n": the sum is n
            said = int(line.rsplit("= ", 1)[1].split()[0].replace(",", ""))
            terms = line.rsplit(" = ", 1)[0].split(" + ")
            assert sum(int(np.prod([int(f.replace(",", "")) for f in
                                    re.findall(r"[\d,]+", t)])) for t in terms
                       ) == said, line


@pytest.mark.parametrize("t", [1, 48, 64, 96])
def test_pair_count_is_a_brute_force_mask_count(t):
    from benchmarks.flops import qwen3_next as flops

    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    assert flops.causal_pairs(t) == int((j <= i).sum())


def test_flops_and_kernel_model_of_the_cell_are_counted_from_its_shapes():
    from benchmarks.flops import qwen3_next as flops
    from benchmarks.trace.readers.roofline_share import least_seconds

    cfg, _, _ = _configured()
    a = cfg.lm_args
    assert (flops.attention_layers(a), flops.linear_layers(a)) == (1, 3)
    parts = flops.forward_flops_per_sequence(cfg)
    per_token = {k: v / a.seq_len for k, v in parts.items()}
    # q | k | v | z, b | a' and out: 2 x 2048 x (12,288 + 64 + 4,096) a layer
    assert per_token["delta_projections"] == 3 * 2 * 2048 * (12288 + 64 + 4096)
    # 6 d_k d_v a position and value head, whatever algorithm runs it
    assert per_token["delta_rule"] == 3 * 32 * 6 * 128 * 128
    assert per_token["projections"] == 2 * 2048 * (3 * 4096 + 2 * 512)
    assert per_token["routed_experts"] == pytest.approx(
        4 * 3 * 2 * 2048 * 512 * 10 * 16 / 512)  # 0.3125 held assignment a token
    assert per_token["head"] == 2 * 2048 * 18992
    assert sum(per_token.values()) == pytest.approx(452.4e6, rel=1e-3)
    assert flops.train_flops_per_sample(cfg) == pytest.approx(11.12e12, rel=1e-3)
    model = flops.kernel_model(cfg, 2)
    assert [c["count"] for c in model["calls"]] == [1, 1, 1]  # ONE layer, forward once
    assert [c["name"] for c in model["calls"]] == [
        "forward", "backward_dq", "backward_dkv"]
    assert model["flops"] == 2 * 33_558_528 * 16 * 18 * 256
    peak = cells.peaks()["TPU v5 lite"]
    assert least_seconds(model, peak) == pytest.approx(0.02512, rel=5e-3)
    for call in model["calls"]:  # the flops bound applies to every call
        assert call["flops"] / peak["bf16_flops_per_s"] > (
            call["bytes"] / peak["hbm_bytes_per_s"])


def test_rehearsal_of_the_new_cell_prints_the_contracts_last_line():
    out = run_cell("--workload", CELL, "--seed", "4300000001", "--seconds", "1",
                   "--trace", "0", "--rehearse", "tiny")
    check_last_line(out, False)
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.startswith("{")]
    check = next(l["reference_check"] for l in lines if "reference_check" in l)
    assert check["ok"] and check["rounds"] == 4 and check["sites"] == 2
    assert {"delta_rule", "delta_gates", "attention", "shared"} <= set(
        check["grad_cosine"])
    assert lines[-1]["correct"] is True
    facts = next(l["facts"] for l in lines if "facts" in l)
    assert [c["count"] for c in facts["kernel_model"]["calls"]] == [1, 1, 1]
    assert facts["moe_expert_load_max_over_mean"] >= 1.0


# -- the reference ------------------------------------------------------------------


def _toy():
    dims = ref.Dims(num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                    partial_rotary_factor=0.25,
                    layer_types=("linear_attention", "full_attention"),
                    linear_num_key_heads=2, linear_num_value_heads=4,
                    linear_key_head_dim=8, linear_value_head_dim=8,
                    num_experts_per_tok=3, first_expert=4, q_block=8,
                    head_block=8, recurrence_block=8)
    h, f, v, e = 32, 16, 48, 16
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 200))
    mat = lambda *shape: 0.3 * jax.random.normal(next(keys), shape)  # noqa: E731
    norm = lambda n: {"scale": 1.0 + 0.1 * jax.random.normal(next(keys), (n,))}  # noqa: E731

    def layer(kind):
        mixer = ({"linear_attn": {
            "in_proj_qkvz": mat(h, 2 * 16 + 2 * 32), "in_proj_ba": mat(h, 8),
            "conv1d": mat(64, 4), "A_log": jnp.log(jnp.arange(1.0, 5.0) * 3),
            "dt_bias": jnp.ones((4,)), "norm": norm(8), "out_proj": mat(32, h)}}
            if kind == "linear_attention" else {"attn": {
                "wq": mat(h, 64), "wg": mat(h, 64), "wk": mat(h, 32),
                "wv": mat(h, 32), "wo": mat(64, h), "q_norm": norm(16),
                "k_norm": norm(16)}})
        moe = {"router": mat(h, e), "w1": mat(4, h, f), "w3": mat(4, h, f),
               "w2": mat(4, f, h), "shared_expert_gate": mat(h, 1),
               "shared": {"w1": mat(h, f), "w3": mat(h, f), "w2": mat(f, h)}}
        return {"input_norm": norm(h), "pre_mlp_norm": norm(h), "moe": moe, **mixer}

    params = {"embed": mat(v, h), "final_norm": norm(h)["scale"],
              "lm_head": mat(h, v),
              **{f"layer_{i}": layer(kind) for i, kind in enumerate(dims.layer_types)}}
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
        # the blocks are no approximation: one block over all keys and positions
        one = ref.forward(params, sample[:-1], dataclasses.replace(
            dims, q_block=40, recurrence_block=40))
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert float(jnp.abs(logits - whole).max()) < 1e-5
    assert float(jnp.abs(one - whole).max()) < 1e-4
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert float(jnp.abs(g - w).max()) <= 1e-4 * max(
            float(jnp.abs(w).max()), 1e-3), jax.tree_util.keystr(path)
    assert float(jnp.abs(want["layer_0"]["linear_attn"]["A_log"]).max()) > 1e-6
    assert float(jnp.abs(want["layer_1"]["attn"]["wg"]).max()) > 1e-5


def test_the_references_delta_rule_is_the_equation_position_by_position():
    t, heads, dk, dv = 11, 2, 4, 3
    rng = np.random.default_rng(3)
    q, k = rng.normal(size=(2, t, heads, dk))
    v = rng.normal(size=(t, heads, dv))
    beta = 1 / (1 + np.exp(-rng.normal(size=(t, heads))))
    g = -np.exp(rng.normal(size=(t, heads)))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.delta_rule(*(jnp.asarray(a, jnp.float32) for a in (
            q, k, v, beta, g)), 4))
    s = np.zeros((heads, dk, dv))
    want = np.zeros((t, heads, dv))
    for i in range(t):
        for h in range(heads):
            s[h] *= np.exp(g[i, h])
            s[h] += np.outer(k[i, h], beta[i, h] * (v[i, h] - s[h].T @ k[i, h]))
            want[i, h] = s[h].T @ q[i, h]
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


def test_reference_dims_take_the_programs_argument_names():
    from dinunet_implementations_tpu.core.config import AFMoEArgs

    args = {f.name for f in dataclasses.fields(AFMoEArgs)}
    own = {"q_block", "head_block", "recurrence_block"}  # the reference's own blocking
    assert {f.name for f in dataclasses.fields(ref.Dims)} - own <= args


def test_reference_imports_nothing_from_the_package():
    src = open(os.path.join(cells.HERE, "reference", "qwen3_next.py")).read()
    code = src.split('"""', 2)[2]
    assert "dinunet_implementations_tpu" not in code
    assert "pallas" not in code and "vmap" not in code and "ragged" not in code
    assert "chunk" not in code  # position by position
    imports = re.findall(r"^(?:from|import) (\S+)", code, re.M)
    assert set(imports) <= {"__future__", "dataclasses", "functools", "math",
                            "jax", "jax.numpy", "benchmarks.reference.afmoe"}
