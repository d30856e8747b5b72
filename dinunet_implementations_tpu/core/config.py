"""Configuration system.

TPU-native re-design of the reference's two-tier config:

1. ``compspec.json`` — the owner/member-scoped, typed flag schema rendered by the
   COINSTAC GUI (reference ``compspec.json:10-297``). Here it becomes a plain
   dataclass :class:`TrainConfig` whose fields carry the same names and defaults,
   with the GUI metadata (``source``, ``conditional``, ``group``) preserved in
   :data:`COMPSPEC_META` so a compspec-compatible JSON schema can be emitted via
   :func:`export_compspec`.
2. Per-site ``inputspec.json`` simulator files (reference
   ``datasets/test_fsl/inputspec.json:1-187``, ``datasets/icalstm/inputspec.json:1-88``)
   — loaded by :func:`load_inputspec`, which unwraps the ``{"key": {"value": v}}``
   envelope and returns one override dict per site.

Config resolution order (mirrors ``COINNLocal`` kwargs being overridden by GUI
``data['input']``, reference ``local.py:31-37``): dataclass defaults < programmatic
kwargs < per-site inputspec values.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Sequence

# ---------------------------------------------------------------------------
# Task / engine registry (reference comps/__init__.py:7-16)
# ---------------------------------------------------------------------------


class NNComputation:
    """Available tasks (reference ``comps/__init__.py:7-10``)."""

    TASK_FREE_SURFER = "FS-Classification"
    TASK_ICA = "ICA-Classification"
    # TPU-build extensions (BASELINE.json configs):
    TASK_SMRI_3D = "sMRI-3D-Classification"
    TASK_MULTIMODAL = "Multimodal-Classification"
    TASK_LM = "LM-NextToken"

    ALL = (TASK_FREE_SURFER, TASK_ICA, TASK_SMRI_3D, TASK_MULTIMODAL,
           TASK_LM)


class AggEngine:
    """Aggregation engines (reference ``comps/__init__.py:13-16``)."""

    DECENTRALIZED_SGD = "dSGD"
    RANK_DAD = "rankDAD"
    POWER_SGD = "powerSGD"

    ALL = (DECENTRALIZED_SGD, RANK_DAD, POWER_SGD)


# ---------------------------------------------------------------------------
# Task-specific argument blocks
# ---------------------------------------------------------------------------


@dataclass
class FSArgs:
    """FreeSurfer classification parameters (reference ``compspec.json:225-250``)."""

    labels_file: str = "site0_covariates.csv"
    data_column: str = "freesurferfile"
    labels_column: str = "isControl"
    input_size: int = 66
    hidden_sizes: tuple = (256, 128, 64, 32)
    num_class: int = 2
    dad_reduction_rank: int = 10
    dad_num_pow_iters: int = 5
    dad_tol: float = 1e-3
    # warm-start rankDAD's subspace Ω from the previous round (engine state;
    # engines/rankdad.py) — the tol early-exit then fires after 1-2 power
    # iterations instead of dad_num_pow_iters. False = stateless cold starts.
    dad_warm_start: bool = True
    split_files: tuple = ()
    # reproduce the reference's string-label bug bit-for-bit: EVERY string
    # maps via (s.lower() == 'true'), so "1" → 0 (comps/fs/__init__.py:25-26);
    # default False parses numeric strings numerically (documented deviation,
    # data/freesurfer.py coerce_label)
    bug_compatible_labels: bool = False


@dataclass
class ICAArgs:
    """ICA classification parameters (reference ``compspec.json:251-281``,
    ``datasets/icalstm/inputspec.json:1-88``)."""

    data_file: str = ""
    labels_file: str = ""
    num_class: int = 2
    monitor_metric: str = "auc"
    metric_direction: str = "maximize"
    log_header: str = "Loss|AUC"
    num_components: int = 100
    temporal_size: int = 980
    window_size: int = 10
    window_stride: int = 10
    input_size: int = 256
    # The compspec template default is 384 (compspec.json:267) but the actual
    # shipped workload uses 348 (datasets/icalstm/inputspec.json, both sites) —
    # we default to the workload value so config, bench, and fixtures agree.
    hidden_size: int = 348
    num_layers: int = 1
    bidirectional: bool = True
    dad_reduction_rank: int = 10
    dad_num_pow_iters: int = 5
    dad_tol: float = 1e-3
    dad_warm_start: bool = True  # see FSArgs.dad_warm_start
    split_files: tuple = ()
    # parity-only fields: present in compspec.json:261-264 but never read by
    # the reference trainers (grep: no seq_len/components_file use in comps/)
    seq_len: int = 13
    components_file: str = ""
    # TPU extension: "bfloat16" runs encoder/LSTM matmuls in bf16 with f32
    # accumulation (~MXU-native mixed precision); "" = full f32 (parity)
    compute_dtype: str = ""


@dataclass
class SMRI3DArgs:
    """3D sMRI classification parameters (TPU-build extension; BASELINE.json
    configs: '3D-CNN sMRI (T1w volumes) federated classifier, 8 sites')."""

    data_file: str = ""
    labels_file: str = ""
    num_class: int = 2
    volume_shape: tuple = (64, 64, 64)
    channels: tuple = (16, 32, 64, 128)
    # "bfloat16" = bf16 convolutions with f32 BatchNorm/head; "" = full f32
    compute_dtype: str = ""
    # fold 2x2x2 spatial blocks into 8 channels before conv_0 (3.7-6.9x
    # faster on TPU; changes the architecture, so old checkpoints need False)
    space_to_depth: bool = False
    dad_reduction_rank: int = 10
    dad_num_pow_iters: int = 5
    dad_tol: float = 1e-3
    dad_warm_start: bool = True  # see FSArgs.dad_warm_start
    split_files: tuple = ()


@dataclass
class MultimodalArgs:
    """Multimodal FS+ICA transformer parameters (TPU-build extension;
    BASELINE.json configs: 'Multimodal FS+ICA Transformer, 64-site DP-SGD')."""

    data_file: str = ""
    labels_file: str = ""
    data_column: str = "freesurferfile"
    labels_column: str = "isControl"
    num_class: int = 2
    fs_input_size: int = 66
    num_components: int = 100
    temporal_size: int = 980
    window_size: int = 10
    window_stride: int = 10
    embed_dim: int = 256
    num_heads: int = 8
    num_layers: int = 4
    mlp_ratio: int = 4
    # "" = auto: ring attention iff model_axis_size > 1; "local"/"ring" force
    attention: str = ""
    # "bfloat16" = bf16 matmuls with f32 softmax/LayerNorm; "" = full f32
    compute_dtype: str = ""
    dad_reduction_rank: int = 10
    dad_num_pow_iters: int = 5
    dad_tol: float = 1e-3
    dad_warm_start: bool = True  # see FSArgs.dad_warm_start
    split_files: tuple = ()


@dataclass
class AFMoEArgs:
    """A mixture-of-experts decoder as a federated next-token task
    (models/afmoe.py), of the type ``model_type`` names: ``afmoe`` (the
    Trinity family), ``glm4_moe_lite`` (latent attention, two pre-norms a
    block, no embedding scale, multi-token prediction) or ``smallthinker``
    (the router reads the block's input before attention, a softmax over the
    chosen experts' logits, ReGLU experts, no shared expert, no dense layer,
    a full layer without positions first in each period of four) or
    ``lfm2_moe`` (a gated short convolution for attention on its ``conv``
    layers, QK-norm then rotary on its full ones, a tied head). The
    widths default to the published Trinity-Mini ``config.json`` (source:
    huggingface.co/arcee-ai/Trinity-Mini) under its own key names; what a
    configuration CUTS is the depth (``num_hidden_layers``,
    ``num_dense_layers``, ``layer_types``), the share of the routed experts
    held here (``experts_held`` of ``num_experts``, from ``first_expert``;
    the router keeps all ``num_experts`` outputs and ``num_experts_per_tok``)
    and the share of the vocabulary (``vocab_rows`` of ``vocab_size``).

    A ``glm4_moe_lite`` configuration gives its ``config.json``'s sizes under
    these names where the two families name one thing differently
    (``n_routed_experts`` -> ``num_experts``, ``n_shared_experts`` ->
    ``num_shared_experts``, ``first_k_dense_replace`` -> ``num_dense_layers``,
    ``norm_topk_prob`` -> ``route_norm``, ``routed_scaling_factor`` ->
    ``route_scale``) and the latent widths under its own; it reads neither
    ``num_key_value_heads``, ``head_dim``, ``sliding_window`` nor
    ``mup_enabled``, and every layer of it is ``full_attention``.

    A ``smallthinker`` configuration likewise (``moe_num_primary_experts`` ->
    ``num_experts``, ``moe_num_active_primary_experts`` ->
    ``num_experts_per_tok``, ``moe_ffn_hidden_size`` ->
    ``moe_intermediate_size``, ``sliding_window_size`` -> ``sliding_window``)
    with ``num_dense_layers`` 0 and ``num_shared_experts`` 0, and its two
    per-layer lists under their own names; it reads neither
    ``intermediate_size``, ``route_norm`` (``norm_topk_prob`` divides a softmax
    by its sum, 1), ``route_scale`` nor ``mup_enabled``.

    An ``lfm2_moe`` configuration likewise (``norm_eps`` -> ``rms_norm_eps``,
    ``norm_topk_prob`` -> ``route_norm``, ``routed_scaling_factor`` ->
    ``route_scale``) with ``num_shared_experts`` 0 and ``head_dim`` =
    ``hidden_size / num_attention_heads``; ``layer_types`` is REQUIRED, one
    of ``conv`` and ``full_attention`` a kept layer (the published list has
    no rule to derive it from), and ``num_dense_layers`` counts layers of that
    list; it reads neither ``sliding_window`` nor ``mup_enabled``.

    A ``qwen3_next`` configuration likewise (``full_attention_interval`` ->
    ``global_attn_every_n_layers``, ``norm_topk_prob`` -> ``route_norm``,
    ``shared_expert_intermediate_size`` = ``moe_intermediate_size`` x
    ``num_shared_experts`` 1) with its Gated DeltaNet widths and
    ``partial_rotary_factor`` under their own names; every layer routes
    (``num_dense_layers`` 0), ``layer_types`` defaults to the period, a
    ``full_attention`` layer every ``global_attn_every_n_layers``-th and
    ``linear_attention`` between; it reads neither ``sliding_window``,
    ``route_scale`` nor ``mup_enabled``."""

    data_file: str = ""
    # or "glm4_moe_lite", "smallthinker", "lfm2_moe", "qwen3_next"
    model_type: str = "afmoe"
    seq_len: int = 8192  # a sample is seq_len + 1 token ids
    vocab_size: int = 200192
    vocab_rows: int = 0  # rows of the vocabulary held here; 0 = all
    hidden_size: int = 2048
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    experts_held: int = 0  # routed experts held here; 0 = all
    first_expert: int = 0  # index of the first held expert
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    # one entry a layer; () = the published period, a full layer every
    # global_attn_every_n_layers-th
    layer_types: tuple = ()
    global_attn_every_n_layers: int = 4
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    route_norm: bool = True
    route_scale: float = 2.826
    mup_enabled: bool = True
    # glm4_moe_lite's published keys (0 = the type has none): the ranks of
    # the query and key-value latents, a head's content and rotary widths
    # (one rotary key a position, shared by the heads), the value width
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # smallthinker's published per-layer lists, 1 = the layer has a window /
    # a rotary term (() = the type has none; the two must agree: attention
    # applies rotary positions iff it has a window)
    sliding_window_layout: tuple = ()
    rope_layout: tuple = ()
    # prediction depths beyond the next token (0 or 1), and the weight of
    # that depth's loss beside the next token's (the config gives none)
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.3
    # lfm2_moe's published keys: positions a ``conv`` layer's filter covers;
    # the head is the embedding's own matrix (no ``lm_head`` in the tree)
    conv_L_cache: int = 3
    tie_word_embeddings: bool = False
    # qwen3_next's published keys (0 = the type has none): a
    # ``linear_attention`` layer's key and value heads and their widths, the
    # positions its convolution covers; the share of a full layer's head
    # dimensions that rotary positions cover
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    partial_rotary_factor: float = 1.0
    # "bfloat16" = bf16 matmuls, f32 accumulation/norms/softmax/router/loss
    compute_dtype: str = ""
    q_block: int = 512  # query rows an attention block holds
    kv_chunk: int = 2048  # step in which a full layer's key prefix grows
    loss_block: int = 1024  # positions the head and the loss hold at a time
    dad_reduction_rank: int = 10
    dad_num_pow_iters: int = 5
    dad_tol: float = 1e-3
    dad_warm_start: bool = True  # see FSArgs.dad_warm_start
    split_files: tuple = ()


@dataclass
class PretrainArgs:
    """Pretraining arguments (reference ``compspec.json:128-148``)."""

    epochs: int = 0
    learning_rate: float = 1e-3
    batch_size: int = 16
    local_iterations: int = 1
    validation_epochs: int = 1
    pin_memory: bool = False
    num_workers: int = 0
    patience: int = 51


# ---------------------------------------------------------------------------
# The main config
# ---------------------------------------------------------------------------


@dataclass
class TrainConfig:
    """Full training configuration.

    Field names and defaults mirror the reference compspec
    (``compspec.json:32-224``) plus the ``COINNLocal`` call-site kwargs
    (``local.py:31-37``). One flat dataclass replaces the reference's
    cache-dict-of-everything.
    """

    # --- task selection (compspec.json:32-55)
    task_id: str = NNComputation.TASK_FREE_SURFER
    mode: str = "train"  # train | test
    # --- aggregation (compspec.json:56-79)
    agg_engine: str = AggEngine.DECENTRALIZED_SGD
    num_reducers: int = 2  # no-op on TPU (reduction is a collective); kept for parity
    # --- training loop (compspec.json:80-224, local.py:31-37)
    batch_size: int = 16
    local_iterations: int = 1  # gradient accumulation steps
    learning_rate: float = 1e-3
    epochs: int = 101
    pretrain: bool = False
    pretrain_args: PretrainArgs | None = None
    validation_epochs: int = 1
    # payload dtype for gradient exchange: "32" | "16" (bf16 — the TPU-native
    # 16-bit type) | "16-ieee" (the reference's literal fp16, compat mode —
    # compspec.json:161-176)
    precision_bits: str = "32"
    pin_memory: bool = False  # torch DataLoader parity no-op
    num_workers: int = 0  # torch DataLoader parity no-op
    patience: int = 35
    split_ratio: tuple = (0.8, 0.1, 0.1)
    num_folds: int | None = None  # mutually exclusive with split_ratio
    # --- trainer extras (local.py:31-37)
    num_class: int = 2
    monitor_metric: str = "auc"
    metric_direction: str = "maximize"
    log_header: str = "loss|auc"
    # warm start from a saved checkpoint's params (the reference library's
    # load-pretrained capability implied by best_val_epoch/pretrain semantics,
    # SURVEY.md §5 checkpoint/resume); "" = train from init
    pretrained_path: str = ""
    dataloader_args: dict = field(default_factory=lambda: {"train": {"drop_last": True}})
    seed: int = 0
    optimizer: str = "adam"  # coinstac-dinunet trains with Adam at `learning_rate`
    # --- task args
    fs_args: FSArgs = field(default_factory=FSArgs)
    ica_args: ICAArgs = field(default_factory=ICAArgs)
    smri3d_args: SMRI3DArgs = field(default_factory=SMRI3DArgs)
    multimodal_args: MultimodalArgs = field(default_factory=MultimodalArgs)
    lm_args: AFMoEArgs = field(default_factory=AFMoEArgs)
    # --- TPU-build extras
    num_sites: int = 2
    sites_per_device: int = 1  # >1 folds several simulated sites onto one chip
    # multi-slice scale-out (r18, parallel/mesh.py sliced_site_mesh): > 1
    # lays an OUTER `slice` mesh axis over the site tier — sites spread
    # num_slices ways, intra-slice aggregation rides ICI and ONE inter-slice
    # hop per round crosses DCN carrying the already-reduced per-slice
    # partial (quantized by dcn_wire_quant). 1 (default) is the legacy
    # single-mesh program, bit-identical (S005 "slices-off"). Emulated on
    # virtual CPU devices in one process; real hosts launch one
    # runner/dcn_worker.py process per slice.
    num_slices: int = 1
    # the INTER-SLICE wire codec, independent of the intra-slice `wire_quant`
    # ("" = follow wire_quant): "none" ships the per-slice partial fused with
    # the intra-slice reduce (no slice-boundary re-quantization — sliced
    # trajectories stay bit-exact vs unsliced); "bf16"/"int8"/"fp8" re-
    # quantize the partial before the DCN hop, landing the shrink exactly
    # where bandwidth is scarcest (S002-proven per-tier wire models).
    dcn_wire_quant: str = ""
    # slice-quorum floor (r19 slice elasticity, trainer/steps.py): on a
    # sliced mesh with a slice-fault plan, a round with fewer LIVE slices
    # than this HOLDS — params/optimizer/engine/health frozen, NaN loss,
    # held_rounds telemetry — instead of training on a rump cohort. 1
    # (default) trains whenever any slice survives; only meaningful with
    # num_slices > 1 (rejected otherwise).
    min_slices: int = 1
    # sequence/model parallelism (SURVEY.md §2.2 TPU extension): >1 builds a
    # (site, model) mesh; each site's model shards its sequence axis over the
    # model axis — ICALstm runs its BiLSTM as a ring LSTM, the multimodal
    # transformer uses ring attention (runner/registry.py wires both). Needs
    # num_sites × model_axis_size devices.
    model_axis_size: int = 1
    # ring-LSTM wavefront pipelining (parallel/sequence.py): number of batch
    # microbatches per ring stage. 0 = auto (minimize 8-row MXU tile work);
    # 1 = the unpipelined masked wavefront; must divide the batch size.
    # Only meaningful with model_axis_size > 1 on an LSTM task.
    sequence_microbatches: int = 0
    # rounds-leading scan xs for the epoch loop (trainer/steps.py): the
    # default trades ~1x the epoch-input size in peak HBM residency for
    # throughput (chosen by an r5 A/B; not re-measured since — ROADMAP D3).
    # False switches to the per-round dynamic-slice arm — the escape hatch
    # for multi-GB epoch inputs where that residency bump matters more than
    # the speed.
    rounds_scan_xs: bool = True
    # input pipeline (trainer/loop.py): "device" (default) uploads each
    # site's inventory to the mesh once per fit and drives every epoch from a
    # compact [S, steps, B] int32 index plan — the jitted epoch gathers
    # batches on-device, so per-epoch host→device traffic is index-plan
    # bytes, not dataset bytes (plus a double-buffered background planner
    # building epoch N+1's plan while epoch N runs). "host" is the legacy
    # dense path: plan_epoch re-materializes [S, steps, B, ...] on the host
    # and ships it every epoch (the A/B arm, and the escape hatch if the
    # padded inventory grid itself cannot fit in HBM).
    pipeline: str = "device"
    # donate the carried TrainState's buffers to the epoch program
    # (jax.jit donate_argnums): the update writes in place instead of
    # allocating a second params+optimizer copy per epoch. The trainer
    # snapshots best-state selections, so donation is transparent; False
    # restores the copying behavior.
    donate_epoch_state: bool = True
    # non-empty → persistent XLA compilation cache at this directory
    # (jax compilation_cache): re-runs and later folds of the same
    # (engine, topology) program load the compiled epoch from disk instead
    # of recompiling. CLI: --compile-cache DIR.
    compile_cache_dir: str = ""
    # non-empty → wrap each fit() in jax.profiler.trace(profile_dir) and
    # write a TensorBoard-compatible device trace there (SURVEY.md §5: the
    # reference only has wall-clock duration lists; this is the TPU upgrade)
    profile_dir: str = ""
    # unified telemetry (telemetry/): "on" threads the span tracer through
    # the fit, accumulates on-device per-round per-site metrics (grad/update
    # norms, engine residual, payload bytes) in TrainState.telemetry, and
    # writes manifest.json / metrics.jsonl / Perfetto-loadable trace files
    # under <out_dir>/telemetry/fold_<k>. "off" (default) statically
    # compiles the device metrics out — the epoch program is bitwise-equal
    # to the pre-telemetry one (same pattern as quarantine_rounds=-1).
    telemetry: str = "off"
    # non-empty → telemetry artifacts land here instead of
    # <out_dir>/telemetry (useful when out_dir is unset or shared)
    telemetry_dir: str = ""
    # non-empty → jax.profiler capture around the xprof_window epoch range
    # only (CLI --xprof-dir). Windowed alternative to profile_dir (which
    # traces the WHOLE fit); the two are mutually exclusive per fit.
    xprof_dir: str = ""
    # (first, last) epochs of the xprof capture window, 1-based inclusive
    xprof_window: tuple = (1, 1)
    # buffered-async aggregation (r13 elastic rounds, trainer/steps.py): a
    # positive bound switches every engine to staleness-bounded buffered
    # aggregation — each virtual site's LAST deposited update keeps
    # contributing, weighted by staleness_decay^age, until its age exceeds
    # the bound (then masked exactly like a dead site). 0 (default) is the
    # bulk-synchronous path, statically compiled to the exact legacy program
    # (lowering-identical; checks/semantic.py S005 "async-off").
    staleness_bound: int = 0
    # per-round-of-age weight multiplier for buffered contributions; 1.0
    # keeps stale updates at full weight until the bound cuts them off
    staleness_decay: float = 0.5
    # quantized collective wires (r14, parallel/collectives.py WireCodec):
    # "none" (default) keeps the legacy precision_bits wire byte-for-byte
    # (program-identical; S005-gated); "bf16" forces a bf16 wire; "int8" /
    # "fp8" quantize every engine payload (dSGD deltas, rankDAD/powerSGD
    # factors) to a 1-byte grid with a scale per payload before the
    # collective, dequantizing after the reduce — ~4x fewer wire bytes than
    # f32, proven exactly by checks/semantic.py S002 against the traced
    # program. Matmul precision stays governed by precision_bits.
    wire_quant: str = "none"
    # stochastic rounding on the int8 wire grid (unbiased in expectation;
    # value-hashed dither, no RNG state): False = round-to-nearest-even
    wire_stochastic: bool = False
    # overlapped rounds (r14, trainer/steps.py): issue round t's
    # aggregation collective while round t+1's batch gather + compute run
    # (double-buffered TrainState.overlap stash; one-round-delayed
    # pipelined update). False (default) compiles the exact legacy round
    # (S005-gated). Mutually exclusive with staleness_bound > 0.
    overlap_rounds: bool = False
    # fault tolerance (robustness/): a site whose round gradient is
    # non-finite for this many CONSECUTIVE rounds is quarantined — zero
    # weight for the rest of the fit, params advance on the live sites'
    # aggregate. 0 keeps the per-round non-finite skip but never quarantines;
    # -1 statically compiles the whole fault machinery out of the epoch
    # program (exact pre-robustness program; liveness masks still work when a
    # FaultPlan is given).
    quarantine_rounds: int = 3
    # byzantine-robust aggregation (r17, parallel/collectives.py
    # ROBUST_AGGS): "none" (default) keeps the renormalizing weighted mean
    # program-identically (S005-gated); "norm_clip" clips each site's
    # gradient norm to robust_clip_mult × the live-weighted median site norm
    # before the UNCHANGED weighted-mean wire (composes with wire_quant);
    # "trimmed_mean" / "coordinate_median" swap the psum-shaped exchange for
    # a cross-site gather + per-coordinate robust reduce (wire grows —
    # S002-proven per engine). Any non-"none" mode also switches on the
    # anomaly-scored reputation layer (robustness/health.py).
    robust_agg: str = "none"
    # fraction of total live weight trimmed from EACH tail by the
    # trimmed-mean reducer; must exceed the hostile weight fraction for the
    # defense to hold (f attackers of S equal sites need trim_frac > f/S)
    robust_trim_frac: float = 0.2
    # norm_clip threshold multiplier over the live-weighted median site norm
    robust_clip_mult: float = 2.5
    # reputation layer (robust_agg != "none"): a live site whose per-round
    # anomaly z-score (max of distance-to-robust-aggregate and gradient-norm
    # z across the live cohort) exceeds reputation_z for reputation_rounds
    # CONSECUTIVE rounds trips the same sticky quarantine flag as a NaN
    # streak. reputation_rounds=0 scores without quarantining. z-scores top
    # out at (S_live-1)/sqrt(S_live), so small cohorts need a lower z.
    reputation_z: float = 2.0
    reputation_rounds: int = 8
    # --- privacy plane (r20, privacy/) ---------------------------------
    # in-scan DP-SGD (privacy/dpsgd.py): dp_clip > 0 clips each site's
    # round-gradient L2 norm to this C inside the per-site phase (before
    # engine compression); dp_noise_multiplier > 0 then adds σ·C Gaussian
    # noise per leaf, counter-keyed by (dp_seed, site, round) so replays
    # are chunk/resume/packing-independent. Both 0 (default) statically
    # compiles the mechanism out — the epoch program is bit-identical to
    # the legacy one (S005 "dp-off"). Noise needs a clip (rejected
    # otherwise: unbounded sensitivity has no DP guarantee).
    dp_clip: float = 0.0
    dp_noise_multiplier: float = 0.0
    dp_seed: int = 0
    # δ for the reported (ε, δ); the RDP accountant (privacy/accounting.py)
    # surfaces ε per epoch in telemetry rows, logs.json, the report CLI and
    # the train_epsilon /statusz gauge
    dp_delta: float = 1e-5
    # > 0: stop the fit cleanly once the accountant's ε reaches this budget
    # — the epoch completes, its rotating checkpoint lands, a "dp-budget"
    # event is recorded, and the fit proceeds to best-state test (the
    # Preempted-style checkpointed exit, minus the nonzero exit code)
    dp_epsilon_budget: float = 0.0
    # secure-aggregation masked wires (privacy/secure_agg.py, dSGD only):
    # "mask" encodes each site's weighted delta on a shared fixed-point
    # grid and one-time-pads it with pairwise antisymmetric int32 masks
    # that cancel EXACTLY (integer arithmetic) in the unchanged psum-shaped
    # wire — masked == unmasked bit-exact, wire bytes unchanged
    # (S002-proven), int8/fp8 codecs refused (float grids shred the pads;
    # bf16 composes by pre-rounding the payload). "mask-nopads" is the
    # pads-zeroed VERIFICATION arm the bit-exactness claim is asserted
    # against; "off" (default) is the bit-identical legacy program
    # (S005 "secureagg-off").
    secure_agg: str = "off"
    secure_agg_seed: int = 0
    # personalized per-site heads (privacy/personalize.py): param-path
    # substring patterns naming head leaves kept OUT of aggregation
    # entirely — per-site head rows ride TrainState.personal (P(site),
    # checkpointed, rejoin-reset), each site trains and evaluates its own
    # head. () (default) compiles none of it (S005 "personalize-off").
    personalize: tuple = ()

    # -- helpers ---------------------------------------------------------

    def task_args(self):
        if self.task_id == NNComputation.TASK_FREE_SURFER:
            return self.fs_args
        if self.task_id == NNComputation.TASK_ICA:
            return self.ica_args
        if self.task_id == NNComputation.TASK_SMRI_3D:
            return self.smri3d_args
        if self.task_id == NNComputation.TASK_MULTIMODAL:
            return self.multimodal_args
        if self.task_id == NNComputation.TASK_LM:
            return self.lm_args
        raise ValueError(f"Invalid task: {self.task_id}")

    def replace(self, **kw) -> "TrainConfig":
        return dataclasses.replace(self, **kw)

    def with_overrides(self, overrides: dict) -> "TrainConfig":
        """Apply a flat override dict (e.g. one site's inputspec values).

        Unknown keys are routed into the active task-args block when they match
        one of its fields (the reference dumps everything into one cache dict;
        we keep the namespacing but accept the flat form).
        """
        # Accept compspec-style block keys ("FS-Classification_args") as well
        # as our field names ("fs_args").
        overrides = {_COMPSPEC_KEY_ALIASES.get(k, k): v for k, v in overrides.items()}
        cfg = self
        flat = {}
        for k, v in overrides.items():
            if k in _TRAIN_FIELDS and k not in _BLOCK_FIELDS:
                flat[k] = _coerce(_TRAIN_FIELDS[k], v)
        cfg = dataclasses.replace(cfg, **flat)

        # Dataclass-typed blocks: a dict override merges into the block
        # (the GUI sends plain JSON objects for type="object" fields).
        for args_name, args_cls in _BLOCK_FIELDS.items():
            current = getattr(cfg, args_name)
            if current is None and overrides.get(args_name) is None:
                continue  # don't materialize an unset optional block (even on
                # an explicit JSON null override)
            block = current or args_cls()
            fields = {f.name: f for f in dataclasses.fields(args_cls)}
            upd = {}
            if isinstance(overrides.get(args_name), dict):
                upd.update(
                    {k: _coerce(fields[k], v) for k, v in overrides[args_name].items() if k in fields}
                )
            elif dataclasses.is_dataclass(overrides.get(args_name)):
                block = overrides[args_name]
            if args_name != "pretrain_args":
                # flat keys route into every matching task-args block (the
                # reference dumps everything into one cache dict)
                upd.update(
                    {k: _coerce(fields[k], v) for k, v in overrides.items() if k in fields}
                )
            if upd:
                block = dataclasses.replace(block, **upd)
            if block is not getattr(cfg, args_name):
                cfg = dataclasses.replace(cfg, **{args_name: block})
        return cfg

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


_TRAIN_FIELDS = {f.name: f for f in dataclasses.fields(TrainConfig)}
_COMPSPEC_KEY_ALIASES = {
    "FS-Classification_args": "fs_args",
    "ICA-Classification_args": "ica_args",
    "sMRI-3D-Classification_args": "smri3d_args",
    "Multimodal-Classification_args": "multimodal_args",
    "LM-NextToken_args": "lm_args",
}
#: dataclass-typed TrainConfig fields that take dict merges, not raw replacement
_BLOCK_FIELDS = {
    "fs_args": FSArgs,
    "ica_args": ICAArgs,
    "smri3d_args": SMRI3DArgs,
    "multimodal_args": MultimodalArgs,
    "lm_args": AFMoEArgs,
    "pretrain_args": PretrainArgs,
}


def _coerce(f: dataclasses.Field, v: Any) -> Any:
    """Light type coercion: lists → tuples for tuple-typed fields, GUI string
    numbers → numbers are left as-is (the reference treats precision_bits as a
    string select)."""
    if isinstance(v, list) and (f.type or "").startswith("tuple"):
        return tuple(v)
    return v


# ---------------------------------------------------------------------------
# inputspec.json loading (simulator per-site overrides)
# ---------------------------------------------------------------------------


def load_inputspec(path: str) -> list[dict]:
    """Load a COINSTAC simulator ``inputspec.json``.

    The file is a list (one entry per site) of ``{"key": {"value": v}}``
    envelopes (reference ``datasets/test_fsl/inputspec.json``). A single dict is
    accepted as a 1-site spec. Returns a list of flat per-site override dicts.
    """
    with open(path) as fh:
        spec = json.load(fh)
    if isinstance(spec, dict):
        spec = [spec]
    out = []
    for site in spec:
        flat = {}
        for k, v in site.items():
            flat[k] = v.get("value") if isinstance(v, dict) and "value" in v else v
        out.append(flat)
    return out


def resolve_site_configs(
    base: TrainConfig, dataset_dir: str, num_sites: int | None = None
) -> list[TrainConfig]:
    """Build per-site configs for a ``datasets/<name>`` tree.

    Reads ``<dataset_dir>/inputspec.json`` if present; site i gets entry
    ``i % len(spec)``, cycling through the spec entries when there are more
    sites than entries.
    """
    spec_path = os.path.join(dataset_dir, "inputspec.json")
    overrides: Sequence[dict] = [{}]
    if os.path.exists(spec_path):
        overrides = load_inputspec(spec_path)
    n = num_sites if num_sites is not None else len(overrides)
    return [base.with_overrides(overrides[i % len(overrides)]) for i in range(n)]


# ---------------------------------------------------------------------------
# compspec schema export (GUI metadata parity)
# ---------------------------------------------------------------------------

#: GUI metadata for each flag: (type, source, group, order, conditional, label)
#: — preserved from reference ``compspec.json`` so the schema can be re-emitted.
COMPSPEC_META: dict[str, dict] = {
    "task_id": dict(type="select", source="owner", group="NN Params", order=3,
                    values=list(NNComputation.ALL),
                    label="Pick a NN task:"),
    "mode": dict(type="select", source="owner", group="NN Params", order=4,
                 values=["train", "test"], label="NN Mode:"),
    "agg_engine": dict(type="select", source="owner", group="NN Params", order=5,
                       values=list(AggEngine.ALL),
                       conditional=dict(variable="mode", value="train"),
                       label="Pick aggregation engine:"),
    "num_reducers": dict(type="number", source="owner", group="NN Params", order=6,
                         label="Number of reducers in the aggregator(Depends on number of sites):"),
    "batch_size": dict(type="number", source="owner", group="NN Params", order=7,
                       label="Batch size:"),
    "local_iterations": dict(
        type="number", source="owner", group="NN Params", order=8,
        label="Local gradient accumulation iterations"
              "(effective batch size = batch size * gradient accumulation iterations)"),
    "learning_rate": dict(type="number", source="owner", group="NN Params", order=9,
                          conditional=dict(variable="mode", value="train"),
                          label="Learning rate:"),
    "epochs": dict(type="number", source="owner", group="NN Params", order=10,
                   conditional=dict(variable="mode", value="train"), label="Epochs:"),
    "pretrain": dict(type="boolean", source="owner", group="NN Params", order=11,
                     label="Use the site with maximum data to pre-train locally as starting point:"),
    "pretrain_args": dict(type="object", source="owner", group="NN Params", order=12,
                          conditional=dict(variable="pretrain", value=True),
                          label="Pretraining arguments:"),
    "validation_epochs": dict(type="number", source="owner", group="NN Params", order=13,
                              conditional=dict(variable="mode", value="train"),
                              label="Run validation after every epochs:"),
    "precision_bits": dict(type="select", source="owner", group="NN Params", order=14,
                           # "16" = bf16 on TPU; "16-ieee" = the reference's
                           # literal fp16 payload (compat)
                           values=["32", "16", "16-ieee"],
                           conditional=dict(variable="mode", value="train"),
                           label="Floating point precision for payload:"),
    "pin_memory": dict(type="boolean", source="member", group="NN Params", order=15,
                       label="Pin Memory:"),
    "num_workers": dict(type="number", source="member", group="NN Params", order=16,
                        label="Number of workers:"),
    "patience": dict(type="number", source="owner", group="NN Params", order=17,
                     conditional=dict(variable="mode", value="train"),
                     label="Early stopping patience epochs:"),
    "split_ratio": dict(type="object", source="owner", group="NN Params", order=21,
                        label="Data split ratio for train, validation, test in the same order:"),
    "num_folds": dict(type="number", source="owner", group="NN Params", order=22,
                      label="Number of folds for K-Fold Cross Validation"
                            "(Mutually exclusive with split ratio):"),
    "fs_args": dict(type="object", source="owner", group="Computation", order=23,
                    conditional=dict(variable="task_id", value="FS-Classification"),
                    label="FreeSurfer classification parameters.",
                    compspec_key="FS-Classification_args"),
    "ica_args": dict(type="object", source="owner", group="Computation", order=26,
                     conditional=dict(variable="task_id", value="ICA-Classification"),
                     label="ICA classification parameters.",
                     compspec_key="ICA-Classification_args"),
    "smri3d_args": dict(type="object", source="owner", group="Computation", order=27,
                        conditional=dict(variable="task_id", value="sMRI-3D-Classification"),
                        label="3D sMRI classification parameters.",
                        compspec_key="sMRI-3D-Classification_args"),
    "multimodal_args": dict(type="object", source="owner", group="Computation", order=28,
                            conditional=dict(variable="task_id", value="Multimodal-Classification"),
                            label="Multimodal FS+ICA transformer parameters.",
                            compspec_key="Multimodal-Classification_args"),
    "lm_args": dict(type="object", source="owner", group="Computation", order=29,
                    conditional=dict(variable="task_id", value="LM-NextToken"),
                    label="Next-token language-model parameters (model_type afmoe | glm4_moe_lite | smallthinker).",
                    compspec_key="LM-NextToken_args"),
}


def export_compspec(cfg: TrainConfig | None = None) -> dict:
    """Emit a COINSTAC-style compspec dict (schema + defaults) for this build."""
    cfg = cfg or TrainConfig()
    inputs: dict[str, Any] = {}
    for name, meta in COMPSPEC_META.items():
        default = getattr(cfg, name)
        if dataclasses.is_dataclass(default):
            default = dataclasses.asdict(default)
        entry = {"default": _jsonable(default), **{k: v for k, v in meta.items() if k != "compspec_key"}}
        inputs[meta.get("compspec_key", name)] = entry
    return {
        "meta": {
            "name": "Decentralized Deep Artificial Neural Networks on TPU",
            "id": "dinunet-tpu",
            "version": "v1.0.0",
            "repository": "local",
            "description": "TPU-native federated NN training: sites on a mesh axis, "
                           "aggregation via XLA collectives.",
        },
        "computation": {"input": inputs, "output": {}, "type": "tpu-spmd"},
    }


def _jsonable(v):
    if isinstance(v, tuple):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {k: _jsonable(x) for k, x in v.items()}
    return v
