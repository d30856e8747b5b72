"""Task registry: task_id → (model builder, Dataset, DataHandle).

Mirrors the reference's dispatch tables (``local.py:40-47``,
``remote.py:28-35``) and the ``NNComputation``/``AggEngine`` enums
(``comps/__init__.py:7-16``). Adding a computation = registering one entry
(the reference's "Add new NN computation Here" comment, made a table).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Callable

from ..core.config import NNComputation, TrainConfig
from ..data.api import DataHandle, SiteDataset
from ..parallel.mesh import MODEL_AXIS
from ..data.freesurfer import FreeSurferDataset, FSVDataHandle
from ..data.ica import ICADataHandle, ICADataset
from ..data.multimodal import MultimodalDataHandle, MultimodalDataset
from ..data.smri import SMRIDataHandle, SMRIDataset
from ..data.tokens import TokenDataHandle, TokenDataset
from ..models.afmoe import (
    AFMOE,
    CONV,
    FULL,
    GLM4_MOE_LITE,
    LFM2_MOE,
    LINEAR,
    MODEL_TYPES,
    QWEN3_NEXT,
    SLIDING,
    SMALLTHINKER,
    AFMoE,
    Dims,
)
from ..models.cnn3d import SMRI3DNet
from ..models.icalstm import ICALstm
from ..models.msannet import MSANNet
from ..models.transformer import MultimodalNet


@dataclass(frozen=True)
class TaskSpec:
    task_id: str
    build_model: Callable[[TrainConfig], object]
    dataset_cls: type[SiteDataset]
    handle_cls: type[DataHandle]
    # per-task inference forward spec (serving/engine.py): how the serving
    # path shapes a request for this task. None = the task has no serving
    # surface yet (it cannot be loaded into an InferenceEngine).
    serving: "ServingSpec | None" = None


def _ica_windows(a) -> int:
    """Window count per subject — the reference's rule: count from
    window_size, offset from stride (data/ica.py window_timecourses)."""
    return int(a.temporal_size / a.window_size)


@dataclass(frozen=True)
class ServingSpec:
    """What the serving engine needs to know about a task, statically.

    ``sample_shape(cfg)`` is ONE example's feature shape (no batch axis) —
    the shape the microbatcher's row buckets pad to, and the shape a
    request's rows must carry. ``stream_shape(cfg)`` is one STREAMING
    timestep's shape (None = the task has no recurrent session semantics);
    ``streaming_ok(cfg)`` gates the streaming lane on the config actually
    being causal — the ICA-LSTM streams iff ``bidirectional=False`` (the
    reverse direction of a biLSTM reads the future; models/icalstm.py
    ICALstmStream)."""

    sample_shape: Callable[[TrainConfig], tuple]
    stream_shape: Callable[[TrainConfig], tuple] | None = None
    streaming_ok: Callable[[TrainConfig], bool] | None = None

    def supports_streaming(self, cfg: TrainConfig) -> bool:
        return (
            self.stream_shape is not None
            and (self.streaming_ok is None or bool(self.streaming_ok(cfg)))
        )


def _build_msannet(cfg: TrainConfig):
    a = cfg.fs_args
    return MSANNet(
        in_size=a.input_size,
        hidden_sizes=tuple(a.hidden_sizes),
        out_size=a.num_class,
    )


def _build_icalstm(cfg: TrainConfig):
    a = cfg.ica_args
    return ICALstm(
        input_size=a.input_size,
        hidden_size=a.hidden_size,
        bidirectional=a.bidirectional,
        num_cls=a.num_class,
        num_comps=a.num_components,
        window_size=a.window_size,
        num_layers=a.num_layers,
        compute_dtype=a.compute_dtype or None,
        # model_axis_size > 1 → window axis sharded over the mesh model axis
        # (ring LSTM; parallel/sequence.py)
        sequence_axis=MODEL_AXIS if cfg.model_axis_size > 1 else None,
        sequence_microbatches=cfg.sequence_microbatches,
    )


def _build_smri3d(cfg: TrainConfig):
    a = cfg.smri3d_args
    return SMRI3DNet(
        channels=tuple(a.channels), num_cls=a.num_class,
        compute_dtype=a.compute_dtype or None,
        # The fold itself is applied ONCE in the data pipeline
        # (data/smri.py:space_to_depth_222_np; 2.0-2.6x end-to-end vs the
        # per-step in-model fold, docs/bench_smri_s2d_ab_r5.jsonl). The
        # model still takes the flag: it recognizes pre-folded 8-channel
        # input and no-ops, but keeps honoring the configured architecture
        # if a custom dataset_cls bypasses the pipeline fold.
        space_to_depth=a.space_to_depth,
    )


def _build_multimodal(cfg: TrainConfig):
    a = cfg.multimodal_args
    attention = a.attention or ("ring" if cfg.model_axis_size > 1 else "local")
    if attention == "ring" and cfg.model_axis_size < 2:
        # forced ring without a model axis would crash much later with an
        # opaque "unbound axis name" trace error on the vmap-folded path
        raise ValueError(
            'attention="ring" needs model_axis_size >= 2 (the token axis '
            "shards over the mesh model axis)"
        )
    return MultimodalNet(
        fs_input_size=a.fs_input_size,
        num_comps=a.num_components,
        window_size=a.window_size,
        embed_dim=a.embed_dim,
        num_heads=a.num_heads,
        num_layers=a.num_layers,
        mlp_ratio=a.mlp_ratio,
        num_cls=a.num_class,
        attention=attention,
        axis_name=MODEL_AXIS if attention == "ring" else None,
        compute_dtype=a.compute_dtype or None,
    )


def _kinds(layout) -> tuple:
    """A published per-layer list of flags as attention kinds: 1 = sliding."""
    return tuple(SLIDING if on else FULL for on in layout)


def afmoe_layer_types(a) -> tuple:
    """One kind of token mixer a layer: ``layer_types`` as given, else the
    published period (a full layer every ``global_attn_every_n_layers``-th;
    latent attention has no window: every layer full; ``smallthinker``
    publishes its period as a list, ``sliding_window_layout``, full FIRST).
    ``lfm2_moe`` publishes the list itself, with no rule behind it, and is
    the one type whose layers may be ``conv``; ``qwen3_next`` the one whose
    layers may be ``linear_attention``, and its period puts them between the
    full layers."""
    if a.model_type == LFM2_MOE:
        kinds = tuple(a.layer_types)
        if not kinds or set(kinds) - {CONV, FULL}:
            raise ValueError(
                f"{LFM2_MOE} names every layer in layer_types, each {CONV!r} "
                f"or {FULL!r} (got {kinds})")
        return kinds
    if a.model_type == QWEN3_NEXT:
        n = a.global_attn_every_n_layers
        kinds = tuple(a.layer_types) or tuple(
            FULL if (i + 1) % n == 0 else LINEAR
            for i in range(a.num_hidden_layers))
        if set(kinds) - {LINEAR, FULL}:
            raise ValueError(
                f"a {QWEN3_NEXT} layer is {LINEAR!r} or {FULL!r} (got {kinds})")
        return kinds
    for kind, owner in ((CONV, LFM2_MOE), (LINEAR, QWEN3_NEXT)):
        if kind in a.layer_types:
            raise ValueError(
                f"a {kind!r} layer is {owner}'s: model_type {a.model_type!r} "
                "has attention on every layer")
    if a.layer_types:
        return tuple(a.layer_types)
    if a.model_type == GLM4_MOE_LITE:
        return (FULL,) * a.num_hidden_layers
    if a.model_type == SMALLTHINKER:
        return _kinds(a.sliding_window_layout)
    n = a.global_attn_every_n_layers
    return tuple(FULL if (i + 1) % n == 0 else SLIDING
                 for i in range(a.num_hidden_layers))


def _build_afmoe(cfg: TrainConfig):
    a = cfg.lm_args
    layer_types = afmoe_layer_types(a)
    if len(layer_types) != a.num_hidden_layers:
        raise ValueError(
            f"layer_types names {len(layer_types)} layers, num_hidden_layers "
            f"is {a.num_hidden_layers}")
    held = a.experts_held or a.num_experts
    if a.first_expert < 0 or a.first_expert + held > a.num_experts:
        raise ValueError(
            f"experts {a.first_expert}..{a.first_expert + held - 1} are not "
            f"among the model's {a.num_experts}")
    if a.model_type not in MODEL_TYPES:
        raise ValueError(
            f"model_type {a.model_type!r} is none of {', '.join(MODEL_TYPES)}")
    latent = a.model_type == GLM4_MOE_LITE
    if latent:
        widths = (a.q_lora_rank, a.kv_lora_rank, a.qk_nope_head_dim,
                  a.qk_rope_head_dim, a.v_head_dim)
        if min(widths) <= 0 or set(layer_types) != {FULL}:
            raise ValueError(
                f"{GLM4_MOE_LITE} needs its five latent widths (got "
                f"{widths}) and full attention on every layer")
        if a.qk_nope_head_dim + a.qk_rope_head_dim != a.v_head_dim:
            raise ValueError(
                "the attention paths carry one head width: qk_nope_head_dim "
                "+ qk_rope_head_dim must equal v_head_dim")
    if a.model_type == SMALLTHINKER:
        # attention applies rotary positions iff it has a window, so the
        # type's two published lists have to say the same of every layer
        if _kinds(a.rope_layout) != layer_types:
            raise ValueError(
                f"rope_layout {tuple(a.rope_layout)} disagrees with the "
                f"layers' windows {layer_types}: a {SMALLTHINKER} layer has "
                "a rotary term iff it has a window")
        if a.num_dense_layers or a.num_shared_experts:
            raise ValueError(
                f"{SMALLTHINKER} has no dense layer and no shared expert "
                f"(got num_dense_layers {a.num_dense_layers}, "
                f"num_shared_experts {a.num_shared_experts})")
    if a.model_type == LFM2_MOE:
        if a.num_shared_experts:
            raise ValueError(
                f"{LFM2_MOE} has no shared expert (got num_shared_experts "
                f"{a.num_shared_experts})")
        if a.head_dim * a.num_attention_heads != a.hidden_size:
            raise ValueError(
                f"{LFM2_MOE} publishes no head width: its heads split the "
                f"hidden size, {a.hidden_size} / {a.num_attention_heads}, "
                f"and head_dim says {a.head_dim}")
    if a.model_type == QWEN3_NEXT:
        heads = (a.linear_num_key_heads, a.linear_num_value_heads,
                 a.linear_key_head_dim, a.linear_value_head_dim)
        if min(heads) <= 0 or a.linear_num_value_heads % a.linear_num_key_heads:
            raise ValueError(
                f"{QWEN3_NEXT} needs its Gated DeltaNet heads and widths, the "
                f"value heads a whole number of key heads' (got {heads})")
        if a.num_shared_experts != 1 or a.num_dense_layers:
            raise ValueError(
                f"{QWEN3_NEXT} has one gated shared expert and no dense layer "
                f"(got num_shared_experts {a.num_shared_experts}, "
                f"num_dense_layers {a.num_dense_layers})")
    rotated = a.head_dim * a.partial_rotary_factor
    if a.partial_rotary_factor != 1 and (
            a.model_type != QWEN3_NEXT or not 0 < rotated < a.head_dim
            or rotated != int(rotated) or int(rotated) % 2):
        raise ValueError(
            f"partial_rotary_factor {a.partial_rotary_factor} is {QWEN3_NEXT}'s "
            f"and must leave an even number of a head's {a.head_dim} dimensions")
    if a.num_nextn_predict_layers not in ((0, 1) if latent else (0,)):
        raise ValueError(
            f"num_nextn_predict_layers {a.num_nextn_predict_layers}: one "
            f"multi-token-prediction module, and only of {GLM4_MOE_LITE}")
    # Dims reads AFMoEArgs' own field names; what differs is resolved here
    resolved = dict(
        experts_held=held, layer_types=layer_types,
        rope_theta=float(a.rope_theta), compute_dtype=a.compute_dtype or None,
    )
    return AFMoE(
        dims=Dims(**{
            f.name: resolved.get(f.name, getattr(a, f.name, f.default))
            for f in dataclasses.fields(Dims)
        }),
        vocab_rows=a.vocab_rows or a.vocab_size,
        mup_enabled=a.mup_enabled and a.model_type == AFMOE,
        tie_word_embeddings=a.tie_word_embeddings,
        loss_block=a.loss_block,
    )


TASKS: dict[str, TaskSpec] = {
    NNComputation.TASK_FREE_SURFER: TaskSpec(
        NNComputation.TASK_FREE_SURFER, _build_msannet, FreeSurferDataset,
        FSVDataHandle,
        serving=ServingSpec(
            sample_shape=lambda cfg: (cfg.fs_args.input_size,),
        ),
    ),
    NNComputation.TASK_ICA: TaskSpec(
        NNComputation.TASK_ICA, _build_icalstm, ICADataset, ICADataHandle,
        serving=ServingSpec(
            sample_shape=lambda cfg: (
                _ica_windows(cfg.ica_args),
                cfg.ica_args.num_components,
                cfg.ica_args.window_size,
            ),
            # one streaming timestep = one temporal window [C, W]
            stream_shape=lambda cfg: (
                cfg.ica_args.num_components, cfg.ica_args.window_size,
            ),
            streaming_ok=lambda cfg: not cfg.ica_args.bidirectional,
        ),
    ),
    NNComputation.TASK_SMRI_3D: TaskSpec(
        NNComputation.TASK_SMRI_3D, _build_smri3d, SMRIDataset, SMRIDataHandle,
        serving=ServingSpec(
            # pipeline-folded shape when space_to_depth is on (data/smri.py
            # space_to_depth_222_np — requests arrive pre-folded, like the
            # training inventory), the raw single-channel volume otherwise
            sample_shape=lambda cfg: (
                tuple(d // 2 for d in cfg.smri3d_args.volume_shape) + (8,)
                if cfg.smri3d_args.space_to_depth
                else tuple(cfg.smri3d_args.volume_shape)
            ),
        ),
    ),
    NNComputation.TASK_MULTIMODAL: TaskSpec(
        NNComputation.TASK_MULTIMODAL, _build_multimodal,
        MultimodalDataset, MultimodalDataHandle,
        serving=ServingSpec(
            sample_shape=lambda cfg: (
                cfg.multimodal_args.fs_input_size
                + _ica_windows(cfg.multimodal_args)
                * cfg.multimodal_args.num_components
                * cfg.multimodal_args.window_size,
            ),
        ),
    ),
    NNComputation.TASK_LM: TaskSpec(
        NNComputation.TASK_LM, _build_afmoe, TokenDataset, TokenDataHandle,
        serving=ServingSpec(
            # a sample is the ids the model reads plus the last target
            sample_shape=lambda cfg: (cfg.lm_args.seq_len + 1,),
        ),
    ),
}


def get_task(task_id: str) -> TaskSpec:
    if task_id not in TASKS:
        raise ValueError(f"Invalid task: {task_id!r} (have {sorted(TASKS)})")
    return TASKS[task_id]


def register_task(spec: TaskSpec):
    TASKS[spec.task_id] = spec


def task_cache(cfg: TrainConfig) -> dict:
    """The flat cache dict datasets consume (the reference merges GUI input
    into one cache; our datasets read the same keys)."""
    return dataclasses.asdict(cfg.task_args())
