"""Graduation of the dormant workloads (r15, ROADMAP item 4 first slice):
the multimodal FS+ICA transformer (models/transformer.py) and MSANNet
(models/msannet.py) as registry-wired, tier-1-smoke-tested tasks — forward
shape/dtype contracts, a real demo-tree fit through the full runner stack,
and the per-task serving specs (runner/registry.py ServingSpec) that the
serving engine sizes its shape buckets from."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinunet_implementations_tpu.core.config import NNComputation, TrainConfig
from dinunet_implementations_tpu.data.demo import (
    make_fs_demo_tree,
    make_ica_demo_tree,
    make_multimodal_demo_tree,
)
from dinunet_implementations_tpu.models import MSANNet, MultimodalNet
from dinunet_implementations_tpu.runner.fed_runner import FedRunner
from dinunet_implementations_tpu.runner.registry import get_task


# ---------------------------------------------------------------------------
# forward shape/dtype contracts
# ---------------------------------------------------------------------------


def _mm_model(**kw):
    return MultimodalNet(
        fs_input_size=10, num_comps=6, window_size=4, embed_dim=16,
        num_heads=4, num_layers=2, num_cls=2, **kw,
    )


def _mm_input(B=5):
    # packed [fs + S*C*W] vector, S = temporal//window handled by the caller:
    # here 3 windows of 6x4
    return jax.random.normal(jax.random.PRNGKey(0), (B, 10 + 3 * 6 * 4))


def test_multimodal_forward_shape_dtype():
    m = _mm_model()
    x = _mm_input()
    variables = m.init({"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)}, x, train=True)
    out = m.apply(variables, x, train=False)
    assert out.shape == (5, 2)
    assert out.dtype == jnp.float32


def test_multimodal_bf16_compute_keeps_f32_logits():
    """Mixed precision is internal: bf16 matmuls, f32 residual/softmax —
    the classifier output must stay full precision."""
    m = _mm_model(compute_dtype="bfloat16")
    x = _mm_input()
    variables = m.init({"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)}, x, train=True)
    out = m.apply(variables, x, train=False)
    assert out.shape == (5, 2)
    assert out.dtype == jnp.float32
    # and stays close to the f32 reference (bf16 is a perturbation, not a
    # different function)
    ref = _mm_model().apply(variables, x, train=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=0.1)


def test_multimodal_eval_deterministic_under_jit():
    m = _mm_model()
    x = _mm_input()
    variables = m.init({"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)}, x, train=True)
    fwd = jax.jit(lambda v, xx: m.apply(v, xx, train=False))
    np.testing.assert_array_equal(np.asarray(fwd(variables, x)), np.asarray(fwd(variables, x)))


def test_msannet_forward_shape_dtype():
    m = MSANNet(in_size=7, hidden_sizes=(12, 8), out_size=3)
    x = jax.random.normal(jax.random.PRNGKey(2), (6, 7))
    variables = m.init(jax.random.PRNGKey(0), x, train=True)
    out = m.apply(variables, x, train=False)
    assert out.shape == (6, 3)
    assert out.dtype == jnp.float32
    # no running stats tracked (track_running_stats=False everywhere)
    assert "batch_stats" not in variables


# ---------------------------------------------------------------------------
# demo-tree fit smoke — the full runner stack on the graduated task
# ---------------------------------------------------------------------------


def test_multimodal_demo_tree_fit_smoke(tmp_path):
    root = make_multimodal_demo_tree(
        str(tmp_path / "mm"), n_sites=2, subjects=16, n_features=8, comps=4,
        temporal=20, window=5, stride=5,
    )
    runner = FedRunner(
        TrainConfig(
            task_id=NNComputation.TASK_MULTIMODAL, epochs=1, batch_size=4,
            patience=2,
        ),
        data_path=root, out_dir=str(tmp_path / "out"),
    )
    res = runner.run(folds=[0], verbose=False)
    assert len(res) == 1
    loss, metric = res[0]["test_metrics"][0]
    assert np.isfinite(loss)
    assert 0.0 <= metric <= 1.0


# ---------------------------------------------------------------------------
# serving specs: the registry's shape contract matches the real data
# ---------------------------------------------------------------------------


def _first_site_arrays(cfg, root):
    from dinunet_implementations_tpu.core.config import resolve_site_configs
    from dinunet_implementations_tpu.data.api import build_site_dataset
    from dinunet_implementations_tpu.runner.fed_runner import discover_site_dirs
    from dinunet_implementations_tpu.runner.registry import task_cache

    dirs = discover_site_dirs(root)
    scfg = resolve_site_configs(cfg, root, num_sites=len(dirs))[0]
    spec = get_task(scfg.task_id)
    ds = build_site_dataset(
        spec.dataset_cls, spec.handle_cls, task_cache(scfg),
        {"baseDirectory": dirs[0]},
    )
    return scfg, spec, ds.as_arrays()


@pytest.mark.parametrize("task_id,maker", [
    (NNComputation.TASK_FREE_SURFER,
     lambda p: make_fs_demo_tree(p, n_sites=1, subjects=6)),
    (NNComputation.TASK_ICA,
     lambda p: make_ica_demo_tree(p, n_sites=1, subjects=6, comps=8,
                                  temporal=40, window=10, stride=10)),
    (NNComputation.TASK_MULTIMODAL,
     lambda p: make_multimodal_demo_tree(p, n_sites=1, subjects=6,
                                         n_features=8, comps=4, temporal=20,
                                         window=5, stride=5)),
])
def test_serving_spec_matches_dataset_shape(tmp_path, task_id, maker):
    """ServingSpec.sample_shape must equal the per-example feature shape the
    data pipeline actually materializes — the microbatcher pads requests
    into buckets of exactly this shape."""
    root = maker(str(tmp_path / "tree"))
    scfg, spec, arrs = _first_site_arrays(TrainConfig(task_id=task_id), root)
    assert spec.serving is not None
    assert tuple(spec.serving.sample_shape(scfg)) == arrs.inputs.shape[1:]


def test_every_task_has_a_serving_spec():
    for task_id in NNComputation.ALL:
        assert get_task(task_id).serving is not None, task_id


def test_lm_sample_is_the_sequence_plus_its_last_target():
    cfg = TrainConfig(task_id=NNComputation.TASK_LM)
    assert get_task(cfg.task_id).serving.sample_shape(cfg) == (8193,)
    short = cfg.with_overrides({"lm_args": {"seq_len": 32}})
    assert get_task(cfg.task_id).serving.sample_shape(short) == (33,)


def test_ica_streaming_gate_is_causality():
    """The streaming lane exists only for the causal (unidirectional)
    config — a biLSTM's reverse direction reads the future."""
    spec = get_task(NNComputation.TASK_ICA)
    uni = TrainConfig(task_id=NNComputation.TASK_ICA).with_overrides(
        {"ica_args": {"bidirectional": False}}
    )
    bi = TrainConfig(task_id=NNComputation.TASK_ICA)
    assert spec.serving.supports_streaming(uni)
    assert not spec.serving.supports_streaming(bi)
    assert tuple(spec.serving.stream_shape(uni)) == (
        uni.ica_args.num_components, uni.ica_args.window_size,
    )
    # non-recurrent tasks never stream
    assert not get_task(NNComputation.TASK_FREE_SURFER).serving.supports_streaming(
        TrainConfig()
    )