"""Device scopes and kernel names (telemetry/scopes.py, ops/lstm_pallas.py).

The epoch program names its own parts: five ``jax.named_scope``s and two
Pallas kernel names (and, of the next-token model with latent attention, three
scopes inside ``model/fwd_bwd`` and the attention kernels' three names). They are metadata: (a) every scope that applies is in
the lowered program's debug info, (b) with the scopes taken away the
lowering is the same program, (c) every ``pl.pallas_call`` of the LSTM
kernels passes a distinct ``name=`` from the file's constants, (d) every
kernel name is read by a metric file of the benchmark and every module of
``ops/`` is imported by a model or an engine.
"""

import ast
import contextlib
import inspect
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

from dinunet_implementations_tpu.checks.lowering import diff_report
from dinunet_implementations_tpu.checks.semantic import (
    TraceCell,
    build_cell_inputs,
)
from dinunet_implementations_tpu.ops import lstm_pallas
from dinunet_implementations_tpu.telemetry import scopes
from dinunet_implementations_tpu.trainer.steps import (
    epoch_program_artifacts,
    make_train_epoch_fn,
)

#: the device pipeline, so that the on-device gather is in the program
CELLS = {
    "dSGD": TraceCell("dSGD", "vmap", "device"),
    # small ranks keep the trace cheap
    "rankDAD": TraceCell("rankDAD", "vmap", "device",
                         engine_kw=(("dad_num_pow_iters", 2),
                                    ("dad_reduction_rank", 2))),
}
SCOPES = {name: getattr(scopes, name)
          for name in ("GATHER", "MODEL", "ENGINE", "POWERITER", "OPTIMIZER")}


def _lowered_text(cell: TraceCell, debug_info: bool) -> str:
    task, engine, opt, _, args, mesh = build_cell_inputs(cell)
    fn = make_train_epoch_fn(task, engine, opt, mesh=mesh,
                             pipeline=cell.pipeline)
    _, low, _ = epoch_program_artifacts(fn, *args, lowered=True)
    return low.as_text(debug_info=debug_info)


def _has_scope(text: str, name: str) -> bool:
    """The scope as whole path pieces of some op's name (``jit(f)/a/b/dot``,
    ``vmap(a/b)/dot``), not as a part of a longer word."""
    return re.search(r"(?<![A-Za-z0-9_])" + re.escape(name)
                     + r"(?![A-Za-z0-9_])", text) is not None


@pytest.fixture(scope="module")
def lowered():
    return {engine: _lowered_text(cell, debug_info=True)
            for engine, cell in CELLS.items()}


def test_scope_constants_are_distinct_paths():
    assert len(set(SCOPES.values())) == len(SCOPES)
    for s in SCOPES.values():
        assert s == s.strip("/") and " " not in s


@pytest.mark.parametrize("scope", sorted(SCOPES))
@pytest.mark.parametrize("engine", sorted(CELLS))
def test_scope_is_in_the_lowered_epoch_program(lowered, engine, scope):
    text, name = lowered[engine], SCOPES[scope]
    if scope == "POWERITER":
        # nests under the engine's scope, and only rankDAD iterates; a
        # transform wraps the scope it maps: vmap(engine/aggregate)/poweriter
        nested = re.escape(scopes.ENGINE) + r"\)*/" + re.escape(name) + r"/while"
        assert bool(re.search(nested, text)) == (engine == "rankDAD")
        assert _has_scope(text, name) == (engine == "rankDAD")
    else:
        assert _has_scope(text, name), f"{name} missing from the {engine} program"


@pytest.mark.parametrize("engine", sorted(CELLS))
def test_scopes_change_metadata_and_nothing_else(monkeypatch, engine):
    scoped = _lowered_text(CELLS[engine], debug_info=False)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _lowered_text(CELLS[engine], debug_info=True)
    assert not _has_scope(plain, scopes.MODEL)  # the patch reached the program
    assert diff_report(plain, scoped, "no-scopes", "scoped") is None


def _pallas_calls():
    tree = ast.parse(inspect.getsource(lstm_pallas))
    return [n for n in ast.walk(tree)
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
            and n.func.attr == "pallas_call"]


def test_every_lstm_pallas_call_is_named_from_the_constants():
    calls = _pallas_calls()
    assert len(calls) == len(lstm_pallas.KERNEL_NAMES) == 2
    used = []
    for call in calls:
        kw = {k.arg: k.value for k in call.keywords}
        assert "name" in kw, f"pallas_call at line {call.lineno} has no name="
        assert isinstance(kw["name"], ast.Name), call.lineno
        used.append(getattr(lstm_pallas, kw["name"].id))
    assert sorted(used) == sorted(set(lstm_pallas.KERNEL_NAMES))


def _whole_word(name: str) -> re.Pattern:
    return re.compile(r"(?<![A-Za-z0-9])" + name + r"(?![A-Za-z0-9])")


@pytest.mark.parametrize("name", lstm_pallas.KERNEL_NAMES)
def test_kernel_name_is_told_from_the_others_as_a_whole_word(name):
    """The TPU compiler names a Mosaic call after the sanitized scope
    (``%vmap_jvp_lstm_fwd__.6``); a metric tells ``lstm_fwd`` from a longer
    word that ends in it by the letters around it."""
    word = _whole_word(name)
    assert re.fullmatch(r"[a-z]+(_[a-z]+)*", name)
    assert word.search(f"%vmap_jvp_bi{name}__.6") is None
    for other in lstm_pallas.KERNEL_NAMES:
        hit = word.search(f"%vmap_jvp_{other}__.6") is not None
        assert hit == (other == name), (name, other)


REPO = pathlib.Path(__file__).resolve().parents[1]
PACKAGE = REPO / "dinunet_implementations_tpu"


def _metric_files_reading(module, name: str, glob: str = "*.json") -> list[str]:
    """The metric files whose pattern holds the kernel name ``name`` as it
    stands; each one's ``what`` must name ``module``'s constant for it."""
    constant = next(k for k, v in vars(module).items()
                    if k.isupper() and v == name)
    readers = []
    for path in sorted((REPO / "benchmarks" / "layer_metrics").glob(glob)):
        metric = json.loads(path.read_text())
        pattern = str(metric.get("args", {}).get("pattern", ""))
        if _whole_word(name).search(pattern):
            assert re.search(rf"\b{constant}\b", metric["what"]), path.name
            readers.append(path.name)
    return readers


@pytest.mark.parametrize("name", lstm_pallas.KERNEL_NAMES)
def test_every_kernel_name_is_read_by_a_metric_of_the_benchmark(name):
    """A kernel no metric file reads is a kernel no cell can judge: its
    metric's pattern holds the name as it stands and its ``what`` names the
    constant, so a new kernel comes with its metric or not at all."""
    assert _metric_files_reading(lstm_pallas, name), name


def test_every_ops_module_is_imported_by_a_model_or_an_engine():
    imported = set()
    for path in [*(PACKAGE / "models").glob("*.py"),
                 *(PACKAGE / "engines").glob("*.py")]:
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.ImportFrom) and node.module:
                parts = node.module.split(".")
                if parts[-1] == "ops":  # from ..ops import <module>
                    imported.update(a.name for a in node.names)
                elif "ops" in parts[:-1] and node.level:  # from ..ops.<module> import
                    imported.add(parts[parts.index("ops") + 1])
    modules = {p.stem for p in (PACKAGE / "ops").glob("*.py")} - {"__init__"}
    assert modules and modules <= imported, sorted(modules - imported)


# -- the next-token model with latent attention (ISSUE 32) ----------------------

LATENT_SCOPES = ("ATTENTION_MLA", "MLA_LATENT", "MTP")
def _latent_lowered_text(debug_info: bool) -> str:
    """The lowered epoch program of a small latent-attention model with its
    second prediction depth: 2 sites folded, dSGD, the device pipeline."""
    from test_glm4_moe_lite import TOY

    from dinunet_implementations_tpu.core.config import NNComputation, TrainConfig
    from dinunet_implementations_tpu.runner.registry import get_task
    from dinunet_implementations_tpu.trainer.loop import FederatedTrainer

    cfg = TrainConfig(task_id=NNComputation.TASK_LM, num_sites=2,
                      batch_size=1).with_overrides(
                          {"lm_args": {**TOY, "num_nextn_predict_layers": 1}})
    trainer = FederatedTrainer(cfg, get_task(cfg.task_id).build_model(cfg), None)
    state = trainer.init_state(jnp.ones((1, 33), jnp.int32), num_sites=2)
    return trainer.epoch_fn.lower(
        state, jnp.zeros((2, 3, 33), jnp.int32), jnp.zeros((2, 3), jnp.int32),
        jnp.zeros((2, 2, 1), jnp.int32), None, None, None, None,
    ).as_text(debug_info=debug_info)


@pytest.fixture(scope="module")
def latent_lowered():
    return _latent_lowered_text(debug_info=True)


@pytest.mark.parametrize("scope", LATENT_SCOPES)
def test_latent_scope_is_in_the_lowered_epoch_program(latent_lowered, scope):
    name, inside = getattr(scopes, scope), re.escape(scopes.MODEL) + r"\)*/.*"
    assert name.startswith("model/")
    if scope == "MLA_LATENT":  # nests in the layer's attention scope
        inside += re.escape(scopes.ATTENTION_MLA) + r"\)*/[^\"]*"
    assert re.search(r"(?<![A-Za-z0-9_])" + inside + re.escape(name)
                     + r"(?![A-Za-z0-9_])", latent_lowered), name
    if scope == "MTP":  # the second depth wears the block's scopes inside
        assert re.search(re.escape(name) + r"\)*/.*"
                         + re.escape(scopes.ATTENTION_MLA), latent_lowered)
        assert re.search(re.escape(name) + r"\)*/.*"
                         + re.escape(scopes.LM_HEAD), latent_lowered)


def test_latent_scopes_change_metadata_and_nothing_else(monkeypatch):
    scoped = _latent_lowered_text(debug_info=False)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _latent_lowered_text(debug_info=True)
    assert not _has_scope(plain, scopes.ATTENTION_MLA)  # the patch reached it
    assert diff_report(plain, scoped, "no-scopes", "scoped") is None


def test_every_kernel_latent_attention_launches_is_read_by_a_new_metric():
    """The kernels latent attention launches are the three splash-attention
    calls: each name is read, as a whole word, by metric files of the new
    cell (``mla_attention_*``), whose ``what`` names the constant."""
    from dinunet_implementations_tpu.models import afmoe

    names = (afmoe.ATTN_FWD, afmoe.ATTN_DQ, afmoe.ATTN_DKV)
    for name in names:
        mine = _metric_files_reading(afmoe, name, "mla_attention_*.json")
        assert f"mla_attention_{name.rsplit('_', 1)[1]}_kernel_ms_per_round.json" in mine
        for other in names:  # told from the others as a whole word
            hit = _whole_word(name).search(f"%{other}_residuals.105") is not None
            assert hit == (other == name)
    both = json.loads((REPO / "benchmarks" / "layer_metrics"
                       / "mla_attention_kernel_roofline.json").read_text())
    assert all(re.search(both["args"]["pattern"],
                         f'%{n}_no_residuals.7 = x custom_call_target="tpu_custom_call"')
               for n in names)
    assert all(re.search(rf"\b{c}\b", both["what"])
               for c in ("ATTN_FWD", "ATTN_DQ", "ATTN_DKV"))


# -- the next-token model whose router reads the block's input (ISSUE 34) -------


def _block_scopes(model_type: str) -> list[str]:
    """The name stacks of a block's equations, in the order it traces them."""
    from dinunet_implementations_tpu.models import afmoe

    if model_type == afmoe.SMALLTHINKER:
        from test_smallthinker import build
    elif model_type == afmoe.GLM4_MOE_LITE:
        from test_glm4_moe_lite import build
    else:
        from test_afmoe import build
    _, model, _ = build()
    layer = len(model.dims.layer_types) - 1  # an expert layer in every toy
    block = afmoe.Block(model.dims, layer)
    h = jnp.zeros((1, 32, model.dims.hidden_size))
    params = jax.eval_shape(block.init, jax.random.PRNGKey(0), h)
    jaxpr = jax.make_jaxpr(block.apply)(params, h).jaxpr
    return [str(e.source_info.name_stack) for e in jaxpr.eqns]


@pytest.mark.parametrize("model_type,route_first", [
    ("smallthinker", True), ("afmoe", False), ("glm4_moe_lite", False)])
def test_the_router_sits_before_attention_in_smallthinkers_block_only(
        model_type, route_first):
    """``model/moe_route`` holds the router's product and the choice: of the
    type that routes on the block's input it comes before the layer's
    attention scope, of the other two after it; the experts' products
    (``model/moe_experts``) come after attention in all three."""
    stacks = _block_scopes(model_type)

    def first(*names):
        return next(i for i, s in enumerate(stacks) if any(n in s for n in names))

    attention = first(scopes.ATTENTION_FULL, scopes.ATTENTION_WINDOW,
                      scopes.ATTENTION_MLA)
    assert (first(scopes.MOE_ROUTE) < attention) == route_first
    assert first(scopes.MOE_EXPERTS) > attention
    shared = any(scopes.MOE_SHARED in s for s in stacks)
    assert shared == (model_type != "smallthinker")  # it has no shared expert


def test_every_kernel_smallthinker_launches_is_read_by_a_new_metric():
    """No new kernel name: the block launches the three splash-attention
    calls, and each is read, as a whole word, by a metric file of the new
    cell (``smallthinker_attention_*``), whose ``what`` names the constant;
    the grouped products are read by name (``ragged-dot``)."""
    from dinunet_implementations_tpu.models import afmoe

    for name in (afmoe.ATTN_FWD, afmoe.ATTN_DQ, afmoe.ATTN_DKV):
        mine = _metric_files_reading(afmoe, name, "smallthinker_attention_*.json")
        assert (f"smallthinker_attention_{name.rsplit('_', 1)[1]}"
                "_kernel_ms_per_round.json") in mine
    grouped = json.loads((REPO / "benchmarks" / "layer_metrics" /
                          "smallthinker_moe_grouped_matmul_ms_per_round.json"
                          ).read_text())
    assert re.search(grouped["args"]["pattern"], "ragged-dot-none.3")


# -- the next-token model whose token mixer is a short convolution (ISSUE 38) ----


def test_the_short_convolution_wears_its_scope_where_attention_sits():
    """``model/short_conv`` holds a ``conv`` layer's operator, its two
    projections included, inside ``model/fwd_bwd``; no attention scope is on
    such a layer, the attention layer of the same model wears
    ``model/attention_full``, and the expert layer's scopes come after the
    token mixer in both."""
    from test_lfm2_moe import KINDS, build

    from dinunet_implementations_tpu.models import afmoe
    from dinunet_implementations_tpu.trainer.loop import FederatedTrainer

    assert scopes.SHORT_CONV == "model/short_conv"
    cfg, model, _ = build(num_sites=2, batch_size=1)
    for layer, kind in enumerate(KINDS):
        block = afmoe.Block(model.dims, layer)
        h = jnp.zeros((1, 32, model.dims.hidden_size))
        params = jax.eval_shape(block.init, jax.random.PRNGKey(0), h)
        eqns = jax.make_jaxpr(block.apply)(params, h).jaxpr.eqns
        stacks = [str(e.source_info.name_stack) for e in eqns]
        mixer = scopes.SHORT_CONV if kind == afmoe.CONV else scopes.ATTENTION_FULL
        other = scopes.ATTENTION_FULL if kind == afmoe.CONV else scopes.SHORT_CONV
        assert any(mixer in s for s in stacks) and not any(other in s for s in stacks)
        # the operator's projections are inside: two products on a conv layer
        dots = [s for e, s in zip(eqns, stacks)
                if e.primitive.name == "dot_general" and mixer in s]
        assert len(dots) == (2 if kind == afmoe.CONV else 4)
        if layer:  # an expert layer: the router and the experts after the mixer
            last = max(i for i, s in enumerate(stacks) if mixer in s)
            assert min(i for i, s in enumerate(stacks)
                       if scopes.MOE_ROUTE in s) > last
    trainer = FederatedTrainer(cfg, model, None)
    state = trainer.init_state(jnp.ones((1, 33), jnp.int32), num_sites=2)
    text = trainer.epoch_fn.lower(
        state, jnp.zeros((2, 3, 33), jnp.int32), jnp.zeros((2, 3), jnp.int32),
        jnp.zeros((2, 2, 1), jnp.int32), None, None, None, None,
    ).as_text(debug_info=True)
    assert re.search(re.escape(scopes.MODEL) + r"\)*/.*"
                     + re.escape(scopes.SHORT_CONV) + r"(?![A-Za-z0-9_])", text)


def test_every_kernel_lfm2_launches_is_read_by_a_new_metric():
    """No new kernel name: the one attention layer launches the three
    splash-attention calls, and each is read, as a whole word, by a metric
    file of the new cell (``lfm2_attention_*``), whose ``what`` names the
    constant; the grouped products are read by name (``ragged-dot``)."""
    from dinunet_implementations_tpu.models import afmoe

    for name in (afmoe.ATTN_FWD, afmoe.ATTN_DQ, afmoe.ATTN_DKV):
        mine = _metric_files_reading(afmoe, name, "lfm2_attention_*.json")
        assert (f"lfm2_attention_{name.rsplit('_', 1)[1]}"
                "_kernel_ms_per_round.json") in mine
    grouped = json.loads((REPO / "benchmarks" / "layer_metrics" /
                          "lfm2_moe_grouped_matmul_ms_per_round.json"
                          ).read_text())
    assert re.search(grouped["args"]["pattern"], "ragged-dot-none.3")


# -- the host half: the fit loop's own spans (ISSUE 36) ---------------------------

HOST_CONSTANTS = ("PLAN_WAIT", "PLAN_BUILD", "EPOCH_INPUTS", "EPOCH_DISPATCH",
                  "LOSS_FETCH", "EPOCH_ACCOUNT", "INVENTORY_UPLOAD")
SPAN_METRICS = sorted(
    p.name for p in (REPO / "benchmarks" / "layer_metrics").glob("*.json")
    if json.loads(p.read_text()).get("reader") == "program_span_ms")


def test_host_span_constants_are_distinct_plain_names():
    names = [getattr(scopes, c) for c in HOST_CONSTANTS]
    assert tuple(names) == scopes.HOST_SPANS and len(set(names)) == len(names)
    for name in names:
        assert re.fullmatch(r"[a-z]+(-[a-z]+)*", name), name
    assert scopes.HOST_PREFIX.endswith("/")
    assert not "bench/".startswith(scopes.HOST_PREFIX)  # the harness's own


def test_the_benchmark_reads_the_loops_spans_through_eight_metric_files():
    assert len(SPAN_METRICS) == 8


@pytest.mark.parametrize("metric_file", SPAN_METRICS)
def test_every_host_span_a_metric_reads_exists(metric_file):
    """The rule ``KERNEL_NAMES`` is held to, for the host half: a metric file
    that reads a program span names one that ``scopes.HOST_SPANS`` lists, as
    it stands, and its ``what`` names the constant — a renamed span fails
    here instead of falling silent on the chip."""
    metric = json.loads(
        (REPO / "benchmarks" / "layer_metrics" / metric_file).read_text())
    spans = metric["args"]["span"]
    for name in [spans] if isinstance(spans, str) else spans:
        assert name in scopes.HOST_SPANS, (metric_file, name)
        constant = next(c for c in HOST_CONSTANTS if getattr(scopes, c) == name)
        assert re.search(rf"\b{constant}\b", metric["what"]), (metric_file,
                                                               constant)


def test_every_span_of_the_fit_loop_is_named_from_the_constants():
    """Every ``tracer.span(...)`` of trainer/loop.py's per-epoch path and of
    trainer/prefetch.py takes its name from ``scopes``; each host constant is
    opened somewhere, with ``epoch=``."""
    opened = {}
    for rel in ("trainer/loop.py", "trainer/prefetch.py"):
        tree = ast.parse((PACKAGE / rel).read_text())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "span" and node.args
                    and isinstance(node.args[0], ast.Attribute)
                    and getattr(node.args[0].value, "id", "") == "scopes"):
                opened.setdefault(node.args[0].attr, []).append(
                    {k.arg for k in node.keywords})
    assert set(opened) == set(HOST_CONSTANTS)
    assert all("epoch" in kw for kws in opened.values() for kw in kws)


def _toy_trainer_lowered(monkeypatch, tracer=None) -> tuple:
    """``(lowered epoch program text, trainer)`` of a small dSGD trainer with
    ``cfg.telemetry`` off, after one epoch through ``run_epoch``; ``tracer``
    replaces the loop's (None: the one the trainer builds)."""
    from test_telemetry import _toy_sites

    from dinunet_implementations_tpu.core.config import TrainConfig
    from dinunet_implementations_tpu.models import MSANNet
    from dinunet_implementations_tpu.trainer.loop import FederatedTrainer

    sites = _toy_sites(2)
    trainer = FederatedTrainer(
        TrainConfig(epochs=1, batch_size=8),
        MSANNet(in_size=6, hidden_sizes=(8,), out_size=2), mesh=None)
    if tracer is not None:
        trainer.tracer = tracer
    state = trainer.init_state(jnp.ones((8, 6)), num_sites=2)
    seen = {}
    real = trainer.epoch_fn

    def spy(*args):
        seen["text"] = real.lower(*args).as_text()
        return real(*args)

    monkeypatch.setattr(trainer, "epoch_fn", spy)
    trainer.run_epoch(state, sites, 1, batch_size=8)
    return seen["text"], trainer


def test_host_spans_leave_the_lowered_epoch_program_as_it_is(monkeypatch):
    """The spans are host work around the call: the epoch program lowered
    from ``run_epoch``'s own arguments is the same text under the annotating
    tracer (``cfg.telemetry`` off) as under one that does nothing."""
    from dinunet_implementations_tpu.telemetry import (
        NULL_TRACER,
        PROFILER_TRACER,
    )

    with_spans, trainer = _toy_trainer_lowered(monkeypatch)
    assert trainer.tracer is PROFILER_TRACER
    without, _ = _toy_trainer_lowered(monkeypatch, tracer=NULL_TRACER)
    assert diff_report(without, with_spans, "no-spans", "spans") is None
    assert PROFILER_TRACER.events() == []


# -- rotary's hand-over kernels (ISSUE 37) ----------------------------------------


def test_every_rope_pallas_call_is_named_from_the_constants():
    from dinunet_implementations_tpu.ops import rope_pallas

    tree = ast.parse(inspect.getsource(rope_pallas))
    names = [{k.arg: k.value for k in n.keywords}["name"].id
             for n in ast.walk(tree)
             if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
             and n.func.attr == "pallas_call"]
    assert sorted(getattr(rope_pallas, n) for n in names) == sorted(
        rope_pallas.KERNEL_NAMES)


@pytest.mark.parametrize("name", ["rope_fwd", "rope_bwd"])
def test_every_rope_kernel_name_is_read_by_a_metric_of_the_benchmark(name):
    """``KERNEL_NAMES``' rule for the two new names: each is read, as a whole
    word, by ``rotary_kernel_ms_per_round.json``, whose ``what`` names the
    constant; the instruction names the compiler gives the calls match, a
    longer word does not."""
    from dinunet_implementations_tpu.ops import rope_pallas

    assert name in rope_pallas.KERNEL_NAMES
    assert _metric_files_reading(rope_pallas, name) == [
        "rotary_kernel_ms_per_round.json"]
    metric = json.loads((REPO / "benchmarks" / "layer_metrics"
                         / "rotary_kernel_ms_per_round.json").read_text())
    call = ' = (bf16[2,4,7,16384,128]{4,3,2,1,0}) custom-call(%x), custom_call_target="tpu_custom_call"'
    for instruction in (f"%{name}.3", f"%vmap_{name}_.2", f"%vmap_jvp_{name}__.6",
                        f"%checkpoint_vmap_{name}_.11"):
        assert re.search(metric["args"]["pattern"], instruction + call)
    for other in (f"%vmap_x{name}_.2", "%splash_mqa_fwd_residuals.1", "%lstm_fwd.2"):
        assert not re.search(metric["args"]["pattern"], other + call)
    assert not re.search(metric["args"]["pattern"], f"%{name}.3 = f32[8] fusion(%x)")


def test_the_old_rotarys_witness_reads_slice_negate_fusions_by_name():
    metric = json.loads((REPO / "benchmarks" / "layer_metrics"
                         / "rotary_slice_negate_ms_per_round.json").read_text())
    assert metric["args"]["field"] == "name"
    hit = re.compile(metric["args"]["pattern"])
    assert hit.search("slice_negate_fusion") and hit.search("slice_negate_fusion.12")
    assert not hit.search("slice_negate_fusion_2") and not hit.search("negate_fusion")
    assert not hit.search("pad_slice_negate_fusion.1")
