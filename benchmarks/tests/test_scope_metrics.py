"""The per-layer metrics that read names the PROGRAM gives its own parts
(PR 24): each pattern against event texts copied from chip traces, and each
name in a metric file against the program's constants, so that a rename in
the program fails here and not silently as 0.0 on the chip.

What a v5e trace shows (jax 0.9.0; PERF.md section 3): an ``XLA Ops`` event's
name is the whole HLO instruction and its own stats are three timing numbers;
``hlo_category``, ``tf_op`` (the HLO ``op_name``, which carries the
``jax.named_scope``s of telemetry/scopes.py) and the rest sit on the event's
METADATA, which ``jax.profiler.ProfileData`` does not hand out. So the kernel
names are readable today (the TPU compiler names a Mosaic call after its
``name=``), the scopes are not: their lines are kept below for the
``benchmark`` PR that teaches the extractor to read metadata.
"""

import re

import pytest

from benchmarks.lib import cells
from benchmarks.run import Ctx
from benchmarks.trace.extract import Op, Span, Trace
from benchmarks.trace.readers import device_ops_matching
from dinunet_implementations_tpu.ops import lstm_pallas
from dinunet_implementations_tpu.telemetry import scopes

BENCH = cells.benchmark_json()
KERNEL_METRICS = {"lstm_fwd_kernel_ms_per_round": "LSTM_FWD",
                  "lstm_bwd_kernel_ms_per_round": "LSTM_BWD"}

# Event names of the changed tree's traced run (icalstm-hcp32.rankdad,
# seed 2147480023, my chip run, PR 24), operand lists shortened with "...".
CHIP_LINES = {
    "lstm_fwd": '%lstm_fwd.22 = (bf16[98,512,174]{2,1,0:T(8,128)(2,1)S(1)}, ..., f32[512,174]{1,0:T(8,128)}) custom-call(...), custom_call_target="tpu_custom_call", operand_layout_constraints={...}, frontend_attributes={kernel_metadata={}}',
    "lstm_bwd": '%lstm_bwd.23 = (bf16[98,512,174]{2,1,0:T(8,128)(2,1)}, ..., f32[512,174]{1,0:T(8,128)}) custom-call(...), custom_call_target="tpu_custom_call", operand_layout_constraints={...}, frontend_attributes={kernel_metadata={}}',
}
# The step-0 probe (benchmarks/tools/scope_probe.py, my chip run, PR 24): a
# kernel under vmap and jvp, a kernel of another family, and operations that
# only CONSUME a kernel's result or are not Mosaic calls at all.
OTHER_LINES = [
    '%vmap_jvp_bilstm_fwd__.3 = f32[4,512,256]{2,1,0:T(8,128)S(1)} custom-call(f32[4,512,256]{2,1,0:T(8,128)S(1)} %fusion.48), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[4,512,256]{2,1,0}}, frontend_attributes={kernel_metadata={}}',
    '%multiply_add_fusion.2 = f32[4,512,256]{2,1,0:T(8,128)S(1)} fusion(f32[4,512,256]{2,1,0:T(8,128)S(1)} %vmap_jvp_lstm_fwd__.6, f32[4,512,256]{2,1,0:T(8,128)S(1)} %vmap_jvp_bilstm_fwd__.3), kind=kLoop, calls=%fused_computation.4.clone.clone',
    '%custom-call.2 = f32[6]{0:T(128)S(1)} custom-call(), custom_call_target="AllocateBuffer"',
    '%fusion.47 = bf16[512,4,256]{2,1,0:T(4,128)(2,1)S(1)} fusion(bf16[4,1024,256]{2,0,1:T(4,128)(2,1)S(1)} %get-tuple-element.129, s32[1024]{0:T(1024)S(1)} %pad_clamp_fusion.2), kind=kCustom, calls=%fused_computation.clone.clone',
]
PROBE_KERNEL_UNDER_TRANSFORMS = '%vmap_jvp_lstm_fwd__.6 = f32[4,512,256]{2,1,0:T(8,128)S(1)} custom-call(f32[4,512,256]{2,1,0:T(8,128)S(1)} %fusion.48), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[4,512,256]{2,1,0}}, frontend_attributes={kernel_metadata={}}'

# ``tf_op`` of event metadata in the traced runs of the final tree (seeds
# 2147487011 / 2147487023, my chip runs, PR 24; read by hand from the raw
# profile, PERF.md section 5): what a scope pattern will have to match once
# the extractor reads it. A transform wraps the scope it maps. OPTIMIZER names
# only the zero-live hold's select fusions: XLA fuses the rest of the update
# into the engine's last einsum, and a fusion wears the name of its root.
CHIP_TF_OPS = {
    "GATHER": "jit(epoch_fn_impl)/while/body/closed_call/vmap(data/gather)/jit(_take)/gather:",
    "MODEL": "jit(epoch_fn_impl)/while/body/closed_call/vmap()/while/body/closed_call/model/fwd_bwd/transpose(model/fwd_bwd)/jvp(ICALstm)/lstm/rev/lstm_bwd/pallas_call:",
    "ENGINE": "jit(epoch_fn_impl)/while/body/closed_call/vmap(engine/aggregate)/jit(_where)/select_n:",
    "POWERITER": "jit(epoch_fn_impl)/while/body/closed_call/vmap(engine/aggregate)/poweriter/while/body/dot_general:",
    "OPTIMIZER": "jit(epoch_fn_impl)/while/body/closed_call/optimizer/update/jit(_where)/select_n:",
}


def spec_of(metric):
    return cells.layer_metric(metric)


@pytest.mark.parametrize("metric", sorted(KERNEL_METRICS))
def test_kernel_metric_is_declared_like_the_accepted_one(metric):
    spec, old = spec_of(metric), spec_of("lstm_kernel_ms_per_round")
    for key in ("layer", "unit", "better", "source", "moves", "reader"):
        assert spec[key] == old[key], key
    assert spec["args"]["field"] == "text" and spec["args"]["how"] == "sum"
    assert spec["args"]["per"] == old["args"]["per"]
    assert [m["name"] for m in BENCH["per_layer"]].count(metric) == 1


@pytest.mark.parametrize("metric", sorted(KERNEL_METRICS))
def test_pattern_matches_its_chip_line_and_no_other(metric):
    name = getattr(lstm_pallas, KERNEL_METRICS[metric])
    rx = re.compile(spec_of(metric)["args"]["pattern"])
    assert rx.search(CHIP_LINES[name])
    for other, line in CHIP_LINES.items():
        assert bool(rx.search(line)) == (other == name), other
    for line in OTHER_LINES:
        assert not rx.search(line), line
    assert bool(rx.search(PROBE_KERNEL_UNDER_TRANSFORMS)) == (name == "lstm_fwd")
    # the same kernel family with another of the file's names never matches
    for other in lstm_pallas.KERNEL_NAMES:
        line = CHIP_LINES[name].replace("%" + name, "%" + other)
        assert bool(rx.search(line)) == (other == name), other


@pytest.mark.parametrize("metric", sorted(KERNEL_METRICS))
def test_every_name_in_a_metric_file_is_a_constant_of_the_program(metric):
    spec, const = spec_of(metric), KERNEL_METRICS[metric]
    name = getattr(lstm_pallas, const)
    words = set(re.findall(r"(?<![A-Za-z0-9_])(?:bi)?lstm_(?:pool_)?[a-z]+wd"
                           r"(?![A-Za-z0-9])", spec["args"]["pattern"]))
    assert words == {name}
    assert name in lstm_pallas.KERNEL_NAMES
    assert f"ops/lstm_pallas.py {const}" in spec["what"]


def test_forward_and_backward_add_up_to_the_accepted_kernel_metric():
    """Through the reader itself, on hand-made ops with the chip's texts: two
    forward and two backward calls a round under a rounds scan, a fusion that
    consumes a kernel, a kernel of the other family."""
    def op(text, lo, hi, leaf=True):
        o = Op(text.split(" = ", 1)[0].lstrip("%"), lo, hi, text)
        o.leaf = leaf
        return o

    fwd, bwd = CHIP_LINES["lstm_fwd"], CHIP_LINES["lstm_bwd"]
    ops = [op("%while.1 = () while(...)", 0.0, 10.0, leaf=False),
           op(fwd, 0.0, 1.0), op(fwd.replace(".22", ".23"), 1.0, 2.0),
           op(OTHER_LINES[1], 2.0, 4.0),
           op(bwd, 4.0, 4.5), op(bwd.replace(".23", ".22"), 4.5, 5.5),
           op(OTHER_LINES[3], 6.0, 9.0)]
    trace = Trace(devices={"d0": ops}, spans=[Span("bench/epoch", 0.0, 10.0)])
    ctx = Ctx(trace, trace.window(), {"rounds_traced": 2})
    read = {m: device_ops_matching.read(ctx, **spec_of(m)["args"])
            for m in [*KERNEL_METRICS, "lstm_kernel_ms_per_round"]}
    assert read["lstm_fwd_kernel_ms_per_round"] == pytest.approx(1000.0)
    assert read["lstm_bwd_kernel_ms_per_round"] == pytest.approx(750.0)
    assert read["lstm_kernel_ms_per_round"] == pytest.approx(1750.0)


@pytest.mark.parametrize("const", sorted(CHIP_TF_OPS))
def test_scope_constants_are_what_the_chip_trace_carries(const):
    """The scopes reach the profile (in metadata the extractor cannot read
    yet): each constant is in its chip line as whole path pieces, wrapped by
    the transform that maps it, and in no other scope's line."""
    def has(text, scope):
        return re.search(r"(?<![A-Za-z0-9_])" + re.escape(scope)
                         + r"(?![A-Za-z0-9_])", text) is not None

    scope = getattr(scopes, const)
    assert has(CHIP_TF_OPS[const], scope)
    nested = re.escape(scopes.ENGINE) + r"\)*/" + re.escape(scopes.POWERITER)
    assert bool(re.search(nested, CHIP_TF_OPS[const])) == (const == "POWERITER")
    for other, line in CHIP_TF_OPS.items():
        if other == const or {other, const} == {"ENGINE", "POWERITER"}:
            continue
        assert not has(line, scope), (const, other)
