"""jaxprlint (checks/semantic.py + checks/lowering.py) — the traced-program
tier.

Four layers:
- negative fixtures that each S-rule must catch: a mis-axed collective and
  an outside-scan collective (S001), an inconsistent / undercounting /
  overcounting wire model (S002), a donated-but-unaliased buffer (S003), an
  f32 upcast on a 16-bit wire path (S004), and a divergent off-program
  (S005);
- baseline round-trip per rule (semantic findings are baseline-suppressed;
  there is no source line for inline markers);
- the wire_bytes cross-check over all four engine corners (dSGD / rankDAD /
  powerSGD / the low-rank engines' non-compressible fallback);
- the acceptance gate: the FULL engine × topology × pipeline matrix traces
  clean against the checked-in (empty) semantic baseline.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinunet_implementations_tpu.checks import semantic as sem
from dinunet_implementations_tpu.checks.core import (
    apply_baseline,
    load_baseline,
    save_baseline,
)
from dinunet_implementations_tpu.checks.lowering import (
    diff_report,
    normalize_lowering,
)
from dinunet_implementations_tpu.checks.rules import COLLECTIVE_AXIS_ARG
from jax import shard_map
from dinunet_implementations_tpu.engines import make_engine
from dinunet_implementations_tpu.engines.base import mask_dead_site
from dinunet_implementations_tpu.parallel.collectives import (
    site_weighted_mean,
)
from dinunet_implementations_tpu.telemetry.metrics import (
    modeled_wire_shapes,
    payload_bytes_of,
)


def _rules(findings):
    return sorted(f.rule for f in findings)


# ---------------------------------------------------------------------------
# tier agreement
# ---------------------------------------------------------------------------


def test_ast_and_semantic_collective_tables_agree():
    """Every collective the AST tier (R003) knows maps onto a traced
    primitive the semantic walker audits — the two tiers cannot disagree on
    what counts as a collective."""
    for api_name in COLLECTIVE_AXIS_ARG:
        prim = sem.prim_for(api_name)
        assert prim in sem.COMM_PRIMS | sem.QUERY_PRIMS, (
            f"R003 collective {api_name!r} traces to {prim!r}, which the "
            f"semantic tier does not audit"
        )


# ---------------------------------------------------------------------------
# S001 — collective/mesh audit
# ---------------------------------------------------------------------------


def _rogue_axis_program(in_scan: bool):
    """A shard_map program over a TYPO'D mesh axis name ('sites') — traces
    fine, reduces over something that is not a declared mesh constant."""
    from jax.sharding import Mesh, PartitionSpec as P

    cpus = [d for d in jax.devices() if d.platform == "cpu"]
    mesh = Mesh(np.array(cpus[:2]), ("sites",))

    def inner(x):
        if in_scan:
            def body(c, xs):
                return c + jax.lax.psum(xs, "sites").sum(), ()

            out, _ = jax.lax.scan(body, 0.0, x)
            return out
        return jax.lax.psum(x, "sites").sum()

    f = jax.jit(lambda x: shard_map(
        inner, mesh=mesh, in_specs=P("sites"), out_specs=P(),
        check_vma=False,
    )(x))
    return jax.make_jaxpr(f)(jnp.ones((2, 3)))


def test_s001_rogue_axis_and_outside_scan_flagged():
    audit = sem.audit_jaxpr(_rogue_axis_program(in_scan=False))
    fs = sem.check_collective_axes(audit.collectives, "trace://fixture")
    assert _rules(fs) == ["S001", "S001"]
    msgs = " | ".join(f.message for f in fs)
    assert "'sites'" in msgs and "outside" in msgs.lower()


def test_s001_declared_axis_inside_scan_is_clean():
    audit = sem.audit_jaxpr(_rogue_axis_program(in_scan=True))
    # same program with the axis declared: only the name check applies
    fs = sem.check_collective_axes(
        audit.collectives, "trace://fixture", allowed_axes={"sites"}
    )
    assert fs == []


# ---------------------------------------------------------------------------
# S002 — wire-byte proof
# ---------------------------------------------------------------------------

_MESH_HOST = dict(topology="mesh", pipeline="host")

#: the four engine corners of the acceptance criterion, derived from the
#: semantic tier's own matrix table so this cross-check and the CLI gate
#: can never verify different corners
ENGINE_CORNERS = [
    (name + ("-fallback" if dense else ""), kw, dense)
    for name, kw, dense in sem._ENGINE_CORNERS
]
assert len(ENGINE_CORNERS) == 4 and ENGINE_CORNERS[-1][2]  # incl. fallback


def _trace(engine_name, kw=(), dense=False, precision="32", engine=None,
           **cell_kw):
    cell = sem.TraceCell(
        engine_name.split("-")[0], precision_bits=precision, engine_kw=kw,
        dense_model=dense, **{**_MESH_HOST, **cell_kw},
    )
    return sem.trace_cell(cell, engine=engine)


@pytest.mark.parametrize("name,kw,dense", ENGINE_CORNERS,
                         ids=[c[0] for c in ENGINE_CORNERS])
def test_s002_wire_bytes_verified_for_every_engine(name, kw, dense):
    """The acceptance cross-check: for all four engine corners, the traced
    per-round per-site collective payload equals the engine's wire_bytes
    model exactly, and the structured wire_shapes hook sums to the same."""
    prog = _trace(name, kw, dense)
    shapes = modeled_wire_shapes(prog.engine, prog.state.params)
    total = sum(int(np.prod(s)) * d.itemsize for s, d in shapes)
    assert total == int(payload_bytes_of(prog.engine, prog.state.params))
    fs = sem.check_wire_bytes(
        prog.audit.collectives, prog.engine, prog.state.params, prog.block,
        prog.path,
    )
    assert fs == [], "\n".join(f.format() for f in fs)


def test_s002_pack_unaware_model_flagged_on_packed_cell():
    """The r12 wire-accounting proof: on a packed cell (4 virtual sites per
    device) a wire model that keeps PER-SITE accounting — ignoring that the
    factor gather ships every virtual site's block while psums reduce
    locally first — must be flagged; the real pack-aware engine is clean on
    the same traced program."""
    kw = (("dad_num_pow_iters", 2), ("dad_reduction_rank", 2))
    prog = _trace("rankDAD", kw, topology="fold4")
    assert prog.block == 4
    # the real engine's model matches the traced packed program exactly
    assert sem.check_wire_bytes(
        prog.audit.collectives, prog.engine, prog.state.params, prog.block,
        prog.path,
    ) == []
    # a per-site (pack-unaware) model on the same program: the traced
    # [4, Σ(m+n), r] gather block is unmodeled, its own [1, ...] entry never
    # ships — both coverage directions trip
    base = prog.engine
    naive = dataclasses.replace(
        base,
        wire_shapes=lambda g: base.wire_shapes(g, pack=1),
        wire_bytes=lambda g: base.wire_bytes(g, pack=1),
    )
    fs = sem.check_wire_bytes(
        prog.audit.collectives, naive, prog.state.params, prog.block,
        prog.path,
    )
    snippets = {f.snippet for f in fs}
    assert any(s.startswith("missing") for s in snippets), snippets
    assert any(s.startswith("unmodeled") for s in snippets), snippets


def test_s002_robust_model_on_plain_psum_program_flagged():
    """The r17 robust-wire negative fixture (mirror of the pack-unaware one
    above): an engine that DECLARES the robust gather-mode wire model while
    its traced program still ships the plain weighted psum must trip S002 in
    both directions — the modeled [pack, ...] per-site gather blocks never
    ship (overcounting), and the psum'd dense operands are covered by
    nothing (undercounting). The real trimmed-mean engine is clean on its
    own traced program (the acceptance matrix covers that cell)."""
    prog = _trace("dSGD")  # the legacy psum program
    robust = make_engine("dSGD", robust_agg="trimmed_mean")
    lying = dataclasses.replace(
        prog.engine,
        wire_shapes=robust.wire_shapes,
        wire_bytes=robust.wire_bytes,
    )
    fs = sem.check_wire_bytes(
        prog.audit.collectives, lying, prog.state.params, prog.block,
        prog.path,
    )
    snippets = {f.snippet for f in fs}
    assert any(s.startswith("missing") for s in snippets), snippets
    assert any(s.startswith("unmodeled") for s in snippets), snippets


def test_s002_robust_cells_wire_models_consistent():
    """wire_shapes must sum to wire_bytes for every engine × robust mode at
    pack factors 1 and 4 — the structural half of the robust-mode S002 proof
    (the traced half runs in the acceptance matrix)."""
    params = {
        "dense": jnp.zeros((8, 4), jnp.float32),
        "bias": jnp.zeros((4,), jnp.float32),
    }
    for name in ("dSGD", "rankDAD", "powerSGD"):
        for mode in ("norm_clip", "trimmed_mean", "coordinate_median"):
            eng = make_engine(name, robust_agg=mode, dad_reduction_rank=2)
            for pack in (1, 4):
                shapes = modeled_wire_shapes(eng, params, pack=pack)
                total = sum(
                    int(np.prod(s)) * d.itemsize for s, d in shapes
                )
                assert total == int(
                    payload_bytes_of(eng, params, pack=pack)
                ), (name, mode, pack)


def test_s002_inconsistent_model_flagged():
    bad = dataclasses.replace(
        make_engine("dSGD"), wire_bytes=lambda g: 1, wire_shapes=None
    )
    prog = _trace("dSGD", engine=bad)
    fs = sem.check_wire_bytes(
        prog.audit.collectives, bad, prog.state.params, prog.block, prog.path
    )
    assert "S002" in _rules(fs)
    assert any(f.snippet == "model-inconsistent" for f in fs)


def test_s002_unmodeled_collective_flagged():
    """An aggregate that ships something the wire model doesn't count —
    the undercounting direction."""
    from dinunet_implementations_tpu.parallel.collectives import site_sum

    base = make_engine("dSGD")

    def agg(grads, state, weight, axis_name, live=None):
        out, st = base.aggregate(grads, state, weight, axis_name, live=live)
        # a stray unmodeled payload; site_sum resolves the packed/classic
        # axis form like a real engine would (leading [K] axis when packed)
        site_sum(jnp.zeros((1, 7, 7), jnp.float32), axis_name)
        return out, st

    bad = dataclasses.replace(base, aggregate=agg)
    prog = _trace("dSGD", engine=bad)
    fs = sem.check_wire_bytes(
        prog.audit.collectives, bad, prog.state.params, prog.block, prog.path
    )
    assert any(
        f.rule == "S002" and f.snippet == "unmodeled psum (7, 7)" for f in fs
    ), "\n".join(f.format() for f in fs)


def test_s002_overcounting_model_flagged():
    """A wire model claiming payload that never ships."""
    base = make_engine("dSGD")
    phantom = ((9, 9), np.dtype(np.float32))
    bad = dataclasses.replace(
        base,
        wire_shapes=lambda g: base.wire_shapes(g) + [phantom],
        wire_bytes=lambda g: base.wire_bytes(g) + 9 * 9 * 4,
    )
    prog = _trace("dSGD", engine=bad)
    fs = sem.check_wire_bytes(
        prog.audit.collectives, bad, prog.state.params, prog.block, prog.path
    )
    assert any(
        f.rule == "S002" and f.snippet == "missing (9, 9)" for f in fs
    ), "\n".join(f.format() for f in fs)


# ---------------------------------------------------------------------------
# S003 — donation proof
# ---------------------------------------------------------------------------


def test_s003_aliased_donation_is_clean():
    f = jax.jit(
        lambda s, x: ({"a": s["a"] + 1.0, "b": s["b"] * 2.0}, x.sum()),
        donate_argnums=(0,),
    )
    s = {"a": jnp.ones((4, 4)), "b": jnp.ones((8,))}
    x = jnp.ones((3,))
    comp = f.lower(s, x).compile()
    assert sem.check_donation(comp, (s, x), (0,), "trace://donate") == []


def test_s003_unaliased_donation_flagged():
    """A donated buffer with no same-shape output cannot alias — the silent
    double-residency bug S003 exists to catch."""
    f = jax.jit(lambda s, x: s["a"].sum() + x.sum(), donate_argnums=(0,))
    s = {"a": jnp.ones((16,)), "b": jnp.ones((4, 4))}
    x = jnp.ones((3,))
    comp = f.lower(s, x).compile()
    fs = sem.check_donation(comp, (s, x), (0,), "trace://donate")
    assert _rules(fs) == ["S003", "S003"]  # neither 'a' nor 'b' can alias
    assert any("['b']" in f.snippet for f in fs)
    # the non-donated arg is never flagged
    assert not any("arg1" in f.snippet for f in fs)


def test_s003_real_donated_epoch_aliases_every_state_leaf():
    """The trainer's real default (device pipeline + donated state): every
    TrainState leaf must appear in the compiled executable's aliasing."""
    prog = _trace("dSGD", topology="vmap", pipeline="device", donate=True)
    fs = sem.check_donation(prog.compiled, prog.args, (0,), prog.path)
    assert fs == [], "\n".join(f.format() for f in fs)


# ---------------------------------------------------------------------------
# S004 — precision flow
# ---------------------------------------------------------------------------


def test_s004_f32_wire_upcast_flagged():
    """A 16-bit-wire engine that skips the payload cast: every payload
    collective rides f32 — the compression silently not happening."""
    e16 = make_engine("dSGD", precision_bits="16")

    def agg(grads, state, weight, axis_name, live=None):
        grads, weight = mask_dead_site(grads, weight, live)
        return site_weighted_mean(grads, weight, axis_name), state

    cheat = dataclasses.replace(e16, aggregate=agg)
    prog = _trace("dSGD", precision="16", engine=cheat)
    fs = sem.check_precision_flow(
        prog.audit.collectives, cheat, prog.state.params, prog.block,
        prog.path,
    )
    assert fs and set(_rules(fs)) == {"S004"}
    assert all(f.snippet.startswith("upcast") for f in fs)
    # ...and the byte proof independently disagrees with the model
    fs2 = sem.check_wire_bytes(
        prog.audit.collectives, cheat, prog.state.params, prog.block,
        prog.path,
    )
    assert any(f.snippet == "bytes-mismatch" for f in fs2)


def test_s004_missing_lowp_dot_flagged():
    prog = _trace(
        "rankDAD", (("dad_num_pow_iters", 2), ("dad_reduction_rank", 2)),
        precision="16",
    )
    # the real engine IS clean...
    assert sem.check_precision_flow(
        prog.audit.collectives, prog.engine, prog.state.params, prog.block,
        prog.path, require_lowp_dot=True, dots=prog.audit.dots,
    ) == []
    # ...and the same program with its low-precision dots "lost" is caught
    fs = sem.check_precision_flow(
        prog.audit.collectives, prog.engine, prog.state.params, prog.block,
        prog.path, require_lowp_dot=True,
        dots=[(4, 4, 1)],
    )
    assert [f.snippet for f in fs] == ["no-lowp-dot"]


def _psum_wire_itemsize(fn, *xs):
    """Wire itemsize of the first traced psum operand in ``fn``."""
    audit = sem.audit_jaxpr(jax.make_jaxpr(fn)(*xs))
    site = next(s for s in audit.collectives if s.prim == "psum")
    return site.wire_itemsizes[0]


def _one_site_shard(f):
    from jax.sharding import Mesh, PartitionSpec as P

    cpus = [d for d in jax.devices() if d.platform == "cpu"]
    mesh = Mesh(np.array(cpus[:1]), ("sites",))
    # check_vma=False: with the check on, jax rewrites psum into pvary +
    # psum_invariant at trace time and the audit (which reads the psum the
    # engines trace under the trainer's check_vma=False) finds no collective
    return shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                     check_vma=False)


def test_s004_walk_not_fooled_by_bf16_touched_mask():
    """An f32 payload multiplied by a same-shape mask that passed through
    bf16 is NOT a 16-bit wire: only the payload's own dataflow may narrow
    the reading. A regression here silently re-greens the S002/S004 proofs
    on an engine that dropped its payload cast but still multiplies by a
    narrow-float mask."""

    def tainted(g):
        mask = jnp.ones_like(g).astype(jnp.bfloat16).astype(jnp.float32)
        return jax.lax.psum(g * mask, "sites")

    assert _psum_wire_itemsize(_one_site_shard(tainted), jnp.ones((8,))) == 4


def test_s004_walk_sees_through_wire_compress_round_trip():
    """The inverse direction: wire_compress's bf16→f32 round trip scaled by
    an f32 scalar still reads as a 2-byte wire — the shared scale does not
    de-quantize the payload."""

    def bf16_wire(g, w):
        p = g.astype(jnp.bfloat16).astype(jnp.float32)
        return jax.lax.psum(p * w, "sites")

    assert _psum_wire_itemsize(
        _one_site_shard(bf16_wire), jnp.ones((8,)), jnp.float32(0.5)
    ) == 2


def test_s004_int8_declared_but_f32_shipped_flagged():
    """The r14 negative fixture: an engine whose wire model DECLARES an int8
    wire but whose aggregate ships raw (unquantized) f32 payloads — S004
    must flag the upcast on every payload and S002's byte totals must
    disagree (the 4x shrink is claimed, not happening)."""
    e8 = make_engine("dSGD", wire_quant="int8")

    def agg(grads, state, weight, axis_name, live=None):
        grads, weight = mask_dead_site(grads, weight, live)
        return site_weighted_mean(grads, weight, axis_name), state

    cheat = dataclasses.replace(e8, aggregate=agg)
    prog = _trace("dSGD", engine=cheat)
    fs = sem.check_precision_flow(
        prog.audit.collectives, cheat, prog.state.params, prog.block,
        prog.path,
    )
    assert fs and set(_rules(fs)) == {"S004"}
    assert all(f.snippet.startswith("upcast") for f in fs)
    assert any("int8" in f.message for f in fs)
    fs2 = sem.check_wire_bytes(
        prog.audit.collectives, cheat, prog.state.params, prog.block,
        prog.path,
    )
    assert any(f.snippet == "bytes-mismatch" for f in fs2)


def test_s004_walk_resolves_int8_quant_chain():
    """The quant→collective→dequant chain (round/clamp → int8 cast →
    dequant mul) reads as a 1-byte wire — the r14 codec's round-trip is
    proven, not re-greened via a dropped cast."""
    from dinunet_implementations_tpu.parallel.collectives import (
        resolve_wire_codec,
    )

    codec = resolve_wire_codec("32", "int8")
    sr = resolve_wire_codec("32", "int8", stochastic=True)

    def int8_wire(g):
        return jax.lax.psum(codec.compress(g), "sites")

    def int8_sr_wire(g):
        return jax.lax.psum(sr.compress(g), "sites")

    x = jnp.linspace(-1.0, 1.0, 8)
    assert _psum_wire_itemsize(_one_site_shard(int8_wire), x) == 1
    assert _psum_wire_itemsize(_one_site_shard(int8_sr_wire), x) == 1


def test_s004_walk_packed_row_scale_does_not_widen():
    """The packed per-row [K, 1, 1] quant scale reaches the dequant mul at
    its own rank-kept shape (no broadcast_in_dim in the jaxpr) — it must
    still read as a scale, not as f32 payload data (the r14
    rankDAD@int8/fold4 cell's regression: the gathered factor block ships
    every virtual site's row, each with its own scale)."""
    from dinunet_implementations_tpu.parallel.collectives import (
        resolve_wire_codec,
    )

    codec = resolve_wire_codec("32", "int8")

    def packed_gather(g):  # g [K, m, n], per-row scales, gathered whole
        return jax.lax.all_gather(
            codec.compress(g, batched=True), "sites", axis=0
        )

    x = jnp.arange(24.0).reshape(4, 3, 2) + 1.0
    audit = sem.audit_jaxpr(
        jax.make_jaxpr(_one_site_shard(packed_gather))(x)
    )
    site = next(s for s in audit.collectives if s.prim == "all_gather")
    assert site.wire_itemsizes[0] == 1


def test_s002_match_prefers_exact_dtype_for_same_shape_payloads():
    """Two same-shape payloads at different dtypes (a bf16 factor next to an
    f32 dense leaf) must pair with their own model entries — first-fit by
    shape alone could cross-pair them, minting a spurious S004 upcast or
    masking a real one."""
    shape = (8, 2)
    aval = jax.ShapeDtypeStruct(shape, jnp.float32)
    sites = [
        sem.CollectiveSite("psum", ("site",), (aval,), 1, (4,)),
        sem.CollectiveSite("psum", ("site",), (aval,), 1, (2,)),
    ]
    expected = [
        (shape, np.dtype(np.float32)),
        (shape, np.dtype(jnp.bfloat16)),
    ]
    matches, missing, leftovers = sem._match_payload(sites, expected)
    assert missing == [] and leftovers == []
    assert {(d.itemsize, traced) for _, d, traced, _ in matches} == {
        (4, 4), (2, 2),
    }


# ---------------------------------------------------------------------------
# S005 — program identity
# ---------------------------------------------------------------------------


def _texts():
    t1 = jax.jit(lambda x: x + 1.0).lower(jnp.ones((3,))).as_text()
    t2 = jax.jit(lambda x: x * 2.0).lower(jnp.ones((3,))).as_text()
    return t1, t2


def test_s005_divergent_off_program_flagged():
    t1, t2 = _texts()
    fs = sem.check_lowering_identity([("fixture-off", t1, t2, True)])
    assert _rules(fs) == ["S005"]
    assert "diverges" in fs[0].message


def test_s005_vanished_divergence_flagged():
    t1, _ = _texts()
    fs = sem.check_lowering_identity([("fixture-opt-out", t1, t1, False)])
    assert _rules(fs) == ["S005"]
    assert "identical" in fs[0].message


def test_s005_identical_pair_clean():
    t1, _ = _texts()
    assert sem.check_lowering_identity([("ok", t1, t1, True)]) == []


def test_differ_normalization_and_first_divergence_report():
    t1, t2 = _texts()
    assert diff_report(t1, t1) is None
    # normalization strips locations/metadata and canonicalizes ids
    lines = normalize_lowering(t1)
    assert not any("loc(" in ln for ln in lines)
    report = diff_report(t1, t2, "add-one", "times-two")
    assert report is not None
    assert "first at line" in report and "add-one" in report


def test_differ_single_insertion_counts_once():
    """One op inserted mid-program is ONE divergence reported at its true
    location — not a positional cascade where every shifted line after the
    insertion reads as differing and the context block shows
    identical-content lines."""
    lines = [f"op{i} = work arg{i}" for i in range(40)]
    a = "\n".join(lines)
    b = "\n".join(lines[:20] + ["opX = extra"] + lines[20:])
    report = diff_report(a, b, "base", "plus-one")
    assert "1 differing line(s)" in report
    assert "first at line 21 (insert)" in report
    assert "opX = extra" in report


# ---------------------------------------------------------------------------
# suppression (baseline) round-trip per rule
# ---------------------------------------------------------------------------


def _finding_fixtures():
    """One representative finding list per S-rule, from the fixtures
    above."""
    audit = sem.audit_jaxpr(_rogue_axis_program(in_scan=False))
    s001 = sem.check_collective_axes(audit.collectives, "trace://fixture")
    bad = dataclasses.replace(
        make_engine("dSGD"), wire_bytes=lambda g: 1, wire_shapes=None
    )
    prog = _trace("dSGD", engine=bad)
    s002 = sem.check_wire_bytes(
        prog.audit.collectives, bad, prog.state.params, prog.block, prog.path
    )
    f = jax.jit(lambda s: s["a"].sum(), donate_argnums=(0,))
    s = {"a": jnp.ones((16,))}
    s003 = sem.check_donation(f.lower(s).compile(), (s,), (0,), "trace://d")
    s004 = sem.check_precision_flow(
        prog.audit.collectives, prog.engine, prog.state.params, prog.block,
        prog.path, require_lowp_dot=True, dots=[],
    )
    t1, t2 = _texts()
    s005 = sem.check_lowering_identity([("fx", t1, t2, True)])
    return {"S001": s001, "S002": s002, "S003": s003, "S004": s004,
            "S005": s005}


def test_semantic_baseline_roundtrip_per_rule(tmp_path):
    """Trigger + baseline-suppression + round-trip for every S-rule: a
    grandfathered finding stops gating, an un-grandfathered one still
    does."""
    fixtures = _finding_fixtures()
    for rule, findings in fixtures.items():
        assert findings, f"{rule} fixture produced no findings"
        assert {f.rule for f in findings} == {rule}
        bl_path = save_baseline(findings, str(tmp_path / f"{rule}.json"))
        baseline = load_baseline(bl_path)
        new, matched = apply_baseline(findings, baseline)
        assert new == [] and matched == len(findings), rule
        fresh = dataclasses.replace(
            findings[0], snippet=findings[0].snippet + " (new)"
        )
        new2, _ = apply_baseline(findings + [fresh], baseline)
        assert new2 == [fresh], rule


# ---------------------------------------------------------------------------
# CLI plumbing
# ---------------------------------------------------------------------------


def test_cli_semantic_flag_gates_and_emits_json(tmp_path, capsys, monkeypatch):
    from dinunet_implementations_tpu.checks.__main__ import main

    fake = _finding_fixtures()["S005"]
    monkeypatch.setattr(sem, "run_semantic_checks", lambda: list(fake))
    assert main(["--semantic", "--no-baseline", "--format", "json"]) == 1
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [r["rule"] for r in rows] == ["S005"]
    # grandfathering through a baseline file turns the gate green
    bl = save_baseline(fake, str(tmp_path / "bl.json"))
    assert main(["--semantic", "--baseline-file", bl]) == 0


def test_cli_sarif_format(tmp_path, capsys):
    from dinunet_implementations_tpu.checks.__main__ import main

    bad = tmp_path / "trainer" / "hot.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("def f():\n    print('x')\n")
    rc = main([str(tmp_path), "--no-baseline", "--format", "sarif"])
    assert rc == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["version"] == "2.1.0"
    (run,) = doc["runs"]
    assert run["tool"]["driver"]["name"] == "jaxlint"
    (res,) = run["results"]
    assert res["ruleId"] == "R001"
    assert res["locations"][0]["physicalLocation"]["region"]["startLine"] == 2


# ---------------------------------------------------------------------------
# the acceptance gate
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_full_matrix_scans_clean_with_empty_baseline():
    """The WHOLE engine × topology × pipeline matrix (plus the precision and
    donation corners and the S005 identity gate) traces clean, and the
    checked-in semantic baseline is genuinely empty.

    Slow tier: traces/compiles the full matrix (~30s); the same zero-findings
    gate is enforced on every push by the dedicated ``semantic`` CI job
    (``checks --semantic`` against the empty baseline), so the fast tier
    keeps only the per-rule unit cells above.
    """
    assert load_baseline(sem.SEMANTIC_BASELINE) == []
    findings = sem.run_semantic_checks()
    assert findings == [], "\n".join(f.format() for f in findings)
