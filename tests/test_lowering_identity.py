"""Program-identity regression harness — the "off == compiled out" claims.

ONE parametrized harness over checks/lowering.py's normalized differ
replaces the ad-hoc ``lowered.as_text() == ...`` comparisons that used to be
duplicated across tests/test_telemetry.py and tests/test_robustness.py:

- every OFF-form (telemetry off, faults at their default resolution, the
  sanitizer's leak-checking observation mode, wire_quant="none",
  overlap_rounds off) must be lowering-identical to the baseline epoch
  program;
- every static OPT-OUT/OPT-IN (``quarantine_rounds=-1``, ``telemetry=True``,
  a quantized wire codec, overlapped rounds) must genuinely diverge — if
  these become identical, "compiled out" has silently stopped being true.

The same pairs gate the CLI via rule S005
(``python -m dinunet_implementations_tpu.checks --semantic``); this file is
the fast tier-1 mirror with per-pair failure reports. The engine-knob cases
(``{"engine": {...}}``) ride the semantic tier's ``identity_text_fn``/table
definitions, so the two gates can never test different pair sets.
"""

import jax
import pytest

from dinunet_implementations_tpu.checks.lowering import diff_report
from dinunet_implementations_tpu.checks.semantic import (
    IDENTITY_CASES,
    TraceCell,
    identity_text_fn,
)


@pytest.fixture(scope="module")
def corner():
    """The flagship matrix corner (dSGD / folded sites / host pipeline),
    built by the semantic tier's shared corner builder — the same programs
    the S005 CLI gate compares."""
    text = identity_text_fn(TraceCell("dSGD", "vmap", "host"))
    # the default build's text once, not once per test
    return text(), text


def _split(cases):
    identical = {
        label: kw for label, (kw, ident) in cases.items()
        if ident and kw is not None
    }
    divergent = {
        label: kw for label, (kw, ident) in cases.items() if not ident
    }
    return identical, divergent


#: derived from the semantic tier's tables so this harness and the S005 CLI
#: gate can never test different pair sets. kwargs=None is the
#: checking_leaks observation mode (its own test below).
IDENTICAL_CASES, DIVERGENT_CASES = _split(IDENTITY_CASES)


@pytest.mark.parametrize("case", sorted(IDENTICAL_CASES))
def test_off_form_is_lowering_identical(corner, case):
    base, text = corner
    report = diff_report(
        base, text(**IDENTICAL_CASES[case]), "default-build", case
    )
    assert report is None, report


@pytest.mark.parametrize("case", sorted(DIVERGENT_CASES))
def test_opt_out_really_changes_the_program(corner, case):
    """The inverse gate: if the opt-out stops diverging, the machinery is no
    longer being compiled in/out and every 'zero overhead when off' claim is
    untested."""
    base, text = corner
    assert diff_report(
        base, text(**DIVERGENT_CASES[case]), "default-build", case
    ) is not None


def test_sanitizer_leak_mode_does_not_perturb_the_program(corner):
    """DINUNET_SANITIZE=leaks wraps the fit in jax.checking_leaks — an
    observation mode that must not alter what it observes."""
    assert IDENTITY_CASES["sanitize-leaks"] == (None, True)
    base, text = corner
    with jax.checking_leaks():
        leaks_text = text()
    report = diff_report(base, leaks_text, "plain", "under-checking_leaks")
    assert report is None, report
