"""Tier-1 guard: every LLMLB_* env knob is documented.

Runs scripts/check_env_docs.py's cross-check in-process: any
`LLMLB_[A-Z0-9_]+` name referenced in llmlb_tpu/ must appear verbatim
somewhere under docs/ (docs/configuration.md is the canonical table), so
a new knob — like LLMLB_QUANTIZE — cannot ship undocumented.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "scripts"))

import check_env_docs  # noqa: E402


def test_all_env_knobs_are_documented():
    missing = check_env_docs.undocumented()
    assert not missing, f"undocumented LLMLB_* env knobs: {missing}"


def test_enumeration_is_not_vacuous():
    """The source scan must find the well-known knobs (no silent pass if
    the glob or regex breaks)."""
    knobs = check_env_docs.source_knobs()
    for expected in ("LLMLB_QUANTIZE", "LLMLB_FUSED_DECODE",
                     "LLMLB_DECODE_BURST", "LLMLB_PREFIX_CACHE"):
        assert expected in knobs, expected
    # glob-style prose ("LLMLB_SPEC_{DECODE,...}") must not leak partials
    assert "LLMLB_SPEC" not in knobs or "LLMLB_SPEC_DECODE" in knobs


def test_checker_catches_missing_knob(monkeypatch):
    """The checker itself must fail on an undocumented knob."""
    real = check_env_docs.source_knobs

    def with_fake():
        return real() | {"LLMLB_NOT_A_REAL_KNOB"}

    monkeypatch.setattr(check_env_docs, "source_knobs", with_fake)
    assert "LLMLB_NOT_A_REAL_KNOB" in check_env_docs.undocumented()
