"""Tier-1 guard: every scheduler terminal path emits a flight-recorder
event.

Runs scripts/check_lifecycle_events.py in-process: a function in
llmlb_tpu/engine/scheduler.py that puts a terminal ("done"/"error")
event-queue tuple without a matching ``_fr_emit``/``flightrec.emit`` call
fails the build — a missing emit is a silent gap in every merged timeline
(docs/tracing.md).
"""

import sys
import textwrap
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "scripts"))

import check_lifecycle_events  # noqa: E402


def test_scheduler_terminal_paths_instrumented():
    findings = check_lifecycle_events.check_scheduler()
    assert not findings, "\n".join(f"line {ln}: {what}"
                                   for ln, what in findings)


def test_checker_is_not_vacuous():
    """The real scheduler must contain terminal puts the checker pairs —
    a refactor that renames events.put would silently disarm the guard."""
    import ast

    source = check_lifecycle_events.SCHEDULER.read_text()
    tree = ast.parse(source)
    puts = sum(
        1 for n in ast.walk(tree)
        if isinstance(n, ast.Call) and check_lifecycle_events._is_terminal_put(n)
    )
    assert puts >= 10, f"only {puts} terminal puts found — pattern drifted?"


def test_checker_flags_missing_emit(tmp_path):
    bad = tmp_path / "sched.py"
    bad.write_text(textwrap.dedent("""
        class S:
            def _finish(self, request):
                request.events.put(("done", "stop"))

            def _park_slot(self, i):
                pass
    """))
    findings = check_lifecycle_events.check_scheduler(bad)
    assert len(findings) == 2, findings
    assert "terminal events.put" in findings[0][1]
    assert "parked" in findings[1][1]


def test_checker_accepts_instrumented(tmp_path):
    ok = tmp_path / "sched.py"
    ok.write_text(textwrap.dedent("""
        class S:
            def _finish(self, request):
                request.events.put(("done", "stop"))
                self._fr_emit(request, "finished", reason="stop")

            def _fail(self, request):
                request.events.put(("error", "boom"))
                self.flightrec.emit(request.request_id, "errored")

            def _park_slot(self, i):
                self._fr_emit(self.slots[i].request, "parked",
                              reason="preempt")

            def _tokens_only(self, request, tok):
                request.events.put(("tokens", [tok]))  # not terminal: no emit
    """))
    assert check_lifecycle_events.check_scheduler(ok) == []
