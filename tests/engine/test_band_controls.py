"""The one-term controls of models/afmoe.py, which
tests/engine/test_band_family.py states in its record: in a file of their
own, so that under `--dist loadfile` no file of the family suite
(tests/engine/family_suite.py) is a run's long pole."""

from tests.engine.family_suite import (  # noqa: F401 — the case held here
    test_a_program_with_one_term_wrong_fails_the_comparison,
)
from tests.engine.test_band_family import CASE  # noqa: F401
