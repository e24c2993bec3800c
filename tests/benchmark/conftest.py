"""One test of this directory cannot be true of a second configuration, and
a PR that adds a configuration may edit no file the benchmark already has.

`test_manifest.py::test_a_configuration_file_cuts_depth_only` is parametrised
over every configuration of `BENCHMARK.json` and asserts Mistral-7B's
published widths (4096, 14336, 32, 8, 32000) and `num_slots == 32` of each:
written when the benchmark had one configuration (PR 23), it holds any other
configuration to Mistral's numbers. Its intent — the published keys
unchanged, `num_hidden_layers` the only cut, the extend path in `correct` —
is held for `kanana-2-30b-a3b-l8` by `test_latent_moe.py`
(`test_the_configuration_holds_the_published_keys_and_one_cut`, against the
published keys it carries). Until a `benchmark` PR gives that test each
configuration's own widths and deletes this file (PERF.md section 7, from
PR 31), that one case is skipped here, by name, and nothing else is: the
case of a configuration added later runs, and fails until it is dealt with.
"""

import pytest

PINNED_TO_MISTRAL = (
    "test_a_configuration_file_cuts_depth_only[kanana-2-30b-a3b-l8]")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.name == PINNED_TO_MISTRAL:
            item.add_marker(pytest.mark.skip(
                reason="asserts Mistral-7B's widths of every configuration; "
                       "for the next benchmark PR (tests/benchmark/conftest.py)"))
