"""Every family's seeded weights, held bit for bit.

`tests/data/init_params_digests.json` holds, for the debug preset of every
registered family (`test_family_contract.PRESETS`) at `PRNGKey(0)`, the
sha256 of each leaf of `init_params` (dtype, shape and bytes). It was
computed at PR 56's tree, before the families' parameter scaffolding was
made one (`models/family.py`): a draw, the order of the keys or a dtype that
moves turns a case red. A family that is ADDED computes its row once:

    JAX_PLATFORMS=cpu python -m tests.engine.test_init_digests <preset> ...

writes the rows of the presets named (all of them, named none) and leaves
the others as they are.
"""

import hashlib
import json
import pathlib
import sys

import jax
import numpy as np
import pytest

from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.models import family_for
from tests.engine.test_family_contract import PRESETS

DIGESTS = pathlib.Path(__file__).parents[1] / "data" / "init_params_digests.json"


def leaf_digests(preset: str) -> dict[str, str]:
    cfg = get_preset(preset)
    params = family_for(cfg).init_params(cfg, jax.random.PRNGKey(0))
    leaves, _ = jax.tree_util.tree_flatten_with_path(params)
    out = {}
    for path, leaf in leaves:
        leaf = np.asarray(leaf)
        head = f"{leaf.dtype}{leaf.shape}".encode()
        out[jax.tree_util.keystr(path)] = hashlib.sha256(
            head + leaf.tobytes()).hexdigest()
    return out


@pytest.mark.parametrize("preset", sorted(PRESETS.values()))
def test_the_seeded_weights_are_bit_for_bit_the_recorded_ones(preset):
    want = json.loads(DIGESTS.read_text())[preset]
    got = leaf_digests(preset)
    assert sorted(got) == sorted(want)  # the same leaves
    moved = [name for name in want if got[name] != want[name]]
    assert not moved, f"{preset}: these leaves' bytes, dtype or shape moved"


if __name__ == "__main__":
    rows = json.loads(DIGESTS.read_text()) if DIGESTS.exists() else {}
    for name in sys.argv[1:] or sorted(PRESETS.values()):
        rows[name] = leaf_digests(name)
    DIGESTS.write_text(json.dumps(rows, indent=1, sort_keys=True) + "\n")
