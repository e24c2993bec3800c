"""How the family suite (tests/engine/family_suite.py) runs in a family's
file: a function of the suite that the file imports runs for the file's
`CASE`, once an entry of the record's list that the function's argument
names; `params` and `engine` are built once a file."""

import jax
import pytest

from llmlb_tpu.engine.scheduler import EngineCore
from tests.engine import family_suite

# a suite function's argument -> the entries of the record it runs over
_OVER = {
    "run": lambda case: [(run, run[0]) for run in case.runs],
    "control": lambda case: [(name, name) for name in case.controls],
    "refusal": lambda case: [(r, f"{r[1]}{i}")
                             for i, r in enumerate(case.refused)],
    "start": lambda case: [(s, next(iter(s[0])))
                           for s in case.engine.refused_starts],
}


def pytest_generate_tests(metafunc):
    case = getattr(metafunc.module, "CASE", None)
    if case is None or "case" not in metafunc.fixturenames:
        return
    # also a test of the file's own that asks for `params`: one `case` a
    # file, so that what is built once a file is built once
    metafunc.parametrize("case", [case], ids=[case.name], scope="module")
    if metafunc.function.__module__ == family_suite.__name__:
        for argument in set(_OVER) & set(metafunc.fixturenames):
            values, ids = zip(*_OVER[argument](case))
            metafunc.parametrize(argument, values, ids=ids)


@pytest.fixture(scope="module")
def case(request):
    return request.module.CASE


@pytest.fixture(scope="module")
def params(case):
    return case.family.init_params(case.cfg, jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def engine(case, params):
    core = EngineCore(case.cfg, params, **case.engine.args)
    core.start()
    yield core
    core.stop()
