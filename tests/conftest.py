# Test-session configuration. Tests run on the default single CPU device;
# multi-device sharding tests spawn subprocesses with their own XLA_FLAGS
# (see test_sharding_dryrun.py).
#
# jit compile-count guard (repro.analysis.compile_guard). Registering the
# module as a plugin runs its pytest_configure (marker registration + jit
# tracking install) before test modules import repro.*, so every wrapper
# the suite creates is counted. The autouse fixture enforces
# @pytest.mark.compile_budget(n) budgets.
import pytest

pytest.register_assert_rewrite("repro.analysis.compile_guard")
pytest_plugins = ("repro.analysis.compile_guard",)

from repro.analysis.compile_guard import make_autouse_fixture  # noqa: E402

_compile_budget_guard = make_autouse_fixture(pytest)
