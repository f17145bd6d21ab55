"""Test config: run on an 8-device virtual CPU mesh (SURVEY.md §4.8 — the
always-on 'fake TPU'); the chip is reached by chip_smoke.py, not by tests."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests run on the virtual mesh, chip or not
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")  # in case jax was imported pre-conftest

# Persistent XLA compile cache: repeated test runs skip recompiles.  Placed
# by the one rule (flags.apply_compile_cache): where the environment names a
# directory, there; otherwise the fixed one in the checkout.
from paddle_tpu.flags import CHECKOUT_CACHE_DIR, apply_compile_cache  # noqa: E402

apply_compile_cache(CHECKOUT_CACHE_DIR, min_compile_secs=0.5)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: minutes of one compile; left out of tier-1 (`-m 'not slow'`), run by name")


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Each test builds into fresh default programs and a fresh scope."""
    import paddle_tpu as fluid
    from paddle_tpu.core import program as prog_mod
    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.core import unique_name

    old_main, old_startup = prog_mod._main_program, prog_mod._startup_program
    old_scope = scope_mod._global_scope
    prog_mod._main_program = fluid.Program()
    prog_mod._startup_program = fluid.Program()
    scope_mod._global_scope = scope_mod.Scope()
    with unique_name.guard():
        yield
    prog_mod._main_program, prog_mod._startup_program = old_main, old_startup
    scope_mod._global_scope = old_scope
