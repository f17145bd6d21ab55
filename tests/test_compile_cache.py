"""FLAGS_compile_cache_dir: XLA's persistent compilation cache pays the
cold-start `executor.compile` cost once per machine, not once per
process.  Verified the only honest way — two fresh subprocesses."""
import json
import os
import subprocess
import sys

import pytest

CHILD = r"""
import json
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import monitor

main_p, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main_p, startup):
    x = fluid.layers.data("x", [256], dtype="float32")
    y = fluid.layers.data("y", [1], dtype="float32")
    h = x
    for _ in range(6):
        h = fluid.layers.fc(h, 256, act="relu")
    loss = fluid.layers.mean(
        fluid.layers.square_error_cost(fluid.layers.fc(h, 1), y))
    fluid.optimizer.Adam(1e-3).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
scope = fluid.Scope()
exe.run(startup, scope=scope)
monitor.enable()
feed = {"x": np.zeros((32, 256), "f4"), "y": np.zeros((32, 1), "f4")}
exe.run(main_p, feed=feed, fetch_list=[loss], scope=scope)
spans = monitor.json_snapshot()["spans"]
[compiled] = [e for e in monitor.get_monitor().events() if e[0] == "executor.compile"]
print(json.dumps({"compile_s": spans["executor.compile"]["total_s"],
                  "program": main_p._uuid[:8], "span": compiled[5],
                  "counters": {k: v for k, v in monitor.get_monitor().counter_values().items()
                               if k.startswith("executor.compile_cache")}}))
"""


def _run_child(cache_dir):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["FLAGS_compile_cache_dir"] = cache_dir
    env.pop("JAX_COMPILATION_CACHE_DIR", None)  # it would outrank the flag
    env["PYTHONPATH"] = (os.path.dirname(os.path.dirname(__file__))
                         + os.pathsep + env.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", CHILD], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, f"child failed:\n{out.stderr[-3000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def two_processes(tmp_path_factory):
    """The same program compiled in two fresh processes over one cache."""
    cache = str(tmp_path_factory.mktemp("xla_cache"))
    first = _run_child(cache)
    assert os.listdir(cache), "first process wrote no cache entries"
    return first, _run_child(cache)


def test_compile_cache_hits_across_processes(two_processes):
    first, second = (r["compile_s"] for r in two_processes)
    # Measured locally: 0.82s cold vs 0.055s cache hit (~15x).  Gate at 3x
    # so shared-CI timer noise can't flake the test while a broken cache
    # (second == first) still fails loudly.
    assert second < first / 3, (
        f"persistent compile cache miss: cold {first:.3f}s vs second "
        f"process {second:.3f}s (expected an order-of-magnitude drop)")


def test_the_step_keeps_its_name_and_says_that_the_cache_served_it(two_processes):
    """The module's name is part of the persistent cache's key: it is made
    of the program's structure, the same in every process, and the
    `executor.compile` span and the two counters tell a load from a compile."""
    first, second = two_processes
    assert first["program"] != second["program"]        # uuid4 a process
    assert first["span"]["module"] == second["span"]["module"]
    assert first["span"]["module"].startswith("train_")
    assert first["span"]["cache_hit"] is False and second["span"]["cache_hit"] is True
    assert first["counters"] == {"executor.compile_cache_miss": 1}
    assert second["counters"] == {"executor.compile_cache_hit": 1}


def test_compile_cache_flag_registered():
    import paddle_tpu as fluid

    assert fluid.get_flags("FLAGS_compile_cache_dir") == {
        "FLAGS_compile_cache_dir": ""}


# --------------------------------------------------------------------------
# where the cache lives: flags.apply_compile_cache is the one rule
# --------------------------------------------------------------------------


def _apply_recording(monkeypatch, env_dir, flag_dir):
    """Run the rule with jax.config.update recorded instead of applied."""
    import jax

    from paddle_tpu import flags

    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    monkeypatch.setitem(flags._REGISTRY["FLAGS_compile_cache_dir"], "value",
                        flag_dir)
    return flags.apply_compile_cache(flags.CHECKOUT_CACHE_DIR), calls


def test_cache_dir_from_the_environment_is_never_overridden(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself; the program sets
    no directory in code, flag or not."""
    got, calls = _apply_recording(monkeypatch, "/placed/from/outside",
                                  "/from/the/flag")
    assert got == "/placed/from/outside"
    assert calls == {}, f"code set cache options under the env var: {calls}"


def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout(monkeypatch):
    """Unset: an entry point gets the fixed, git-ignored directory in the
    checkout — the path is part of the cache key, so it must not move."""
    from paddle_tpu import flags

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    got, calls = _apply_recording(monkeypatch, None, "")
    assert got == flags.CHECKOUT_CACHE_DIR == os.path.join(repo, ".jax_cache")
    assert calls["jax_compilation_cache_dir"] == got
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
