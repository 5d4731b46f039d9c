"""Launcher plumbing: argument parsing, the compile-cache location, and
RealBackend's one-device-per-trial placement (on virtual CPU devices)."""
import os
import subprocess
import sys

import pytest

from repro.launch import cache, train

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("argv,reduced", [
    ([], True), (["--reduced"], True), (["--no-reduced"], False)])
def test_train_reduced_flag(argv, reduced):
    assert train.parse_args(argv).reduced is reduced


def _run(script, env, *args, timeout=300):
    r = subprocess.run([sys.executable, "-c", script, *args],
                       capture_output=True,
                       text=True, timeout=timeout, cwd=REPO,
                       env={**env, "PYTHONPATH": os.path.join(REPO, "src")})
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


CACHE_SCRIPT = r"""
import sys
import jax, jax.numpy as jnp
from repro.launch.cache import enable_compile_cache
path = enable_compile_cache()
if sys.argv[1:] == ["compile"]:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
print(path)
print(jax.config.jax_compilation_cache_dir)
"""


def test_compile_cache_uses_env_dir(tmp_path):
    env = {**os.environ, cache.ENV: str(tmp_path)}
    used, configured = _run(CACHE_SCRIPT, env, "compile").split()
    assert used == configured == str(tmp_path)
    assert any(tmp_path.iterdir())          # the compile was written there


def test_compile_cache_defaults_to_checkout():
    env = {k: v for k, v in os.environ.items() if k != cache.ENV}
    used, configured = _run(CACHE_SCRIPT, env).split()
    assert used == configured == os.path.join(REPO, ".jax_cache")
    assert cache.DEFAULT_DIR == used


PLACEMENT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from repro.core.backends import RealBackend
hp = {"batch_size": 32, "learning_rate": 0.01}
for n, want in ((1, 1), (4, 4)):
    be = RealBackend(n_train=64, n_eval=32, steps_per_epoch=1, n_devices=n)
    trials = [be.init_trial("lenet-mnist", hp, seed=s) for s in range(4)]
    assert len({ts.device for ts in trials}) == want, (n, trials)
    ts, _ = be.run_epoch(trials[-1], {"microbatches": 1})
    arrays = jax.tree.leaves((ts.params, ts.opt_state, ts.eval_batch))
    assert all(a.devices() == {ts.device} for a in arrays)
print("PLACEMENT_OK")
"""


def test_real_backend_places_each_trial_on_one_device():
    assert "PLACEMENT_OK" in _run(PLACEMENT_SCRIPT, dict(os.environ))
