"""The one rule for where the persistent compile cache lives
(distributed_tensorflow_tpu/compile_cache.py): the environment variable
places it and then nothing is set in code; unset, every process started from
this checkout uses the same fixed directory inside it."""

import os
import re
import subprocess
import sys

import jax

from distributed_tensorflow_tpu import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_REPORT = (
    "import jax\n"
    "from distributed_tensorflow_tpu import compile_cache\n"
    "print(compile_cache.configure())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def _configure_in_a_fresh_process(env_value):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop(compile_cache.ENV_VAR, None)
    if env_value is not None:
        env[compile_cache.ENV_VAR] = env_value
    out = subprocess.run(
        [sys.executable, "-c", _REPORT], env=env, cwd=REPO, check=True,
        capture_output=True, text=True, timeout=300).stdout.split()
    return out  # [configure()'s answer, what JAX will use]


def test_env_var_wins_and_nothing_is_set_in_code(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_env_var_places_the_cache_jax_uses(tmp_path):
    assert _configure_in_a_fresh_process(str(tmp_path)) == [str(tmp_path)] * 2


def test_unset_is_one_fixed_dir_in_the_checkout_from_any_process():
    first = _configure_in_a_fresh_process(None)
    second = _configure_in_a_fresh_process(None)
    assert first == second == [os.path.join(REPO, ".jax_cache")] * 2
    assert compile_cache.DEFAULT_DIR == first[0]


def test_no_entry_point_places_a_cache_of_its_own():
    """No ``setdefault`` of the variable and no cache path under /tmp is
    left in the tree: the helper is the only place that decides."""
    offenders = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out", "tests")]
        for name in files:
            if not name.endswith((".py", ".sh")):
                continue
            path = os.path.join(root, name)
            if path == compile_cache.__file__:
                continue
            with open(path) as f:
                text = f.read()
            if re.search(r"/tmp/\w*jax_cache|setdefault\(\s*[\"']"
                         + compile_cache.ENV_VAR, text):
                offenders.append(os.path.relpath(path, REPO))
    assert not offenders, offenders
