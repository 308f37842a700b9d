"""Where the persistent XLA compile cache goes (utils/compile_cache.py), and
that chip_smoke.py refuses to run without a TPU. Each case is its own
process: the cache directory is process-wide JAX configuration, and this
suite's own process has already placed it (tests/conftest.py)."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPORT = (
    "import json, jax\n"
    "from quoracle_tpu.utils.compile_cache import enable_compilation_cache\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "got = enable_compilation_cache()\n"
    "print(json.dumps([before, got, jax.config.jax_compilation_cache_dir]))\n")


def run(code_or_script, env_extra, cwd=REPO):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env_extra)
    return subprocess.run([sys.executable, *code_or_script], env=env,
                          cwd=cwd, capture_output=True, text=True,
                          timeout=120)


def test_env_var_places_the_cache_and_code_sets_no_directory(tmp_path):
    placed = str(tmp_path / "placed")
    p = run(["-c", REPORT], {"JAX_COMPILATION_CACHE_DIR": placed})
    assert p.returncode == 0, p.stderr
    before, got, after = json.loads(p.stdout.strip().splitlines()[-1])
    assert before == placed          # JAX read the variable itself
    assert got == placed and after == placed
    assert not os.path.exists(placed)   # nothing created by this module


def test_unset_falls_to_one_in_checkout_path_in_every_process(tmp_path):
    outs = []
    for cwd in (REPO, str(tmp_path)):   # the path must not follow the cwd
        p = run(["-c", REPORT], {}, cwd=cwd)
        assert p.returncode == 0, p.stderr
        outs.append(json.loads(p.stdout.strip().splitlines()[-1]))
    (before, got, after), second = outs
    assert before is None
    assert got == after == os.path.join(REPO, ".xla_cache")
    assert second[1:] == [got, after]
    # git-ignored: the cache never becomes part of a commit
    assert ".xla_cache/" in open(os.path.join(REPO, ".gitignore")).read()


def test_chip_smoke_refuses_to_run_without_a_tpu():
    p = run([os.path.join(REPO, "chip_smoke.py")], {})
    assert p.returncode not in (0, None)
    assert "'cpu'" in p.stderr          # names the platform it found
    assert '"ok"' not in p.stdout       # and prints no result
