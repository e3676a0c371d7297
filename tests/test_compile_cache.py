"""repro.launch.compile_cache: where the persistent compile cache lives.
Each case runs in a subprocess, so this process's JAX config is untouched
and nothing is written into the checkout."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = (
    "import jax, jax.numpy as jnp\n"
    "from repro.launch.compile_cache import enable_compile_cache\n"
    "print('DIR', enable_compile_cache())\n"
    "print('CFG', jax.config.jax_compilation_cache_dir)\n"
    "if COMPILE:\n"
    "    jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.ones(7))"
    ".block_until_ready()\n")


def _run(env_extra, compile_):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra)
    r = subprocess.run(
        [sys.executable, "-c", f"COMPILE = {compile_}\n" + SCRIPT],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = dict(ln.split(" ", 1) for ln in r.stdout.splitlines()
                 if ln.startswith(("DIR ", "CFG ")))
    return lines["DIR"], lines["CFG"]


def test_cache_goes_where_the_environment_says(tmp_path):
    where = str(tmp_path / "cache")
    got, cfg = _run({"JAX_COMPILATION_CACHE_DIR": where,
                     "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"},
                    compile_=True)
    assert got == cfg == where
    assert os.listdir(where), "the compile was not cached there"


def test_cache_defaults_to_a_fixed_path_in_the_checkout():
    from repro.launch.compile_cache import CHECKOUT_CACHE_DIR
    got, cfg = _run({}, compile_=False)
    assert got == cfg == CHECKOUT_CACHE_DIR == os.path.join(ROOT,
                                                            ".jax_cache")
