"""Where the persistent compilation cache lives, and where it is
refused (``stateright_tpu/jit_cache.py``).

Each case runs in a fresh interpreter: JAX reads
``JAX_COMPILATION_CACHE_DIR`` at import, and decides once per process
whether the cache is in use. ``platform="tpu"`` steers the decision
that the initialized backend makes on the chip.
"""

import json
import os
import subprocess
import sys

from stateright_tpu.jit_cache import DEFAULT_CACHE_DIR

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, jax
from stateright_tpu.jit_cache import enable_persistent_jit_cache
enable_persistent_jit_cache({arg})
print(json.dumps({{"dir": jax.config.jax_compilation_cache_dir,
                  "on": jax.config.jax_enable_compilation_cache}}))
"""


def _probe(arg="", cache_dir=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    proc = subprocess.run([sys.executable, "-c", _PROBE.format(arg=arg)],
                          cwd=_REPO, env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_env_cache_dir_is_used_exactly(tmp_path):
    where = str(tmp_path / "x")
    assert _probe('platform="tpu"', cache_dir=where) == {"dir": where,
                                                         "on": True}


def test_default_cache_dir_is_fixed():
    assert DEFAULT_CACHE_DIR == os.path.join(_REPO, ".jax_cache")
    assert _probe('platform="tpu"') == {"dir": DEFAULT_CACHE_DIR,
                                        "on": True}


def test_cache_is_refused_on_the_cpu_backend(tmp_path):
    # The backend decides: here it initializes as cpu. An env-set
    # directory does not sneak the cache back on.
    got = _probe(cache_dir=str(tmp_path / "x"))
    assert got["on"] is False
    assert _probe() == {"dir": None, "on": True}
