"""Where jax's persistent compilation cache lives (common.compile_cache_dir):
JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache, the same
path in every process, whatever RAMBA_CACHE says.  Each case runs in a
fresh interpreter: the directory is fixed at import."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import json, os, sys
import jax
import ramba_tpu as rt
from ramba_tpu import common

d = jax.config.jax_compilation_cache_dir
before = len(os.listdir(d)) if os.path.isdir(d) else 0
n = int(sys.argv[1])
a = rt.arange(n) * 3.0 + 1.0   # a shape no other test compiles
total = float(rt.sum(a))
assert total == 3.0 * n * (n - 1) / 2 + n, total
print(json.dumps({
    "dir": d, "resolved": common.compile_cache_dir(),
    "before": before, "after": len(os.listdir(d)),
    "enabled": bool(jax.config.jax_enable_compilation_cache),
    "aot_dir": common.persistent_cache_path(),
}))
"""


def _run(n, **env_over):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    for k in ("JAX_COMPILATION_CACHE_DIR", "RAMBA_CACHE",
              "JAX_ENABLE_COMPILATION_CACHE"):
        env.pop(k, None)
    env.update({k: v for k, v in env_over.items() if v is not None})
    r = subprocess.run([sys.executable, "-c", _CHILD, str(n)],
                       capture_output=True, text=True, timeout=240,
                       cwd=REPO, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("ramba_cache", [None, "1", "path"])
def test_env_places_the_cache(tmp_path, ramba_cache):
    want = str(tmp_path / "placed")
    if ramba_cache == "path":
        ramba_cache = str(tmp_path / "aot")
    out = _run(1931, JAX_COMPILATION_CACHE_DIR=want, RAMBA_CACHE=ramba_cache)
    assert out["dir"] == want == out["resolved"], out
    assert out["enabled"] and out["after"] > out["before"], out
    # RAMBA_CACHE arms the AOT lane at its own path, never jax's cache
    if ramba_cache is None:
        assert out["aot_dir"] is None
    elif ramba_cache == "1":
        assert out["aot_dir"] == os.path.join(want, "ramba_aot")
    else:
        assert out["aot_dir"] == ramba_cache
    assert not os.path.exists(os.path.expanduser("~/.ramba_tpu_xla_cache"))


def test_unset_env_means_the_checkout(tmp_path):
    want = os.path.join(REPO, ".jax_cache")
    first = _run(1933)
    assert first["dir"] == want == first["resolved"], first
    assert first["enabled"] and first["after"] > 0, first
    # a second process sees the same path and starts with the first's files
    second = _run(1933, RAMBA_CACHE=str(tmp_path / "aot"))
    assert second["dir"] == want, second
    assert second["before"] >= first["after"], (first, second)


def test_no_code_points_jax_at_another_directory():
    """One resolver: the only config.update of jax_compilation_cache_dir
    in the tree is common.setup_compile_cache's."""
    sources = [os.path.join(REPO, f)
               for f in ("chip_smoke.py", "__graft_entry__.py")]
    for root in ("ramba_tpu", "scripts", "examples"):
        for dp, _dn, files in os.walk(os.path.join(REPO, root)):
            sources += [os.path.join(dp, f) for f in files
                        if f.endswith(".py")]
    hits = []
    for path in sources:
        with open(path) as fh:
            if 'jax_compilation_cache_dir",' in fh.read():
                hits.append(os.path.relpath(path, REPO))
    assert hits == [os.path.join("ramba_tpu", "common.py")], hits
