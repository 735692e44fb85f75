"""Runtime configuration for ramba_tpu.

TPU-native rebuild of the reference's env-var config surface
(/root/reference/ramba/common.py:26-264).  The reference reads RAMBA_* environment
variables into module globals at import time and ships them to worker processes;
here there is a single controller process, so the globals are simply read once.

Unlike the reference there is no backend *selection* between ray/zmq/mpi
(/root/reference/ramba/common.py:49-100) — the communication substrate is always
XLA collectives over ICI/DCN, chosen by the device mesh (see parallel/mesh.py).
A debug backend equivalent to RAMBA_NON_DIST is obtained by running on a single
device (or a host-platform CPU mesh).
"""

from __future__ import annotations

import os
import sys
from typing import NamedTuple


_FALSY = ("0", "", "false", "False", "FALSE", "no", "NO", "off", "OFF")
_TRUTHY = ("1", "true", "True", "TRUE", "yes", "YES", "on", "ON")


def _env_flag(name: str, default: bool = False) -> bool:
    v = os.environ.get(name, None)
    if v is None:
        return default
    return v not in _FALSY


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


_BYTE_SUFFIXES = {"k": 1 << 10, "m": 1 << 20, "g": 1 << 30, "t": 1 << 40}


def parse_bytes(s) -> int:
    """Parse a byte count: a plain integer or an integer/float with a
    ``k``/``m``/``g``/``t`` suffix (binary multiples, case-insensitive,
    optional trailing ``b``/``ib``): ``"512k"`` → 524288, ``"1.5g"`` →
    1610612736.  Shared by the memory governor (``RAMBA_HBM_BUDGET``) and
    the fault harness (``oom:...:bytes=1g``).  Raises ValueError on junk."""
    if isinstance(s, (int, float)):
        return int(s)
    text = str(s).strip().lower()
    if not text:
        raise ValueError("empty byte count")
    for tail in ("ib", "b"):
        if text.endswith(tail) and text[:-len(tail)][-1:] in _BYTE_SUFFIXES:
            text = text[:-len(tail)]
            break
    mult = 1
    if text[-1:] in _BYTE_SUFFIXES:
        mult = _BYTE_SUFFIXES[text[-1]]
        text = text[:-1]
    return int(float(text) * mult)


# --- debug / timing flags (reference: common.py:102-178) ---------------------
debug_level = _env_int("RAMBA_DEBUG", 0)
timing_level = _env_int("RAMBA_TIMING", 0)
show_code = _env_flag("RAMBA_SHOW_CODE")  # dumps jaxpr/HLO instead of Numba source
# reference: RAMBA_BIG_DATA switches shard metadata to int64
# (/root/reference/ramba/shardview_array.py:24-28); here it enables x64 mode.
big_data = _env_flag("RAMBA_BIG_DATA")

# Arrays smaller than this are replicated rather than sharded
# (reference: do_not_distribute threshold, /root/reference/ramba/common.py:26,217-218).
dist_threshold = _env_int("RAMBA_DIST_THRESHOLD", 100)

# Max pending lazy ops before a forced flush.  This valve bounds graph
# *memory* (node objects held on the host); compiled-program *size* is
# bounded separately by max_program_instrs below, so this can stay large.
# (Safety valve; the reference DAG is unbounded but practical programs sync
# often.)
max_pending_ops = _env_int("RAMBA_TPU_MAX_PENDING", 10_000)

# Max instructions per compiled XLA program.  A flush whose linearized
# program exceeds this is segmented into chained jit calls of at most this
# many instructions each (fuser._run_segmented), cut at the same places of
# every repetition of a loop the script unrolled, so that a repeated
# structure compiles ONE segment and reuses it.  XLA compile time grows
# with instruction count (a single 3000-op elementwise chain took >2 min
# to compile on CPU); what a cut costs is what crosses it, stored and read
# back.  Read on the chip (NAS MG class C, 668 instructions an iteration,
# 13,381 a solve; PERF.md section 6, PR 32): at 768 an iteration is one
# segment, a solve 3603 ms, cold set-up 76 s; at 384 (the value until
# then) an iteration is cut in two, 3890 ms and 72 s; at 192, 3943 ms and
# 73 s.  Set to 0 to disable segmentation.
max_program_instrs = _env_int("RAMBA_TPU_MAX_PROGRAM_INSTRS", 768)

# How many mesh axes the default mesh is factored into (1..3).
mesh_ndim = _env_int("RAMBA_TPU_MESH_NDIM", 2)

# Pattern-rewrite rules on the lazy graph (reference: DAG rewrites,
# ramba.py:4567-4789; always on there — gated here for debugging).
rewrite_enabled = _env_flag("RAMBA_TPU_REWRITE", True)

# Forced number of devices ("workers"); default = all visible devices.
num_workers_env = os.environ.get("RAMBA_WORKERS", None)

# jax's persistent compilation cache: ONE directory, placed from outside.
# JAX_COMPILATION_CACHE_DIR when set (jax reads the variable itself and no
# code here or elsewhere points jax at another path), else
# <checkout>/.jax_cache next to this package.  A second process finds the
# first one's entries only in a directory that does not move, so it is
# never under ~, a temporary name, a pid or a time.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class CacheStatus(NamedTuple):
    """Typed result of :func:`setup_compile_cache`."""

    path: str          # directory jax's compilation cache lives in
    source: str        # "env" (JAX_COMPILATION_CACHE_DIR) or "checkout"
    ok: bool           # the directory exists and jax is configured for it
    error: str | None  # first failure, when ok is False


def compile_cache_dir() -> str:
    """The directory of jax's persistent compilation cache (see above).
    Reads the live environment."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def setup_compile_cache() -> CacheStatus:
    """Arm jax's on-disk executable cache at :func:`compile_cache_dir`,
    caching every program regardless of compile time or size (the
    reference caches every generated kernel,
    /root/reference/ramba/ramba.py:177-246).  Emits a
    ``compile.persist_init`` event so a trace records where a process
    kept its cache."""
    import jax

    from_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    path = compile_cache_dir()
    error = None
    try:
        os.makedirs(path, exist_ok=True)
    except OSError as e:
        error = f"{type(e).__name__}: {e}"
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    status = CacheStatus(path, "env" if from_env else "checkout",
                         error is None, error)
    from ramba_tpu.observe import events as _events

    _events.emit({"type": "compile.persist_init", **status._asdict()})
    return status


def persistent_cache_path() -> str | None:
    """Directory of the AOT lane (``compile/persist.py``), armed by
    RAMBA_CACHE: a path, or a truthy value for ``ramba_aot`` inside
    :func:`compile_cache_dir`.  None when unset or falsy.  RAMBA_CACHE
    does not place jax's own cache.  Reads the live environment so tests
    see runtime toggles."""
    env = os.environ.get("RAMBA_CACHE")
    if not env or env in _FALSY:
        return None
    if env in _TRUTHY:
        return os.path.join(compile_cache_dir(), "ramba_aot")
    return os.path.expanduser(env)


def dprint(level: int, *args) -> None:
    """Leveled debug print (reference: common.py:168-172)."""
    if debug_level >= level:
        print(*args, file=sys.stderr, flush=True)


def tprint(level: int, *args) -> None:
    """Leveled timing print (reference: common.py:174-178)."""
    if timing_level >= level:
        print(*args, file=sys.stderr, flush=True)


if big_data:
    # Must run before jax is first used by callers that import common first.
    os.environ.setdefault("JAX_ENABLE_X64", "1")
