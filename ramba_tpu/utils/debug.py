"""Debug artifacts for the lazy expression graph.

Reference analogs (/root/reference/ramba/ramba.py):

* ``DAG.output_dot`` — graphviz dump of the live DAG (:4481-4509),
* the unexecuted-node cluster report (:4425-4470), and
* the dag-count history written at exit (:5120-5128).

Here the graph is the pending expression forest held by the fuser; nodes are
``Node``/``Const``/``Scalar`` expressions instead of DAG entries.
"""

from __future__ import annotations

import atexit
import os
import sys

from ramba_tpu import common
from ramba_tpu.core.expr import Const, Node, Scalar


def _walk(roots):
    """Postorder walk with dedup over a set of expression roots."""
    seen: dict[int, object] = {}
    stack = list(roots)
    while stack:
        e = stack.pop()
        if id(e) in seen:
            continue
        seen[id(e)] = e
        if isinstance(e, Node):
            stack.extend(e.args)
    return list(seen.values())


def _label(e) -> str:
    if isinstance(e, Const):
        return f"const {e.aval.shape} {e.aval.dtype}"
    if isinstance(e, Scalar):
        return f"scalar {e.value!r}"
    if isinstance(e, Node):
        return f"{e.op} {tuple(e.aval.shape)} {e.aval.dtype}"
    return type(e).__name__


def output_dot(fname: str = "ramba_tpu_graph.dot") -> str:
    """Write the pending expression forest as graphviz dot (reference:
    DAG.output_dot, ramba.py:4481-4509).  Returns the dot text."""
    from ramba_tpu.core import fuser

    roots = [
        a._expr for a in fuser._pending_arrays()
        if not isinstance(a._expr, Const)
    ]
    nodes = _walk(roots)
    lines = ["digraph ramba_tpu {"]
    for e in nodes:
        shape = "box" if isinstance(e, Node) else "ellipse"
        lines.append(f'  n{id(e)} [label="{_label(e)}", shape={shape}];')
    for e in nodes:
        if isinstance(e, Node):
            for a in e.args:
                lines.append(f"  n{id(a)} -> n{id(e)};")
    lines.append("}")
    text = "\n".join(lines)
    with open(fname, "w") as f:
        f.write(text)
    return text


def report_pending(file=None) -> int:
    """Print a cluster report of not-yet-executed expressions (reference:
    the unexecuted-node report, ramba.py:4425-4470).  Returns the count."""
    from ramba_tpu.core import fuser

    file = file or sys.stderr
    arrs = [
        a for a in fuser._pending_arrays() if not isinstance(a._expr, Const)
    ]
    if not arrs:
        print("no pending lazy arrays", file=file)
        return 0
    print(f"{len(arrs)} pending lazy array(s):", file=file)
    for a in arrs:
        nodes = _walk([a._expr])
        ops = [e.op for e in nodes if isinstance(e, Node)]
        print(
            f"  seq={a._seq} shape={a.shape} dtype={a.dtype} "
            f"ops={len(ops)} [{', '.join(ops[:8])}{'...' if len(ops) > 8 else ''}]",
            file=file,
        )
    return len(arrs)


def drain_effect_errors() -> Exception | None:
    """Consume any poisoned jax runtime-effect tokens, returning the first
    error (or None).

    A kernel host-fallback (``pure_callback``) that raises — e.g. a
    ``KernelTraceError`` from a dtype-probe miss — leaves its error attached
    to jax's runtime token set; jax re-raises it at the *next* effects sync,
    which may be an unrelated computation or interpreter exit ("Exception
    ignored in atexit").  Call this after catching such an error to reset
    the token state.  jax's own ``block_until_ready`` skips its ``clear()``
    when a token raises, hence the explicit clear here.
    """
    # private API, checked against the installed jax 0.9.0 (no public
    # spelling exists); a move must fail loudly, not turn the drain into a
    # no-op that leaves the poisoned token for the next computation
    from jax._src import dispatch as _dispatch

    tokens = _dispatch.runtime_tokens
    err: Exception | None = None
    try:
        tokens.block_until_ready()
    except Exception as e:  # noqa: BLE001 - error is the return value
        err = e
    finally:
        try:
            tokens.clear()
        except Exception:  # noqa: BLE001
            pass
    return err


def _dump_history() -> None:
    """Write flush statistics at exit (reference: dag-count history files,
    ramba.py:5120-5128) plus the full observability counter registry."""
    from ramba_tpu.core import fuser
    from ramba_tpu.observe import registry

    try:
        with open("ramba_tpu_flush_history.txt", "w") as f:
            for k, v in fuser.stats.items():
                f.write(f"{k}: {v}\n")
            for k, v in sorted(registry.prefixed("").items()):
                f.write(f"{k}: {v}\n")
    except OSError:
        pass


if os.environ.get("RAMBA_TPU_HISTORY", "0") not in ("0", ""):
    atexit.register(_dump_history)
