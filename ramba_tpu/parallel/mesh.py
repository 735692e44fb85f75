"""Device mesh + partition planning.

TPU-native replacement for the reference's shard/distribution layer:

* the partition scheduler `compute_regular_schedule` that factorizes the worker
  count into per-dimension splits minimizing communication surface
  (/root/reference/ramba/common.py:287-680), and
* the per-worker shardview metadata (/root/reference/ramba/shardview_array.py).

Here the mesh is a `jax.sharding.Mesh` and a "distribution" is a
`jax.sharding.NamedSharding`; XLA GSPMD owns memory layout and inserts the
collectives the reference implements by hand over ZMQ/MPI
(/root/reference/ramba/ramba_queue_zmq.py, ramba_queue_mpi.py).  The
surface-minimizing schedule solver is retained for the manual shard_map
paths (stencil halo planning), where cut surface still determines halo
traffic volume.
"""

from __future__ import annotations

import itertools
import math
from functools import lru_cache
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ramba_tpu import common

_mesh: Optional[Mesh] = None
# Bumped every time the mesh changes so the fuser can invalidate compiled code
# that baked in sharding constraints against the old mesh.
mesh_epoch: int = 0


def _make_default_mesh() -> Mesh:
    import time

    t0 = time.perf_counter()
    devices = jax.devices()  # first call triggers backend init (TPU probe)
    init_s = time.perf_counter() - t0
    n = len(devices)
    if common.num_workers_env is not None:
        n = min(n, int(common.num_workers_env))
        devices = devices[:n]
    ndim = max(1, min(common.mesh_ndim, 3))
    factors = balanced_factors(n, ndim)
    factors = tuple(f for f in factors if f > 1) or (1,)
    names = tuple(f"d{i}" for i in range(len(factors)))
    dev_array = np.array(devices).reshape(factors)
    mesh = Mesh(dev_array, axis_names=names)
    from ramba_tpu.observe import health as _health

    _health.record_mesh(mesh, init_s)
    return mesh


def get_mesh() -> Mesh:
    global _mesh
    if _mesh is None:
        set_mesh(_make_default_mesh())
    return _mesh


def set_mesh(mesh: Mesh) -> None:
    """Install a global device mesh (user-facing; like RAMBA_WORKERS env)."""
    global _mesh, mesh_epoch
    _mesh = mesh
    mesh_epoch += 1


def num_workers() -> int:
    return get_mesh().devices.size


@lru_cache(maxsize=None)
def prime_factors(n: int) -> tuple:
    """Prime factorization (reference: gen_prime_factors,
    /root/reference/ramba/common.py:300-318)."""
    out = []
    d = 2
    while d * d <= n:
        while n % d == 0:
            out.append(d)
            n //= d
        d += 1
    if n > 1:
        out.append(n)
    return tuple(out)


@lru_cache(maxsize=None)
def balanced_factors(n: int, k: int) -> tuple:
    """Split n into k factors as balanced as possible (largest first)."""
    factors = [1] * k
    for p in sorted(prime_factors(n), reverse=True):
        factors[int(np.argmin(factors))] *= p
    return tuple(sorted(factors, reverse=True))


def _schedules(shape: tuple, n: int):
    """Every assignment of ``n``'s prime factors to the dimensions of
    ``shape`` as ``(cost, splits)``, in enumeration order: the cost is the
    total cut surface, (splits - 1) cuts a dimension, each of area
    prod(shape) / shape[d].  Splits never exceed the dimension size."""
    ndim = len(shape)
    primes = prime_factors(n)
    total = math.prod(shape) if shape else 1
    # n is small (the worker count, typically <= a few thousand; primes
    # are few)
    for assignment in itertools.product(range(ndim), repeat=len(primes)):
        splits = [1] * ndim
        for p, d in zip(primes, assignment):
            splits[d] *= p
        if any(s > max(1, shape[d]) for d, s in enumerate(splits)):
            continue
        yield sum((s - 1) * (total / shape[d])
                  for d, s in enumerate(splits) if shape[d] > 0), tuple(splits)


@lru_cache(maxsize=4096)
def compute_regular_schedule(shape: tuple, n: int) -> tuple:
    """Choose per-dimension splits of ``n`` workers over ``shape`` minimizing
    the inter-shard surface area.

    TPU-first re-design of the reference partition scheduler
    (/root/reference/ramba/common.py:287-680, modes ratio/surface/nodesurface):
    rather than materializing per-worker index ranges, the output here is just
    the split count per dimension; the actual layout is delegated to
    NamedSharding.
    """
    ndim = len(shape)
    if ndim == 0 or n <= 1:
        return (1,) * ndim
    best = min(_schedules(shape, n), key=lambda cs: cs[0], default=None)
    return best[1] if best is not None else (1,) * ndim


def _spec_parallelism(spec: P, mesh: Mesh) -> int:
    total = 1
    for e in spec:
        if e is None:
            continue
        for nm in (e,) if isinstance(e, str) else e:
            total *= mesh.shape[nm]
    return total


def _holds(spec: P, shape: tuple, mesh: Mesh) -> bool:
    """Whether every split of ``spec`` divides the extent it splits: jax
    holds no other array."""
    return all(e is None or shape[d] % _spec_parallelism(P(e), mesh) == 0
               for d, e in enumerate(spec))


def _natural_spec(shape: tuple, mesh: Mesh) -> P:
    """The solver's choice realized on the mesh's axes; where the mesh's
    factorization cannot realize it at full parallelism, the greedy
    largest-dim assignment."""
    n = mesh.devices.size
    solved = spec_from_splits(compute_regular_schedule(shape, n), mesh)
    if _spec_parallelism(solved, mesh) == n:
        return solved
    greedy = _greedy_spec(shape, mesh)
    if _spec_parallelism(greedy, mesh) > _spec_parallelism(solved, mesh):
        return greedy
    return solved


def _dividing_spec(shape: tuple, mesh: Mesh) -> Optional[P]:
    """The least-surface split at full parallelism whose counts divide
    the extents they split and that the mesh's axes realize, or None.
    The axes are handed out from the last split dimension to the first,
    so that the layout of a reduction's result refines its operand's: of
    (T, H, W) over time x lon on 'd1' x 'd0', summed along time on every
    device, the default layout of (G, H, W), lon on ('d0', 'd1'), is each
    device's own slice (``groupby.py``)."""
    n = mesh.devices.size
    for _, splits in sorted(_schedules(shape, n), key=lambda cs: cs[0]):
        if any(shape[d] % s for d, s in enumerate(splits)):
            continue
        entries = list(spec_from_splits(splits[::-1], mesh))
        entries += [None] * (len(shape) - len(entries))
        spec = P(*entries[::-1])
        if _spec_parallelism(spec, mesh) == n:
            return spec
    return None


@lru_cache(maxsize=4096)
def _layout(shape: tuple, mesh: Mesh) -> P:
    """The default layout of an array of ``shape`` large enough to
    distribute: the solver's choice, or where that does not divide the
    extents it splits, the split that does."""
    natural = _natural_spec(shape, mesh)
    if mesh.devices.size == 1 or _holds(natural, shape, mesh):
        return natural
    dividing = _dividing_spec(shape, mesh)
    return natural if dividing is None else dividing


def _distributed(shape: tuple) -> bool:
    return len(shape) > 0 and math.prod(shape) >= common.dist_threshold


def held_spec(shape: Sequence[int],
              mesh: Optional[Mesh] = None) -> Optional[P]:
    """``default_spec`` of an array large enough to distribute, where
    every split divides the extent it splits, so that jax can hold the
    array so; None for every other shape.  It is where a flush puts such
    a result (``core/layouts.py``) and an upload such a host array."""
    shape = tuple(int(s) for s in shape)
    mesh = mesh or get_mesh()
    if not _distributed(shape):
        return None
    spec = _layout(shape, mesh)
    return spec if _holds(spec, shape, mesh) else None


def default_spec(shape: Sequence[int], mesh: Optional[Mesh] = None) -> P:
    """Pick a PartitionSpec for a new array of ``shape``.

    Small arrays are replicated (reference: do_not_distribute,
    /root/reference/ramba/common.py:217-218).  Otherwise the
    surface-minimizing partition solver chooses per-dimension split counts
    (the reference's compute_regular_schedule, common.py:287-680) and the
    splits are realized on mesh axes; when the mesh's factorization cannot
    realize the solver's choice at full parallelism, fall back to the
    greedy largest-dim assignment.  Where that choice does not divide the
    extents it splits (10,958 days four ways), the least-surface split at
    full parallelism that does divide is the layout (``_dividing_spec``);
    where none divides, the choice stands as it is.
    """
    shape = tuple(int(s) for s in shape)
    if not _distributed(shape):
        return P()
    return _layout(shape, mesh or get_mesh())


def _greedy_spec(shape: tuple, mesh: Mesh) -> P:
    """Largest-axis-to-largest-dim assignment (pre-solver behavior)."""
    axes = sorted(mesh.shape.items(), key=lambda kv: -kv[1])  # (name, size)
    dims_by_size = sorted(range(len(shape)), key=lambda d: -shape[d])
    assignment: dict[int, list] = {}
    used_dims = set()
    for name, size in axes:
        placed = False
        for d in dims_by_size:
            if d in used_dims:
                continue
            if shape[d] >= size:
                assignment[d] = [name]
                used_dims.add(d)
                placed = True
                break
        if not placed:
            # Stack this axis onto the largest already-assigned dim if the dim
            # can absorb it; otherwise leave it unused (replicate over it).
            for d in dims_by_size:
                if d in used_dims and shape[d] >= size * math.prod(
                    mesh.shape[a] for a in assignment[d]
                ):
                    assignment[d].append(name)
                    placed = True
                    break
    entries = []
    for d in range(len(shape)):
        if d in assignment:
            names = assignment[d]
            entries.append(names[0] if len(names) == 1 else tuple(names))
        else:
            entries.append(None)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def spec_from_splits(splits: Sequence[int], mesh: Optional[Mesh] = None) -> P:
    """Best-effort PartitionSpec for explicit per-dimension split counts
    (the TPU mapping of the reference's explicit ``divisions``/distribution
    arguments, e.g. create_array_with_divisions, ramba.py:8552-8560).

    Each dim with splits>1 greedily claims unused mesh axes whose sizes
    multiply to the requested split; dims whose request can't be met by the
    mesh are left replicated (best-effort, like the reference's schedule
    solver ignoring infeasible constraints)."""
    mesh = mesh or get_mesh()
    free = dict(mesh.shape)
    entries = []
    for s in splits:
        s = int(s)
        if s <= 1:
            entries.append(None)
            continue
        # single axis exact match first, then exhaustive subset search
        # (meshes have <= ~4 axes, so 2^k subsets is trivial)
        names = None
        for name, size in free.items():
            if size == s:
                names = [name]
                break
        if names is None:
            free_items = list(free.items())
            for r in range(2, len(free_items) + 1):
                for combo in itertools.combinations(free_items, r):
                    if math.prod(sz for _, sz in combo) == s:
                        names = [nm for nm, _ in combo]
                        break
                if names:
                    break
        if names:
            for nm in names:
                free.pop(nm)
            entries.append(names[0] if len(names) == 1 else tuple(names))
        else:
            entries.append(None)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def upload_sharding(shape: Sequence[int]) -> NamedSharding:
    """Where a host array of ``shape`` goes: its default layout where jax
    can hold it; where no split divides (10,958 labels over four
    devices), whole on every device of the mesh, never on one of them
    alone: a leaf on one device cannot be lowered beside results pinned
    to the mesh (``core/layouts.py``; PERF.md section 6, PR 36)."""
    mesh = get_mesh()
    spec = held_spec(shape, mesh)
    return NamedSharding(mesh, P() if spec is None else spec)


def replicated_sharding() -> NamedSharding:
    return NamedSharding(get_mesh(), P())
