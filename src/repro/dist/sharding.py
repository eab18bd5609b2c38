"""Logical-axis sharding rules (GShard / t5x style).

Model code annotates arrays with *logical* axis names ("batch", "fsdp",
"tp", ...). A rule set maps each logical axis to zero or more *physical*
mesh axes; ``spec_for`` resolves a tuple of logical axes against the
active rules and a mesh into a ``PartitionSpec``, dropping any mesh axis
that is already used by an earlier dimension (a mesh axis can shard at
most one dimension of an array — duplicates degrade to replication, they
are never an error). The same annotation therefore lowers correctly on a
train mesh, a serve mesh, or no mesh at all.

Rule sets are ordered ``(logical_axis, physical_axes)`` pairs; first
match wins, so a more specific rule set can be built by prepending
overrides to an existing one. Physical axes may be ``None`` (always
replicate), one mesh-axis name, or a tuple of names (shard over their
product, e.g. serve-mode tensor parallelism over the whole pod).

Entry points pick their rule set in ``repro.launch.dryrun`` /
``repro.launch.serve``: DEFAULT_RULES for train/prefill on one pod,
SERVE_RULES for decode (weights stationary over the whole mesh, batch
sharding carried by the KV cache's ``cache_batch``), and the MULTIPOD_*
variants which add the "pod" axis for cross-pod data parallelism.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec

# one logical axis -> physical mesh axes (None | str | tuple of str)
Rules = tuple[tuple[str, None | str | tuple[str, ...]], ...]

# Train / prefill, single pod ("data", "model"): FSDP over data, tensor
# parallelism (and sequence parallelism for activations) over model.
DEFAULT_RULES: Rules = (
    ("batch", "data"),
    ("cache_batch", "data"),
    ("fsdp", "data"),
    ("seq", "model"),
    ("tp", "model"),
    ("heads", "model"),
    ("kv_heads", "model"),
    ("experts", "model"),
    ("vocab", "model"),
)

# Decode, single pod: weights are stationary and shard their output dims
# over the *whole* mesh (no fsdp — no gathers on the latency path); the
# per-request batch rides on the KV cache's cache_batch axis while the
# activation "batch" annotation replicates.
SERVE_RULES: Rules = (
    ("batch", None),
    ("cache_batch", "data"),
    ("fsdp", None),
    ("seq", "model"),
    ("tp", ("data", "model")),
    ("heads", ("data", "model")),
    ("kv_heads", ("data", "model")),
    ("experts", ("data", "model")),
    ("vocab", ("data", "model")),
)

# Train / prefill across pods ("pod", "data", "model"): pure data
# parallelism over the pod axis (gradients all-reduce across pods once
# per step), FSDP kept intra-pod where the links are fast.
MULTIPOD_RULES: Rules = (
    ("batch", ("pod", "data")),
    ("cache_batch", ("pod", "data")),
    ("fsdp", "data"),
    ("seq", "model"),
    ("tp", "model"),
    ("heads", "model"),
    ("kv_heads", "model"),
    ("experts", "model"),
    ("vocab", "model"),
)

# Decode across pods: each pod holds a full weight replica (tp spans one
# pod's mesh), requests split across pods via cache_batch.
MULTIPOD_SERVE_RULES: Rules = (
    ("batch", None),
    ("cache_batch", ("pod", "data")),
    ("fsdp", None),
    ("seq", "model"),
    ("tp", ("data", "model")),
    ("heads", ("data", "model")),
    ("kv_heads", ("data", "model")),
    ("experts", ("data", "model")),
    ("vocab", ("data", "model")),
)


class _RulesContext(threading.local):
    def __init__(self):
        self.stack: list[Rules] = []


_ctx = _RulesContext()


@contextlib.contextmanager
def axis_rules(rules: Rules):
    """Bind a rule set for the dynamic extent of the context (re-entrant;
    the innermost binding wins)."""
    _ctx.stack.append(tuple(rules))
    try:
        yield
    finally:
        _ctx.stack.pop()


def current_rules() -> Rules:
    """The innermost bound rule set, or DEFAULT_RULES outside any
    ``axis_rules`` context."""
    return _ctx.stack[-1] if _ctx.stack else DEFAULT_RULES


def _lookup(rules: Rules, logical: str):
    for name, phys in rules:
        if name == logical:
            return phys
    return None


def _mesh_sizes(mesh) -> dict[str, int]:
    return dict(mesh.shape)


def spec_for(logical_axes: Sequence[str | None], mesh,
             rules: Rules | None = None) -> PartitionSpec:
    """Resolve logical axes to a ``PartitionSpec`` for ``mesh``.

    Unknown logical axes, mesh axes the mesh doesn't have, and mesh axes
    already claimed by an earlier dimension all resolve to replication.
    Trailing replicated dims are trimmed (``P("x", None)`` -> ``P("x")``)
    so specs compare cleanly.
    """
    rules = current_rules() if rules is None else rules
    sizes = _mesh_sizes(mesh)
    used: set[str] = set()
    entries: list[None | str | tuple[str, ...]] = []
    for ax in logical_axes:
        if ax is None:
            entries.append(None)
            continue
        phys = _lookup(rules, ax)
        if phys is None:
            entries.append(None)
            continue
        tup = (phys,) if isinstance(phys, str) else tuple(phys)
        tup = tuple(p for p in tup if p in sizes)
        if not tup or any(p in used for p in tup):
            entries.append(None)
            continue
        used.update(tup)
        entries.append(tup[0] if len(tup) == 1 else tup)
    while entries and entries[-1] is None:
        entries.pop()
    return PartitionSpec(*entries)


def fit_spec_to_shape(spec: PartitionSpec, shape: Sequence[int],
                      mesh) -> PartitionSpec:
    """Drop sharded axes that do not evenly divide their dimension.

    For a tuple entry, trailing sub-axes are peeled off until the product
    of the remaining axis sizes divides the dim (a prefix of a product
    sharding is still a valid sharding); a fully peeled entry replicates.
    """
    entries = tuple(spec)
    if len(entries) > len(shape):
        if any(e is not None for e in entries[len(shape):]):
            raise ValueError(
                f"spec {spec} has rank {len(entries)} but shape {tuple(shape)} "
                f"has rank {len(shape)}")
        entries = entries[:len(shape)]
    sizes = _mesh_sizes(mesh)
    out: list[None | str | tuple[str, ...]] = []
    for dim, e in zip(shape, entries):
        if e is None:
            out.append(None)
            continue
        tup = (e,) if isinstance(e, str) else tuple(e)
        while tup:
            n = 1
            for p in tup:
                n *= sizes[p]
            if dim % n == 0:
                break
            tup = tup[:-1]
        if not tup:
            out.append(None)
        else:
            out.append(tup[0] if len(tup) == 1 else tup)
    return PartitionSpec(*out)


def sanitize_shardings(shardings, abstract_tree):
    """Validate a sharding pytree against the matching abstract-eval tree.

    Every ``NamedSharding`` leaf is re-fit to its array's concrete shape
    (indivisible axes degrade to replication instead of failing at
    compile time); a spec whose rank exceeds the array's, or a tree whose
    structure does not match ``abstract_tree``, raises ``ValueError``.
    """

    def _fix(sh, ab):
        if not isinstance(sh, NamedSharding):
            return sh
        return NamedSharding(sh.mesh,
                             fit_spec_to_shape(sh.spec, tuple(ab.shape),
                                               sh.mesh))

    try:
        return jax.tree.map(_fix, shardings, abstract_tree)
    except ValueError as e:
        raise ValueError(f"sharding pytree does not match abstract tree: {e}") \
            from e


def _active_mesh():
    """The physical mesh bound by ``with mesh:``, or None.

    Reads jax's private ``thread_resources``; if that API moves, this
    raises rather than switching every sharding constraint off."""
    from jax._src import mesh as mesh_lib
    m = mesh_lib.thread_resources.env.physical_mesh
    if m is not None and not m.empty:
        return m
    return None


def shard(x: jax.Array, *logical_axes: str | None) -> jax.Array:
    """``with_sharding_constraint`` by logical axis names.

    A graceful no-op when no mesh is active (pure-CPU tests, eager use)
    or when the bound rule set is empty, so model code is annotated
    unconditionally and only pays for it under ``with mesh:``. The rank
    check runs even in no-op mode so annotation bugs surface in CPU
    tests rather than on the first production mesh.
    """
    if len(logical_axes) != x.ndim:
        raise ValueError(
            f"shard() got {len(logical_axes)} logical axes "
            f"{logical_axes} for an array of rank {x.ndim}")
    mesh = _active_mesh()
    if mesh is None:
        return x
    rules = current_rules()
    if not rules:
        return x
    spec = spec_for(logical_axes, mesh, rules)
    spec = fit_spec_to_shape(spec, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
