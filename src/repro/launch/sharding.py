"""Logical-axis sharding: models annotate tensors with *logical* axis names;
the launcher binds those names to physical mesh axes (MaxText-style rules).

Rules map logical name -> mesh axis (or tuple of mesh axes). Resolution
applies a **divisibility fallback**: if a tensor dim is not divisible by the
product of the mapped mesh-axis sizes, that dim falls back to replication
instead of failing GSPMD (e.g. 28 attention heads on a 16-way model axis).
Every fallback is recorded so the dry-run can report exactly which dims
replicated — replication waste is a first-class roofline signal, not a silent
degradation.

Outside an ``axis_rules`` context (unit tests on one device), ``logical`` is
an identity function, so model code never branches on distribution.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LogicalSpec = Sequence[Union[str, None, Tuple[str, ...]]]

# Default logical -> physical rules for the production meshes. "batch" spans
# the pure-DP axes; "model-ish" names map to the TP axis.
DEFAULT_RULES: Dict[str, Union[str, Tuple[str, ...]]] = {
    "batch": ("pod", "data"),
    "ddp": ("pod", "data"),        # optimizer-state (ZeRO-1) sharding axis
    "model": "model",
    "heads": "model",
    "kv_heads": "model",
    "ff": "model",
    "vocab": "model",
    "embed": None,                 # d_model stays unsharded in activations
    "seq": None,                   # context parallelism binds this (hillclimb)
    "expert": None,                # EP binds this (hillclimb); baseline: F-shard
    "state": None,
}


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Optional[Dict[str, Union[str, Tuple[str, ...]]]] = None
        self.fallbacks: List[Tuple[str, int, int]] = []


_ctx = _Ctx()


@contextlib.contextmanager
def axis_rules(mesh: Mesh, rules: Optional[Dict] = None):
    """Bind logical axis names to *mesh* for the duration of the context."""
    prev = (_ctx.mesh, _ctx.rules)
    _ctx.mesh = mesh
    _ctx.rules = dict(DEFAULT_RULES, **(rules or {}))
    _ctx.fallbacks = []
    try:
        yield
    finally:
        _ctx.mesh, _ctx.rules = prev


def active_mesh() -> Optional[Mesh]:
    return _ctx.mesh


def _manual_abstract_mesh():
    """The ambient AbstractMesh when inside a shard_map manual region,
    else None."""
    amesh = jax.sharding.get_abstract_mesh()
    if amesh.empty or not amesh._any_axis_manual:
        return None
    return amesh


def manual_axes() -> frozenset:
    """Mesh axes currently bound Manual by an enclosing shard_map."""
    amesh = jax.sharding.get_abstract_mesh()
    return frozenset(a for a in amesh.axis_names
                     if amesh._name_to_type[a] ==
                     jax.sharding.AxisType.Manual)


def shard_map_mesh():
    """Mesh object to hand to a nested shard_map: the ambient abstract
    mesh when inside a manual region, else the bound concrete mesh."""
    amesh = _manual_abstract_mesh()
    return _ctx.mesh if amesh is None else amesh


def fallbacks() -> List[Tuple[str, int, int]]:
    """(logical_name, dim_size, required_divisor) replication fallbacks seen."""
    return list(_ctx.fallbacks)


def _mesh_axes_for(name: Optional[str]) -> Tuple[str, ...]:
    if name is None:
        return ()
    rule = _ctx.rules.get(name, None)
    if rule is None:
        return ()
    axes = (rule,) if isinstance(rule, str) else tuple(rule)
    # drop axes not present in the active mesh (e.g. "pod" on single-pod)
    return tuple(a for a in axes if a in _ctx.mesh.shape)


def resolve_spec(shape: Sequence[int], spec: LogicalSpec) -> P:
    """Logical spec -> PartitionSpec with divisibility fallback."""
    assert _ctx.mesh is not None
    out = []
    for dim, names in zip(shape, spec):
        if names is None:
            out.append(None)
            continue
        logical = (names,) if isinstance(names, str) else tuple(names)
        phys: List[str] = []
        for nm in logical:
            phys.extend(_mesh_axes_for(nm))
        if not phys:
            out.append(None)
            continue
        div = 1
        for a in phys:
            div *= _ctx.mesh.shape[a]
        if dim % div != 0:
            # Try dropping trailing physical axes until divisible (partial
            # sharding beats full replication), else replicate.
            while phys and dim % div != 0:
                dropped = phys.pop()
                div //= _ctx.mesh.shape[dropped]
            _ctx.fallbacks.append(
                ("/".join(map(str, logical)), dim, div))
        if not phys:
            out.append(None)
        elif len(phys) == 1:
            out.append(phys[0])
        else:
            out.append(tuple(phys))
    return P(*out)


def logical(x: jax.Array, *spec: Union[str, None, Tuple[str, ...]]):
    """Apply a logical sharding constraint (identity when no rules bound)."""
    if _ctx.mesh is None:
        return x
    if len(spec) != x.ndim:
        raise ValueError(f"spec {spec} rank != array rank {x.ndim}")
    p = resolve_spec(x.shape, spec)
    # Inside a shard_map manual region the trace context carries an
    # AbstractMesh with Manual axis types; constraints must be built
    # against it (rules must not mention the manual axes there).
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(shard_map_mesh(), p))


def named_sharding(shape: Sequence[int], spec: LogicalSpec) -> NamedSharding:
    assert _ctx.mesh is not None
    return NamedSharding(_ctx.mesh, resolve_spec(shape, spec))


def tp_row_matmul(h: jax.Array, w: jax.Array, shard_name: str = "ff"):
    """Row-parallel TP matmul with an EXPLICIT bf16 psum.

    ``h``: (..., F) activations sharded on F over the model axis;
    ``w``: (F, D) row-sharded weights. GSPMD's automatic placement tends to
    sink the partial-sum all-reduce past the downstream f32 upcast (norms),
    doubling wire bytes; a shard_map body forces ``psum`` in the matmul
    dtype. Enabled by ``REPRO_BF16_TP=1`` (a §Perf hillclimb); falls back
    to a plain matmul whenever shapes don't divide the mesh.
    """
    import os
    mesh = _ctx.mesh
    if not os.environ.get("REPRO_BF16_TP") or mesh is None \
            or "model" not in mesh.shape or "model" in manual_axes():
        return h @ w
    tp = mesh.shape["model"]
    F = h.shape[-1]
    if F % tp != 0 or w.shape[0] != F:
        return h @ w
    hspec = resolve_spec(h.shape, ("batch",) + (None,) * (h.ndim - 2)
                         + (shard_name,))
    if hspec[-1] != "model":
        return h @ w                  # contraction dim didn't shard
    wspec = resolve_spec(w.shape, (shard_name, None))
    out_spec = resolve_spec(h.shape[:-1] + (w.shape[-1],),
                            ("batch",) + (None,) * (h.ndim - 2) + (None,))

    def body(hl, wl):
        return jax.lax.psum(hl @ wl, "model")

    manual = {a for a in mesh.shape if a not in manual_axes()}
    return jax.shard_map(
        body, mesh=shard_map_mesh(), in_specs=(hspec, wspec),
        out_specs=out_spec, axis_names=manual, check_vma=False)(h, w)
