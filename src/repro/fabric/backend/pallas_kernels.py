"""Pallas kernels for the fabric hot paths — the ``KernelType.PALLAS``
registrations the PR-6 registry reserved a slot for.

Two kernels carry the sweep runner's arithmetic once variant counts grow
past what ``vmap``+XLA fusion gives (the ROADMAP's giga-scale target,
arXiv:2605.21187's 100k+-rank scenarios):

  * the **fused waterfilling allocator** — one kernel serves the whole
    progressive-filling family. ``maxmin`` is the weight-1.0 instance,
    ``wfq`` passes real weights, and ``strict_priority`` runs the same
    fill per priority class under a static class-mask matrix, all inside
    a single ``pl.pallas_call`` so the sort, the fill, and the per-class
    capacity carry never leave VMEM;
  * the **busy-segment overlap reduction** — the contention-accounting
    inner loop (window-vs-segment clamped overlaps, summed per row).

Bit-exactness strategy (the ``exact`` equivalence tier): the reference
allocators are a stable ascending sort followed by a sequential fill.
Instead of sorting, the kernel computes each flow's *stable rank* with
O(n²) comparisons, one static column at a time — ``rank[j] = #{k :
key[k] < key[j] or
(key[k] == key[j] and k < j)}`` — which reproduces Python ``sorted``'s
tie-breaking exactly, then runs the fill as a ``fori_loop`` over rank
positions, selecting each position's demand/weight by masked sum (adding
``0.0`` is exact). Every arithmetic step — ``remaining * w / w_left``,
the ``d < fair`` comparison, the carry subtractions — is operand-for-
operand the reference loop, so under float64 the allocations are
bit-identical (``tests/test_backend.py`` asserts it). The O(n²) rank is
*also* why the kernel wins: it is pure VPU work with no data-dependent
gather, where the jnp path pays two ``argsort``s and two
``take_along_axis`` gathers per call.

Backend selection follows :mod:`repro.kernels.ops`: on TPU the kernels
compile via ``pl.pallas_call`` with row blocks aligned to the sweep's
variant×links grid (:func:`waterfill_specs`); elsewhere they run in
interpret mode, so CI exercises the identical kernel code on CPU
(``ops.backend(pallas_only=True)`` resolves ``auto`` to ``interpret``,
never ``xla`` — these kernels have no XLA twin). On a TPU they never
interpret unless the caller passes ``interpret=True``. Every loop over a
lane column is a static Python loop: the TPU lowering has no
dynamic lane slice.

Pre-launch validation: the PR-6 NaN/negative-demand rejection contract
holds on every backend — concrete (non-tracer) demands/capacity are
checked *before* kernel launch with the reference's exact
:class:`ValueError` text; inside a trace the check already ran on the
scenario's concrete inputs.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.fabric.backend import BackendError, KernelType, register_kernel
from repro.fabric.backend.jnp_kernels import check_demands_launch

# Row-block sizing: sublane-aligned (float32 min tile is (8, 128)) and
# capped so a (block, n) tile stays far under the ~16 MB VMEM budget even
# for float64 interpret runs.
_SUBLANE = 8
_MAX_BLOCK_ROWS = 512


def interpret_mode() -> bool:
    """Whether the fabric Pallas kernels run in interpret mode.

    One resolution path with :mod:`repro.kernels.ops`: ``auto`` picks the
    real Pallas lowering on TPU and interpret mode elsewhere
    (``pallas_only=True`` — there is no XLA twin to fall back to). Off
    the TPU a forced ``xla`` or ``interpret`` also lands on interpret: it
    is the only way to execute this kernel code there. On a TPU either
    one raises :class:`BackendError` rather than hide the device behind
    the interpreter.
    """
    from repro.kernels import ops
    mode = ops.backend(pallas_only=True)
    if mode == "pallas":
        return False
    if jax.default_backend() == "tpu":
        raise BackendError(
            f"kernel backend {mode!r} on a TPU: the fabric Pallas kernels "
            f"have no XLA twin and do not interpret on the device; use "
            f"'auto' or 'pallas', or pass interpret=True")
    return True


def waterfill_specs(rows: int, n: int,
                    block_rows: Optional[int] = None
                    ) -> Tuple[Tuple[int, ...], int, int]:
    """Grid/block geometry for a ``(rows, n)`` waterfill launch.

    Returns ``(grid, block_rows, padded_rows)``: row blocks are
    sublane-aligned (multiples of 8), capped at ``_MAX_BLOCK_ROWS``, and
    the row count pads up to a whole number of blocks — the shape
    contract the TPU compile path is built on, unit-tested without
    needing TPU hardware (``tests/test_backend.py``).
    """
    if rows < 1 or n < 1:
        raise ValueError(f"rows and n must be >= 1, got ({rows}, {n})")
    br = _MAX_BLOCK_ROWS if block_rows is None else block_rows
    br = max(_SUBLANE, min(br, math.ceil(rows / _SUBLANE) * _SUBLANE))
    br = math.ceil(br / _SUBLANE) * _SUBLANE
    nblocks = math.ceil(rows / br)
    return (nblocks,), br, nblocks * br


# ---------------------------------------------------------------------------
# the fused waterfill primitive
# ---------------------------------------------------------------------------


def _stable_rank(key: jnp.ndarray, n: int) -> jnp.ndarray:
    """Stable ascending rank of each ``key`` along the last axis —
    exactly Python ``sorted``'s order (ties broken by original index).
    One static compare per column keeps every operand a 2-D tile."""
    jidx = lax.broadcasted_iota(jnp.int32, key.shape, 1)
    rank = jnp.zeros(key.shape, jnp.int32)
    for k in range(n):
        kb = key[:, k:k + 1]
        before = (kb < key) | ((kb == key) & (k < jidx))
        rank = rank + before.astype(jnp.int32)
    return rank


def _fill_tile(d, w, remaining, n: int) -> jnp.ndarray:
    """The shared waterfill: one progressive fill of ``(br, n)`` demands
    against per-row ``(br, 1)`` ``remaining`` capacity, weights ``w``.
    Operand-for-operand the reference loop (see module docstring)."""
    rank = _stable_rank(d / w, n)
    w_left = jnp.zeros_like(remaining)
    for i in range(n):                 # left-to-right, original order —
        w_left = w_left + w[:, i:i + 1]    # the reference's running total

    def fill(p, carry):
        remaining, w_left, alloc = carry
        sel = rank == p
        dj = jnp.sum(jnp.where(sel, d, 0.0), axis=-1, keepdims=True)
        wj = jnp.sum(jnp.where(sel, w, 0.0), axis=-1, keepdims=True)
        fair = jnp.where(w_left > 0.0, remaining * wj / w_left, remaining)
        give = jnp.where(dj < fair, dj, fair)
        alloc = jnp.where(sel, give, alloc)
        return remaining - give, w_left - wj, alloc

    _, _, alloc = lax.fori_loop(0, n, fill,
                                (remaining, w_left, jnp.zeros_like(d)))
    return alloc


def _waterfill_kernel(d_ref, w_ref, cap_ref, o_ref, *, n: int):
    o_ref[...] = _fill_tile(d_ref[...], w_ref[...], cap_ref[...], n)


def _strict_priority_kernel(d_ref, m_ref, cap_ref, o_ref, *, n: int,
                            n_classes: int):
    """Descending-priority classes, each a masked waterfill over the full
    flow vector (zero-demand masking is exact — zeros rank first and
    consume nothing), the leftover capacity re-derived by subtracting the
    class's allocations in *index* order with the reference's post-class
    clamp."""
    d = d_ref[...]
    masks = m_ref[...]                 # (n_classes, n), 1.0/0.0, static
    remaining = cap_ref[...]           # (br, 1)
    ones = jnp.ones_like(d)
    alloc = jnp.zeros_like(d)
    for c in range(n_classes):         # static class count: unrolled
        mask = masks[c:c + 1, :] != 0.0
        sub = _fill_tile(jnp.where(mask, d, 0.0), ones, remaining, n)
        sub = jnp.where(mask, sub, 0.0)
        alloc = alloc + sub
        for i in range(n):             # index order, as the reference
            remaining = remaining - sub[:, i:i + 1]
        remaining = jnp.where(remaining < 0.0, 0.0, remaining)
    o_ref[...] = alloc


def _segment_overlap_kernel(si_ref, ei_ref, s_ref, e_ref, o_ref, *,
                            n_segs: int):
    si = si_ref[...]                   # (br, 1)
    ei = ei_ref[...]
    ov = jnp.minimum(ei, e_ref[...]) - jnp.maximum(si, s_ref[...])
    ov = jnp.where(ov > 0.0, ov, 0.0)
    t = jnp.zeros_like(si)
    for k in range(n_segs):            # reference encounter order
        t = t + ov[:, k:k + 1]
    o_ref[...] = t


def _launch_waterfill(d2, w2, cap2, n: int,
                      interpret: Optional[bool]) -> jnp.ndarray:
    """Pad rows to the block grid and launch the fused fill. Padded rows
    carry ``d=0, w=1, cap=0`` — clean arithmetic, discarded on return."""
    R = d2.shape[0]
    grid, br, Rp = waterfill_specs(R, n)
    if Rp != R:
        pad = ((0, Rp - R), (0, 0))
        d2 = jnp.pad(d2, pad)
        w2 = jnp.pad(w2, pad, constant_values=1.0)
        cap2 = jnp.pad(cap2, pad)
    out = pl.pallas_call(
        functools.partial(_waterfill_kernel, n=n),
        grid=grid,
        in_specs=[pl.BlockSpec((br, n), lambda i: (i, 0)),
                  pl.BlockSpec((br, n), lambda i: (i, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, n), d2.dtype),
        interpret=interpret_mode() if interpret is None else interpret,
    )(d2, w2, cap2)
    return out[:R]


def _as_rows(demands, weights, capacity):
    """Normalize ``(..., n)`` demands (+ broadcastable weights/capacity)
    into the ``(R, n)`` launch layout; returns the batch shape to restore."""
    d = jnp.asarray(demands, dtype=float)
    n = d.shape[-1]
    w = jnp.ones_like(d) if weights is None else \
        jnp.broadcast_to(jnp.asarray(weights, d.dtype), d.shape)
    cap = jnp.broadcast_to(jnp.asarray(capacity, d.dtype), d.shape[:-1])
    batch = d.shape[:-1]
    R = int(np.prod(batch, dtype=np.int64)) if batch else 1
    return (d.reshape(R, n), w.reshape(R, n), cap.reshape(R, 1),
            batch, n)


@register_kernel("maxmin_shares", KernelType.PALLAS)
def maxmin_shares(demands, capacity=1.0, *, interpret=None) -> jnp.ndarray:
    """Fused progressive-filling max-min allocator: the weight-1.0
    instance of the waterfill primitive (``x * 1.0`` is exact and the
    weight carry stays a small integer, so the arithmetic is
    operation-for-operation the unweighted reference)."""
    check_demands_launch(demands, capacity)
    d2, w2, cap2, batch, n = _as_rows(demands, None, capacity)
    if n == 0:
        return jnp.zeros(batch + (0,), d2.dtype)
    return _launch_waterfill(d2, w2, cap2, n, interpret).reshape(
        batch + (n,))


@register_kernel("wfq_shares", KernelType.PALLAS)
def wfq_shares(demands, weights=None, capacity=1.0, *,
               interpret=None) -> jnp.ndarray:
    """Fused weighted progressive filling (WFQ steady state): the
    waterfill primitive with real weights — normalized-demand stable
    rank, ``remaining * w / w_left`` fill, left-to-right weight total."""
    check_demands_launch(demands, capacity)
    d2, w2, cap2, batch, n = _as_rows(demands, weights, capacity)
    if n == 0:
        return jnp.zeros(batch + (0,), d2.dtype)
    return _launch_waterfill(d2, w2, cap2, n, interpret).reshape(
        batch + (n,))


@register_kernel("strict_priority_shares", KernelType.PALLAS)
def strict_priority_shares(demands, priorities, capacity=1.0, *,
                           interpret=None) -> jnp.ndarray:
    """Fused strict-priority allocation: ``priorities`` must be concrete
    (host) — the class partition is structural — and becomes a static
    descending class-mask matrix; the kernel runs the shared waterfill
    once per class without leaving VMEM."""
    check_demands_launch(demands, capacity)
    d = jnp.asarray(demands, dtype=float)
    pr = np.asarray(priorities)
    n = d.shape[-1]
    if pr.ndim != 1 or pr.shape[0] != n:
        raise ValueError(f"{n} demands but {pr.size} priorities "
                         f"(must be a concrete 1-D array)")
    if n == 0:
        return jnp.zeros_like(d)
    classes = sorted(set(pr.tolist()), reverse=True)
    masks = np.stack([(pr == prio).astype(np.float64)
                      for prio in classes])
    d2, _, cap2, batch, n = _as_rows(demands, None, capacity)
    R = d2.shape[0]
    grid, br, Rp = waterfill_specs(R, n)
    if Rp != R:
        d2 = jnp.pad(d2, ((0, Rp - R), (0, 0)))
        cap2 = jnp.pad(cap2, ((0, Rp - R), (0, 0)))
    C = len(classes)
    out = pl.pallas_call(
        functools.partial(_strict_priority_kernel, n=n, n_classes=C),
        grid=grid,
        in_specs=[pl.BlockSpec((br, n), lambda i: (i, 0)),
                  pl.BlockSpec((C, n), lambda i: (0, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, n), d2.dtype),
        interpret=interpret_mode() if interpret is None else interpret,
    )(d2, jnp.asarray(masks, d2.dtype), cap2)
    return out[:R].reshape(batch + (n,))


@register_kernel("segment_overlap", KernelType.PALLAS)
def segment_overlap(s_i, e_i, starts, ends, *, interpret=None
                    ) -> jnp.ndarray:
    """Aggregated busy-segment overlap of the window ``[s_i, e_i)`` with
    segments ``(starts, ends)`` along the last axis — clamped overlaps
    accumulated left to right, the reference's encounter order. Empty
    ring slots (``end = -inf``) contribute a clamped ``0.0``."""
    s = jnp.asarray(starts, dtype=float)
    e = jnp.broadcast_to(jnp.asarray(ends, s.dtype), s.shape)
    S = s.shape[-1]
    batch = s.shape[:-1]
    si = jnp.broadcast_to(jnp.asarray(s_i, s.dtype), batch)
    ei = jnp.broadcast_to(jnp.asarray(e_i, s.dtype), batch)
    if S == 0:
        return jnp.zeros(batch, s.dtype)
    R = int(np.prod(batch, dtype=np.int64)) if batch else 1
    grid, br, Rp = waterfill_specs(R, S)
    s2 = s.reshape(R, S)
    e2 = e.reshape(R, S)
    si2 = si.reshape(R, 1)
    ei2 = ei.reshape(R, 1)
    if Rp != R:
        pad = ((0, Rp - R), (0, 0))
        s2 = jnp.pad(s2, pad)
        e2 = jnp.pad(e2, pad, constant_values=-jnp.inf)
        si2 = jnp.pad(si2, pad)
        ei2 = jnp.pad(ei2, pad)
    out = pl.pallas_call(
        functools.partial(_segment_overlap_kernel, n_segs=S),
        grid=grid,
        in_specs=[pl.BlockSpec((br, 1), lambda i: (i, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0)),
                  pl.BlockSpec((br, S), lambda i: (i, 0)),
                  pl.BlockSpec((br, S), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, 1), s.dtype),
        interpret=interpret_mode() if interpret is None else interpret,
    )(si2, ei2, s2, e2)
    return out[:R, 0].reshape(batch)


# ---------------------------------------------------------------------------
# whole-scenario front door: the jnp scan runner with Pallas kernels
# ---------------------------------------------------------------------------


@register_kernel("scenario", KernelType.PALLAS)
def run_scenario(scenario, topo=None):
    """``Scenario.run(backend="pallas")``: the shared scan/vmap runner
    (:mod:`repro.fabric.backend.jnp_engine`) with its allocator and
    segment-overlap calls dispatched to the Pallas kernels above."""
    from repro.fabric.backend.jnp_engine import run_scenarios
    return run_scenarios([(scenario, topo)],
                         kernels=KernelType.PALLAS)[0]
