"""Batched ``jax.numpy`` kernels for the allocator/pacing/contention hot
paths — the ``KernelType.JNP`` registrations.

Design rule: replicate the reference *operation sequence*, not just the
formula. Progressive filling is a sorted sequential fill, so each kernel
sorts with a stable ``argsort`` (Python's ``sorted`` is stable) and runs
the fill as a ``lax.scan`` whose per-position arithmetic is
operand-for-operand the reference loop. Where the reference accumulates
left to right (WFQ's weight total, offered-bytes totals, window sums),
the kernel accumulates left to right too — never a pairwise axis
reduction — so under float64 the allocators and ``offered_share`` are
**bit-identical** to the Python loops, batch dimension and all (the
``exact`` tier in :data:`repro.fabric.backend.EQUIVALENCE_TIERS`).

Two kernels cannot promise bit-equality and declare looser tiers:
``pacing_decide`` (``sqrt``/division chains whose rounding is platform-
uniform but whose masked-window bookkeeping differs from the deque) and
``segment_overlap`` (the reference interleaves same-round and recorded
segments in encounter order; the batched kernel sums each group
separately).

Batching: every kernel accepts leading batch dimensions on its float
inputs. Structural arguments (flow counts, priorities, window length)
are static — grid variants that share structure batch together
(:mod:`repro.fabric.backend.jnp_engine` groups them).

Zero-demand padding is the batching device for ragged flow counts: a
padded zero-demand flow sorts first (stable, zeros before positives),
receives exactly ``0.0``, and leaves ``remaining`` untouched, so the
arithmetic seen by real flows is bit-identical to running the unpadded
allocator — ``tests/test_backend.py`` asserts this directly.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.fabric.backend import KernelType, register_kernel
from repro.fabric.congestion import RESIDUAL_SHARE


def _leftright_sum(a: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Strict left-to-right accumulation (Python ``sum()`` order) — never
    a pairwise reduction, so float results match the reference loops."""
    at = jnp.moveaxis(a, axis, 0)
    total, _ = lax.scan(lambda s, x: (s + x, None),
                        jnp.zeros(at.shape[1:], at.dtype), at)
    return total


def check_demands_launch(demands, capacity) -> None:
    """The allocator rejection contract, shared by the jnp and pallas
    backends: NaN/negative demands or capacity raise *before* any kernel
    launch, with text identical to the reference boundary check
    (:func:`repro.fabric.congestion._check_demands`). Tracer inputs are
    skipped — inside ``jit``/``vmap``/``scan`` the concrete scenario
    inputs were already validated at the launch boundary."""
    if isinstance(demands, jax.core.Tracer) or \
            isinstance(capacity, jax.core.Tracer):
        return
    c = np.asarray(capacity, dtype=np.float64).reshape(-1)
    bad = ~(c >= 0.0)
    if bad.any():
        raise ValueError(
            f"capacity must be >= 0, got {float(c[np.argmax(bad)])!r}")
    d = np.asarray(demands, dtype=np.float64).reshape(-1)
    bad = ~(d >= 0.0)
    if bad.any():
        raise ValueError(
            f"demands must be >= 0, got {float(d[np.argmax(bad)])!r}")


@register_kernel("maxmin_shares", KernelType.JNP)
def maxmin_shares(demands, capacity=1.0) -> jnp.ndarray:
    """Batched progressive-filling max-min allocator.

    ``demands``: ``(..., n)``; ``capacity``: scalar or ``(...)``. Returns
    allocations shaped like ``demands``. Bit-identical to the reference
    under float64: stable ascending sort, then the same
    ``min(demand, remaining / flows_left)`` fill per position.
    """
    check_demands_launch(demands, capacity)
    d = jnp.asarray(demands, dtype=float)
    n = d.shape[-1]
    if n == 0:
        return jnp.zeros_like(d)
    cap = jnp.broadcast_to(jnp.asarray(capacity, d.dtype), d.shape[:-1])
    order = jnp.argsort(d, axis=-1, stable=True)
    ds = jnp.moveaxis(jnp.take_along_axis(d, order, axis=-1), -1, 0)

    def fill(remaining, inp):
        pos, dj = inp
        fair = remaining / (n - pos)
        give = jnp.where(dj < fair, dj, fair)
        return remaining - give, give

    _, gives = lax.scan(fill, cap, (jnp.arange(n), ds))
    alloc_sorted = jnp.moveaxis(gives, 0, -1)
    inv = jnp.argsort(order, axis=-1)
    return jnp.take_along_axis(alloc_sorted, inv, axis=-1)


@register_kernel("wfq_shares", KernelType.JNP)
def wfq_shares(demands, weights=None, capacity=1.0) -> jnp.ndarray:
    """Batched weighted progressive filling (WFQ steady state).

    Stable sort by normalized demand ``d / w``; the fill carries
    ``(remaining, weight_left)`` exactly as the reference, with
    ``weight_left`` initialized by left-to-right accumulation in original
    flow order — the same float the Python loop's running sum produces.
    ``weights=None`` falls through to :func:`maxmin_shares`.
    """
    check_demands_launch(demands, capacity)
    d = jnp.asarray(demands, dtype=float)
    if weights is None:
        return maxmin_shares(d, capacity)
    n = d.shape[-1]
    if n == 0:
        return jnp.zeros_like(d)
    w = jnp.broadcast_to(jnp.asarray(weights, d.dtype), d.shape)
    cap = jnp.broadcast_to(jnp.asarray(capacity, d.dtype), d.shape[:-1])
    w_total = _leftright_sum(w)
    order = jnp.argsort(d / w, axis=-1, stable=True)
    ds = jnp.moveaxis(jnp.take_along_axis(d, order, axis=-1), -1, 0)
    ws = jnp.moveaxis(jnp.take_along_axis(w, order, axis=-1), -1, 0)

    def fill(carry, inp):
        remaining, w_left = carry
        dj, wj = inp
        fair = jnp.where(w_left > 0.0, remaining * wj / w_left, remaining)
        give = jnp.where(dj < fair, dj, fair)
        return (remaining - give, w_left - wj), give

    _, gives = lax.scan(fill, (cap, w_total), (ds, ws))
    alloc_sorted = jnp.moveaxis(gives, 0, -1)
    inv = jnp.argsort(order, axis=-1)
    return jnp.take_along_axis(alloc_sorted, inv, axis=-1)


@register_kernel("strict_priority_shares", KernelType.JNP)
def strict_priority_shares(demands, priorities, capacity=1.0
                           ) -> jnp.ndarray:
    """Batched strict-priority allocation.

    ``priorities`` must be a concrete (host) 1-D array — the class
    partition is structural, resolved at trace time; ``demands`` may
    carry leading batch dimensions. Each class runs the masked max-min
    fill over the *full* flow vector (zero-demand padding for non-class
    flows — exact, see module docstring), and the leftover capacity is
    re-derived by subtracting the class's allocations in index order with
    the reference's post-class clamp, so even the rounding of
    ``remaining`` matches the Python loop.
    """
    check_demands_launch(demands, capacity)
    d = jnp.asarray(demands, dtype=float)
    pr = np.asarray(priorities)
    n = d.shape[-1]
    if pr.ndim != 1 or pr.shape[0] != n:
        raise ValueError(f"{n} demands but {pr.size} priorities "
                         f"(must be a concrete 1-D array)")
    remaining = jnp.broadcast_to(jnp.asarray(capacity, d.dtype),
                                 d.shape[:-1])
    alloc = jnp.zeros_like(d)
    for prio in sorted(set(pr.tolist()), reverse=True):
        mask = jnp.asarray(pr == prio)
        sub = maxmin_shares(jnp.where(mask, d, 0.0), remaining)
        sub = jnp.where(mask, sub, 0.0)
        alloc = alloc + sub
        subs = jnp.moveaxis(sub, -1, 0)
        remaining, _ = lax.scan(lambda r, a: (r - a, None), remaining,
                                subs)
        remaining = jnp.where(remaining < 0.0, 0.0, remaining)
    return alloc


def _drr_single(d, w, cap, rounds: int) -> jnp.ndarray:
    n = d.shape[0]
    unit = cap / rounds / jnp.min(w)

    def round_body(state):
        alloc, deficit, active, remaining = state

        def flow(carry, j):
            alloc, deficit, remaining, stopped, still = carry
            act = active[j] & ~stopped
            dj, wj = d[j], w[j]
            new_def = deficit[j] + unit * wj
            send = new_def
            backlog = dj - alloc[j]
            send = jnp.where(backlog < send, backlog, send)
            send = jnp.where(remaining < send, remaining, send)
            send = jnp.where(act, send, 0.0)
            new_aj = alloc[j] + send
            alloc = alloc.at[j].set(jnp.where(act, new_aj, alloc[j]))
            deficit = deficit.at[j].set(
                jnp.where(act, new_def - send, deficit[j]))
            remaining = remaining - send
            still = still.at[j].set(act & (new_aj < dj))
            stopped = stopped | (act & (remaining <= 0.0))
            return (alloc, deficit, remaining, stopped, still), None

        init = (alloc, deficit, remaining, jnp.asarray(False),
                jnp.zeros(n, dtype=bool))
        (alloc, deficit, remaining, _, still), _ = lax.scan(
            flow, init, jnp.arange(n))
        return alloc, deficit, still, remaining

    def cond(state):
        _, _, active, remaining = state
        return (remaining > 1e-15 * cap) & active.any()

    state = (jnp.zeros_like(d), jnp.zeros_like(d), d > 0.0, cap)
    alloc, _, _, _ = lax.while_loop(cond, round_body, state)
    return alloc


@register_kernel("drr_shares", KernelType.JNP)
def drr_shares(demands, weights=None, capacity=1.0, rounds: int = 64
               ) -> jnp.ndarray:
    """Batched deficit round robin via ``lax.while_loop`` (the quantized
    drain is data-dependent; under ``vmap`` the loop runs until every
    batch lane drains, masking finished lanes). The per-flow arithmetic
    — deficit top-up, backlog/remaining caps, the early break once the
    link saturates mid-round — replicates the reference loop exactly."""
    check_demands_launch(demands, capacity)
    d = jnp.asarray(demands, dtype=float)
    n = d.shape[-1]
    if n == 0:
        return jnp.zeros_like(d)
    w = jnp.broadcast_to(
        jnp.ones((n,), d.dtype) if weights is None
        else jnp.asarray(weights, d.dtype), d.shape)
    cap = jnp.broadcast_to(jnp.asarray(capacity, d.dtype), d.shape[:-1])
    if d.ndim == 1:
        return _drr_single(d, w, cap, rounds)
    batch = d.shape[:-1]
    fn = jax.vmap(_drr_single, in_axes=(0, 0, 0, None))
    out = fn(d.reshape(-1, n), w.reshape(-1, n), cap.reshape(-1), rounds)
    return out.reshape(*batch, n)


@register_kernel("offered_share", KernelType.JNP)
def offered_share(own_bytes, d_i, overlaps, flow_bytes, mask=None
                  ) -> jnp.ndarray:
    """Batched offered-bytes proportional share with the
    :data:`~repro.fabric.congestion.RESIDUAL_SHARE` floor.

    ``overlaps``/``flow_bytes``: ``(..., F)`` co-tenant flows; ``mask``
    zeroes padded flow slots (adding ``0.0`` is exact, so padded and
    unpadded totals are the same float). The total accumulates left to
    right from ``own_bytes``, matching the reference loop bit-for-bit.
    """
    ov = jnp.asarray(overlaps, dtype=float)
    b = jnp.broadcast_to(jnp.asarray(flow_bytes, ov.dtype), ov.shape)
    own = jnp.broadcast_to(jnp.asarray(own_bytes, ov.dtype),
                           ov.shape[:-1])
    di = jnp.broadcast_to(jnp.asarray(d_i, ov.dtype), ov.shape[:-1])
    contrib = jnp.where(ov >= di[..., None], b,
                        (ov / di[..., None]) * b)
    if mask is not None:
        contrib = jnp.where(mask, contrib, 0.0)
    ct = jnp.moveaxis(contrib, -1, 0)
    total, _ = lax.scan(lambda s, x: (s + x, None), own, ct)
    share = jnp.where(total > own, own / total, 1.0)
    return jnp.where(share > RESIDUAL_SHARE, share, RESIDUAL_SHARE)


@register_kernel("segment_overlap", KernelType.JNP)
def segment_overlap(s_i, e_i, starts, ends) -> jnp.ndarray:
    """Aggregated busy-segment overlap of the window ``[s_i, e_i)`` with
    segments ``(starts, ends)`` along the last axis. Dead or padded
    segments need no pruning or mask: any segment with
    ``end <= window start`` (use ``end = -inf`` for empty slots)
    contributes a clamped ``0.0``, exactly as the reference's
    ``ov > 0.0`` guard skips it."""
    s = jnp.asarray(starts, dtype=float)
    e = jnp.broadcast_to(jnp.asarray(ends, s.dtype), s.shape)
    si = jnp.asarray(s_i, s.dtype)[..., None]
    ei = jnp.asarray(e_i, s.dtype)[..., None]
    ov = jnp.minimum(ei, e) - jnp.maximum(si, s)
    return _leftright_sum(jnp.where(ov > 0.0, ov, 0.0))


# ---------------------------------------------------------------------------
# pacing
# ---------------------------------------------------------------------------


def sorting_network(w: int) -> List[Tuple[int, int]]:
    """Batcher's odd-even merge sort on ``w`` wires, as compare-exchange
    pairs ``(i, j)`` with ``i < j``: applied in order, each leaves the
    smaller value on wire ``i``, and at the end wire ``k`` holds the
    ``k``-th smallest. For ``w`` not a power of two the pairs that would
    touch a wire past ``w`` are left out (as if those wires held
    ``+inf``). 12 pairs for ``w = 6``, 191 for 32."""
    pairs = []
    p = 1
    while p < w:
        k = p
        while k:
            for j in range(k % p, w - k, 2 * k):
                for i in range(min(k, w - j - k)):
                    if (i + j) // (2 * p) == (i + j + k) // (2 * p):
                        pairs.append((i + j, i + j + k))
            k //= 2
        p *= 2
    return pairs


def _window_medians(bufs, valid, count):
    """Median of the ``count`` valid entries (mask ``valid`` on the last
    axis) of each row of ``bufs``: invalid slots go to ``+inf`` and
    :func:`sorting_network` orders the columns with ``minimum`` and
    ``maximum``, unrolled at trace time over the static width, in place
    of a device ``sort`` (an XLA sort of a six-wide row costs far more
    than the dozen compare-exchanges). ``min``/``max`` return one of
    their operands, so on inputs without NaN or ``-0.0`` the ranked
    columns are bit for bit ``jnp.sort``'s."""
    w = bufs.shape[-1]
    cols = list(jnp.moveaxis(jnp.where(valid, bufs, jnp.inf), -1, 0))
    for i, j in sorting_network(w):
        cols[i], cols[j] = (jnp.minimum(cols[i], cols[j]),
                            jnp.maximum(cols[i], cols[j]))
    ranked = jnp.stack(cols[:w // 2 + 1])      # ranks count // 2 reaches
    hi = ranked[count // 2]
    lo = ranked[jnp.maximum(count // 2 - 1, 0)]
    return jnp.where(count % 2 == 1, hi, 0.5 * (lo + hi))


def bank_decide(waits, steps, early, delay, pos, count, seen, *,
                enabled: bool, warmup_iters, cv_threshold, skew_threshold,
                gain, decay, max_delay_frac
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One :class:`~repro.core.pacing.PacingBank` decision on ring-buffer
    window state — the jnp engine's per-iteration pacing step and the
    body of the registered ``pacing_decide`` kernel.

    ``waits``/``steps``/``early``: ``(n, w)`` ring buffers (write cursor
    ``pos``, ``count`` filled columns); ``delay``: the unbounded internal
    per-rank delay state; ``seen``: observations so far. Returns
    ``(bounded_delays, new_internal_delay)``. Mirrors the bank's masked
    arithmetic: left-to-right window sums in deque order, row medians
    (:func:`_window_medians`), the decay-to-zero cutoff, and the
    ``max_delay_frac`` bound. The waits and steps have no NaN or
    ``-0.0`` (the engine clips them at ``+0.0``).
    """
    waits = jnp.asarray(waits, dtype=float)
    n, w = waits.shape
    zero = jnp.zeros(n, waits.dtype)
    if not enabled or w < 2:
        return zero, jnp.asarray(delay, waits.dtype)
    steps_b = jnp.asarray(steps, waits.dtype)
    early_b = jnp.asarray(early, waits.dtype)
    delay = jnp.asarray(delay, waits.dtype)

    # deque order: oldest -> newest. While filling (count < w) the valid
    # columns are 0..count-1; once full the oldest sits at the cursor.
    idx = jnp.mod(jnp.arange(w) + jnp.where(count < w, 0, pos), w)
    valid = jnp.arange(w) < count
    wait_o = jnp.where(valid, waits[:, idx], 0.0)
    step_o = jnp.where(valid, steps_b[:, idx], 0.0)
    early_o = jnp.where(valid, early_b[:, idx], jnp.inf)

    cnt = jnp.asarray(count, waits.dtype)
    mean = _leftright_sum(wait_o) / cnt
    dev = jnp.where(valid, wait_o - mean[:, None], 0.0)
    var = _leftright_sum(dev * dev) / cnt
    mean_pos = mean > 0
    cv_wait = jnp.where(mean_pos,
                        jnp.sqrt(var) / jnp.where(mean_pos, mean, 1.0),
                        0.0)

    med_wait, med_step = _window_medians(
        jnp.stack([wait_o, step_o]), valid, count)
    own_wait = waits[:, (pos - 1) % w]       # newest observation
    min_early = early_o.min(axis=1)

    step_pos = med_step > 0
    safe = jnp.where(step_pos, med_step, 1.0)
    rel_med = jnp.where(step_pos, med_wait / safe, 0.0)
    rel_last = jnp.where(step_pos, own_wait / safe, 0.0)
    imbalanced = (rel_med > skew_threshold) | \
        ((cv_wait > cv_threshold) & (rel_last > skew_threshold))
    active = imbalanced & (min_early > 0)

    decayed = delay * decay
    decayed = jnp.where(
        decayed < 1e-6 * jnp.maximum(med_step, 1e-9), 0.0, decayed)
    new_delay = jnp.where(active, gain * min_early, decayed)
    bounded = jnp.minimum(new_delay, max_delay_frac * med_step)

    gate = (seen >= warmup_iters) & (count >= 2)
    return (jnp.where(gate, bounded, 0.0),
            jnp.where(gate, new_delay, delay))


@register_kernel("pacing_decide", KernelType.JNP)
def pacing_decide(waits, steps, early, delay, seen, cfg
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Kernel-registry entry: decide on full ``(n, c)`` windows already
    in deque order (cursor 0, all columns filled) under a
    :class:`~repro.configs.base.PacingConfig`."""
    waits = jnp.asarray(waits, dtype=float)
    c = waits.shape[1]
    return bank_decide(
        waits, steps, early, delay, pos=0, count=c, seen=seen,
        enabled=cfg.enabled, warmup_iters=cfg.warmup_iters,
        cv_threshold=cfg.cv_threshold, skew_threshold=cfg.skew_threshold,
        gain=cfg.gain, decay=cfg.decay,
        max_delay_frac=cfg.max_delay_frac)
