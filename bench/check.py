"""The comparison that decides ``correct``.

A checked variant's step series, as the timed sweep returned it, is held
against the plain reference (:mod:`bench.reference`, float64) of the same
scenario, iteration by iteration and tenant by tenant, as a relative gap
``|program - reference| / reference``.

How far that can hold is set by the reference itself. Where co-tenants
contend, the simulated dynamics amplify rounding: the reference run
against its twin, the same scenario with ``u_mean`` moved by one float32
epsilon, departs by more than ``departure`` at some iteration (the
horizon) and never returns. No float32 program can follow the reference
past it. So each variant is compared over the first half of its twin's
horizon, and over the whole run where the twin never departs.

A paced job's controllers make threshold decisions. Now and then one
lies closer to its threshold than float32 resolves (a relative margin
of a few millionths or less), the float32 program takes the other
branch, and its series runs off the reference by up to a few percent
for some tens of iterations before it rejoins; the twin does not see
this. So beside the widest gap (``worst_rel_iter``) the check reads the
share of a variant's span at which the program is off the reference by
more than ``OFF`` (``off_iter_share``): such a run is off for a bounded
stretch, a fault for as long as it lasts. A cell's limits file says
which of them it compares.
"""
from __future__ import annotations

import copy
import itertools
from typing import Optional

import numpy as np

from bench import reference

EPS32 = float(np.finfo(np.float32).eps)

# a float32 program that follows the reference stays within a few
# float32 epsilons of it; an iteration further off than this is off
OFF = 1e-4


def twin(scn: dict) -> dict:
    out = copy.deepcopy(scn)
    out["congestion"]["u_mean"] = scn["congestion"]["u_mean"] * (1.0 + EPS32)
    return out


def reference_span(scn: dict, departure: float):
    """``(reference rows, horizon, span)``: the reference's reported rows
    over the checked span, the first reported iteration at which the twin
    departs (the run length if it never does), and the span checked."""
    warm, n = scn["warmup"], scn["iters"] - scn["warmup"]
    rows, horizon = [], n
    pairs = zip(reference.steps(scn), reference.steps(twin(scn)))
    for a, b in itertools.islice(pairs, warm, warm + n):
        if (np.abs(b - a) > departure * a).any():
            horizon = len(rows)
            break
        rows.append(a)
    span = n if horizon == n else horizon // 2
    return np.array(rows[:span]).reshape(span, -1), horizon, span


def control_rows(scn: dict, span: int) -> np.ndarray:
    """The control: the reference in bfloat16, its first ``span`` rows."""
    steps = reference.steps(scn, quantize=reference.bfloat16)
    rows = list(itertools.islice(steps, scn["warmup"],
                                 scn["warmup"] + span))
    return np.array(rows).reshape(span, -1)


def rel_gaps(got: Optional[np.ndarray], want: np.ndarray
             ) -> Optional[np.ndarray]:
    """``|got - want| / want`` over ``want``'s rows; ``None`` where
    ``got`` is missing, short or not finite."""
    span = want.shape[0]
    if got is None or got.ndim != 2 or got.shape[0] < span \
            or got.shape[1] != want.shape[1]:
        return None
    got = got[:span]
    if not np.isfinite(got).all():
        return None
    return np.abs(got - want) / want


def worst_rel(got: Optional[np.ndarray], want: np.ndarray) -> float:
    """Widest relative gap of ``got`` against ``want``; ``inf`` where
    ``got`` is missing, short or not finite."""
    gaps = rel_gaps(got, want)
    return float("inf") if gaps is None else float(gaps.max(initial=0.0))


def off_share(got: Optional[np.ndarray], want: np.ndarray) -> float:
    """Share of ``want``'s iterations at which some tenant of ``got`` is
    off by more than :data:`OFF`; ``inf`` where ``got`` is missing, short
    or not finite."""
    gaps = rel_gaps(got, want)
    if gaps is None:
        return float("inf")
    return float((gaps > OFF).any(axis=1).mean()) if gaps.size else 0.0


# the numbers a limits file may name, beside ``failed_variants``
NUMBERS = {"worst_rel_iter": worst_rel, "off_iter_share": off_share}


def compare(scn: dict, got: Optional[np.ndarray], departure: float
            ) -> dict:
    """One checked variant: its twin horizon, the span checked, and each
    of :data:`NUMBERS` of the program over it."""
    want, horizon, span = reference_span(scn, departure)
    return {"horizon": horizon, "span": span,
            **{k: read(got, want) for k, read in NUMBERS.items()}}
