"""Kernel-registry backend dispatch for the simulator's hot paths.

The ROADMAP names the dense-sweep bottleneck explicitly: a 1000-point
:class:`~repro.fabric.scenario.ScenarioGrid` runs 1000 sequential Python
engine loops. The hot arithmetic lives in three places — the
progressive-filling allocators (:mod:`repro.fabric.congestion`), the
vectorized pacing bank (:mod:`repro.core.pacing`), and the busy-segment
contention accounting (:mod:`repro.fabric.engine`) — and each is a pure
function of floats, so it can be routed through a backend enum in the
style of :mod:`repro.kernels.ops`:

  * ``KernelType.REFERENCE`` — the existing Python/loop code, registered
    as-is. This backend *is* the executable spec: goldens, baselines, and
    every bit-exactness contract keep running through the same bytes.
  * ``KernelType.JNP`` — batched :mod:`jax.numpy` kernels plus a
    ``lax.scan``/``vmap`` whole-scenario runner
    (:mod:`repro.fabric.backend.jnp_engine`) that executes every variant
    of a grid sweep as one compiled program.
  * ``KernelType.PALLAS`` — Pallas kernels
    (:mod:`repro.fabric.backend.pallas_kernels`) for the two hot paths
    that dominate dense sweeps: the fused waterfilling allocator family
    (``maxmin``/``wfq``/``strict_priority`` via one primitive) and the
    busy-segment overlap reduction. On TPU they compile via
    ``pl.pallas_call``; on CPU they run in interpret mode so CI
    exercises the identical kernel code. The ``scenario`` kernel is the
    shared scan/vmap runner with its allocator/overlap calls dispatched
    to the Pallas kernels. Kernels without a Pallas win
    (:data:`PALLAS_KERNELS` is the registered subset) still raise
    :class:`BackendError` naming the nearest supported backend.

Selection surfaces: ``Scenario.run(backend=...)``,
``ScenarioGrid.run(backend=...)``, and the ``Policies.backend`` field as
the declarative default. Kernel-level access for tests and benchmarks is
``get_kernel(name, backend)``.

Equivalence is *tiered per kernel*, not hand-waved globally: every entry
in :data:`EQUIVALENCE_TIERS` declares how close the fast backend must
track the reference — ``exact`` (bit-identical under float64), ``ulp``
(a few ULPs, where summation order legitimately differs), or ``rtol``
(relative tolerance, for whole-engine series where rounding differences
feed back through the simulation). ``tests/test_backend.py`` asserts
each kernel at its declared tier, under both float32 (the production
default) and float64.
"""
from __future__ import annotations

import enum
import os
from typing import Callable, Dict, Tuple, Union


class BackendError(RuntimeError):
    """A kernel/scenario was requested on a backend that cannot run it
    (unregistered kernel/backend combination or an unsupported scenario
    feature); the message names the offending feature and the nearest
    backend that supports it."""


class KernelType(enum.Enum):
    """Which implementation family executes a hot-path kernel."""

    REFERENCE = "reference"       # existing Python loops — the spec
    JNP = "jnp"                   # batched jax.numpy / lax.scan / vmap
    PALLAS = "pallas"             # fused Pallas kernels (TPU; interpret
    #                               mode on CPU), PALLAS_KERNELS subset

    @classmethod
    def parse(cls, spec: Union[str, "KernelType", None],
              default: "KernelType" = None) -> "KernelType":
        if spec is None:
            return default if default is not None else cls.REFERENCE
        if isinstance(spec, cls):
            return spec
        try:
            return cls(str(spec).lower())
        except ValueError:
            raise BackendError(
                f"unknown backend {spec!r}; one of "
                f"{tuple(k.value for k in cls)}") from None


BACKENDS: Tuple[str, ...] = tuple(k.value for k in KernelType)

# Fairness modes the batched whole-scenario runner can batch (the owner-
# aggregated share models; see repro.fabric.backend.jnp_engine). Both
# accelerated backends (jnp and pallas) share the runner and therefore
# this envelope. Listed here so Scenario validation can check eagerly
# without importing jax.
JNP_SCENARIO_FAIRNESS: Tuple[str, ...] = ("maxmin", "wfq",
                                          "strict_priority")

# Backends the batched scan/vmap scenario runner serves (eagerly
# validated by Scenario; the runner itself dispatches per-kernel).
BATCHED_SCENARIO_BACKENDS: Tuple[str, ...] = ("jnp", "pallas")

# The kernel catalogue. Every name is registered for REFERENCE (the
# executable spec) and JNP (the batched fast path); the PALLAS_KERNELS
# subset below additionally registers for PALLAS.
KERNELS: Tuple[str, ...] = (
    "maxmin_shares",              # progressive-filling max-min allocator
    "wfq_shares",                 # weighted progressive filling
    "strict_priority_shares",     # descending priority classes
    "drr_shares",                 # deficit round robin
    "offered_share",              # offered-bytes proportional share
    "pacing_decide",              # PacingBank window -> bounded delays
    "segment_overlap",            # busy-segment contention accounting
    "scenario",                   # whole-scenario runner (engine loop)
)

# name -> (tier, tolerance) — how close the fast backend must track the
# reference, asserted per kernel by tests/test_backend.py:
#   exact : bit-identical under float64 (same op sequence, stable sort)
#   ulp   : within `tol` ULPs under float64 (summation order differs)
#   rtol  : within relative `tol` (feedback loops amplify rounding; the
#           float32 production dtype is asserted at a looser 1e-3)
EQUIVALENCE_TIERS: Dict[str, Tuple[str, float]] = {
    "maxmin_shares": ("exact", 0.0),
    "wfq_shares": ("exact", 0.0),
    "strict_priority_shares": ("exact", 0.0),
    "drr_shares": ("exact", 0.0),
    "offered_share": ("exact", 0.0),
    "pacing_decide": ("ulp", 4.0),
    "segment_overlap": ("ulp", 8.0),
    "scenario": ("rtol", 1e-9),
}

# The kernels with a Pallas registration (the fused waterfill family,
# the overlap reduction, and the scenario runner they feed). Each lands
# by registering and declaring its tier above — drr's owner-aggregation
# path and the byte-weighted offered share stay jnp/reference until
# their formulations vectorize (ROADMAP open item).
PALLAS_KERNELS: Tuple[str, ...] = (
    "maxmin_shares",
    "wfq_shares",
    "strict_priority_shares",
    "segment_overlap",
    "scenario",
)

_REGISTRY: Dict[Tuple[str, KernelType], Callable] = {}
_LOADED: set = set()


def register_kernel(name: str, backend: KernelType,
                    fn: Callable = None) -> Callable:
    """``register_kernel(name, backend, fn)`` directly or
    ``@register_kernel(name, backend)`` as a decorator. Re-registering a
    taken (name, backend) slot raises."""
    if name not in KERNELS:
        raise ValueError(f"unknown kernel {name!r}; one of {KERNELS}")

    def _add(f: Callable) -> Callable:
        key = (name, backend)
        if key in _REGISTRY:
            raise ValueError(
                f"kernel {name!r} already registered for backend "
                f"{backend.value!r}")
        _REGISTRY[key] = f
        return f

    return _add(fn) if fn is not None else _add


def _ensure_loaded(backend: KernelType) -> None:
    """Import the backend's kernel module on first use (lazy so that the
    reference path never pays a jax import)."""
    if backend in _LOADED:
        return
    _LOADED.add(backend)
    if backend is KernelType.REFERENCE:
        from repro.fabric.backend import reference  # noqa: F401
    elif backend is KernelType.JNP:
        from repro.fabric.backend import jnp_engine  # noqa: F401
        from repro.fabric.backend import jnp_kernels  # noqa: F401
    elif backend is KernelType.PALLAS:
        from repro.fabric.backend import pallas_kernels  # noqa: F401


def nearest_backend(name: str, requested: KernelType) -> Union[str, None]:
    """The closest registered stand-in for ``name`` when ``requested``
    has no implementation: the fastest backend below the requested one
    (``pallas -> jnp -> reference``), or ``None`` for unknown kernels."""
    avail = available_backends(name)
    for candidate in ("jnp", "reference"):
        if candidate != requested.value and candidate in avail:
            return candidate
    return None


def get_kernel(name: str, backend: Union[str, KernelType]) -> Callable:
    """The registered implementation of ``name`` on ``backend``."""
    bk = KernelType.parse(backend)
    _ensure_loaded(bk)
    try:
        return _REGISTRY[(name, bk)]
    except KeyError:
        if name not in KERNELS:
            raise BackendError(
                f"unknown kernel {name!r}; one of {KERNELS}") from None
        avail = tuple(b.value for (n, b) in _REGISTRY if n == name)
        near = nearest_backend(name, bk)
        hint = f"; nearest supported backend: {near!r}" if near else ""
        raise BackendError(
            f"kernel {name!r} has no {bk.value!r} implementation "
            f"(registered backends: {avail or '()'}){hint}") from None


def available_backends(name: str) -> Tuple[str, ...]:
    """Backends that implement ``name`` (loads the lazy modules)."""
    for bk in KernelType:
        _ensure_loaded(bk)
    return tuple(b.value for (n, b) in _REGISTRY if n == name)


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at one fixed directory and
    return it: the one ``JAX_COMPILATION_CACHE_DIR`` names, which JAX
    reads itself, or else ``<repo>/.jax_cache``. A program calls this at
    startup; importing the library sets no cache."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        *[os.pardir] * 4, ".jax_cache")
    path = os.path.normpath(path)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def batched_eligible(scenario) -> bool:
    """Whether the batched runner takes ``scenario`` in a counterfactual
    sweep: static jobs, fairness inside :data:`JNP_SCENARIO_FAIRNESS`,
    static routing."""
    return (scenario.jobs is not None
            and scenario.policies.fairness in JNP_SCENARIO_FAIRNESS
            and getattr(scenario.policies, "routing", "ecmp_static")
            == "ecmp_static")


def counterfactual_sweep(scenarios, backend: Union[str, KernelType] = "jnp"
                         ) -> list:
    """Run an arbitrary scenario list for the what-if advisor
    (:mod:`repro.fabric.advisor`): every batched-eligible variant (static
    jobs, fairness inside :data:`JNP_SCENARIO_FAIRNESS`) executes through
    the vmapped runner as one program per structural group, everything
    else — event timelines, exotic fairness — falls back to the reference
    engine, as does the whole batch if the runner rejects a schedule
    shape. Returns ``(result, backend_name)`` pairs in input order, so
    the advisor can grade each prediction's confidence by the
    equivalence tier of the backend that produced it.
    """
    kind = KernelType.parse(backend, default=KernelType.JNP)
    out: list = [None] * len(scenarios)
    eligible: list = []
    if kind in (KernelType.JNP, KernelType.PALLAS):
        eligible = [i for i, s in enumerate(scenarios)
                    if batched_eligible(s)]
    if eligible:
        from repro.fabric.backend.jnp_engine import run_scenarios
        try:
            results = run_scenarios(
                [(scenarios[i], None) for i in eligible], kernels=kind)
            for i, res in zip(eligible, results):
                out[i] = (res, kind.value)
        except BackendError:
            pass            # fall through: run the stragglers on reference
    for i, s in enumerate(scenarios):
        if out[i] is None:
            out[i] = (s.run(backend="reference"), "reference")
    return out
