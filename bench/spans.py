"""Host spans around the program's layers, recorded from the benchmark's
side in the traced run only.

Each target is a module function that the sweep path looks up at call
time; while installed, a wrapper times every call on the host clock and
marks it in the profiler's trace as ``bench.<label>``. A target that a
later refactor renamed is skipped, and the metrics that read it come out
empty. The runner factory is wrapped too, to record the name of every
compiled runner that a sweep calls: the runner's device time is read
from the trace under that name.
"""
from __future__ import annotations

import contextlib
import importlib
import time
from typing import Dict, List, Set

ENGINE = "repro.fabric.backend.jnp_engine"
TARGETS = (
    ("sweep_call", ENGINE, "run_scenarios"),
    ("prep", ENGINE, "_prep"),
    ("runner", ENGINE, "_run_group"),
    ("wrap", ENGINE, "_wrap"),
)
RUNNER_FACTORY = (ENGINE, "_get_runner")


@contextlib.contextmanager
def annotate(label: str):
    """A ``bench.<label>`` span in the profiler's trace (free when no
    trace is being taken)."""
    import jax
    with jax.profiler.TraceAnnotation("bench." + label):
        yield


class Spans:
    def __init__(self):
        self.seconds: Dict[str, float] = {}
        self.runner_names: Set[str] = set()
        self._undo: List[tuple] = []

    def _timed(self, label, fn):
        def wrapper(*args, **kw):
            t0 = time.perf_counter()
            try:
                with annotate(label):
                    return fn(*args, **kw)
            finally:
                self.seconds[label] = self.seconds.get(label, 0.0) \
                    + time.perf_counter() - t0
        return wrapper

    def _factory(self, make):
        record = self.runner_names

        def get_runner(*args, **kw):
            fn = make(*args, **kw)
            record.add(getattr(fn, "__name__", ""))
            return fn
        return get_runner

    def install(self) -> None:
        for label, mod, attr in TARGETS:
            self._patch(mod, attr, lambda fn, label=label:
                        self._timed(label, fn))
        self._patch(*RUNNER_FACTORY, self._factory)

    def _patch(self, mod: str, attr: str, wrap) -> None:
        try:
            module = importlib.import_module(mod)
            fn = getattr(module, attr)
        except (ImportError, AttributeError):
            return
        setattr(module, attr, wrap(fn))
        self._undo.append((module, attr, fn))

    def uninstall(self) -> None:
        for module, attr, fn in reversed(self._undo):
            setattr(module, attr, fn)
        self._undo.clear()
