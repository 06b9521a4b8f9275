"""One reader per per-layer metric, found by the metric's name: a module
``bench.metrics.<name>`` with ``read(ctx)`` that returns the value, or
``None`` where the traced run gave it nothing to read. ``ctx`` is the
harness's :class:`bench.harness.Context`."""
