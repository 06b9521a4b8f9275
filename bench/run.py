"""Run one benchmark cell once on the accelerator this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. With ``--trace 0`` the result line carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
read from a profiler trace of the window's first sweeps. The last line
of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, and ``checks`` last: every number
compared with the reference beside its limit); the same checks close
standard error. The run exits non-zero, printing no result, when JAX
finds no TPU or fewer chips than the cell asks for.

JAX's persistent compilation cache is the program's
(``repro.fabric.backend.use_compile_cache``): ``$JAX_COMPILATION_CACHE_DIR``
where it is set, else ``<checkout>/.jax_cache``, so only a checkout's
first run of a cell compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def tpu(chips: int):
    """The device the cell runs on; exits non-zero where JAX finds no TPU
    or fewer than ``chips`` of them."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        sys.exit(f"bench: needs {chips} TPU chip(s); JAX found "
                 f"{len(devices)} {devices[0].platform} device(s)")
    return devices[0]


def main(argv=None) -> None:
    args = parse(argv)
    from bench import cells, harness
    chips = cells.cell(args.workload)["chips"]
    device = tpu(chips)
    import jax
    from repro.fabric.backend import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(f"device: platform={device.platform} kind={device.device_kind} "
          f"count={len(jax.devices())}", file=sys.stderr, flush=True)
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as trace_dir:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), device, T_START,
                          trace_dir=trace_dir)
    w = out["window"]
    print(f"window: sweeps={w['sweeps']} seconds={w['seconds']!r} "
          f"compiles={w['compiles']}", file=sys.stderr)
    for c in out["check_detail"]:
        print("checked: " + " ".join(f"{k}={v}" for k, v in c.items()),
              file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
