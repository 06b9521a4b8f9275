"""Read the control at a cell's own size: the plain reference computed in
bfloat16, held to the float64 reference by the cell's own comparison.
Its smallest reading is the upper end a limit may take.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

For every seed it checks ``checked`` variants of the seed's first sweep,
drawn from the seed, and prints the twin horizon, the span compared and
the control's widest relative gap. It runs on the host alone; the benchmark's runs do not run
it.
"""
import argparse
import os
import random
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import cells, check  # noqa: E402


def readings(name: str, seed: int):
    spec = cells.cell(name)
    scenario = spec["config_data"]["scenario"]
    traffic = spec["traffic_data"]
    limits = spec["limits"]
    bs = cells.base_seed(seed, 0, traffic["seeds"])
    every = cells.variants(scenario, cells.sweep_axes(traffic, bs), bs)
    rng = random.Random(f"{seed}:control")
    for k in rng.sample(range(len(every)),
                        min(limits["checked"], len(every))):
        params, scn = every[k]
        want, horizon, span = check.reference_span(scn, limits["departure"])
        got = check.control_rows(scn, span)
        yield params, horizon, span, check.worst_rel(got, want)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args()
    worst = []
    for seed in args.seeds:
        for params, horizon, span, w in readings(args.workload, seed):
            worst.append(w)
            print(f"seed={seed} {params} horizon={horizon} span={span} "
                  f"control_worst_rel_iter={w!r}", flush=True)
    print(f"control {args.workload}: min={min(worst)!r} max={max(worst)!r} "
          f"over {len(worst)} variants")


if __name__ == "__main__":
    main()
