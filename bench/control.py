"""Read the control at a cell's own size: the plain reference computed in
bfloat16, held to the float64 reference by the cell's own comparison.
Its smallest reading of each number is the upper end that number's
limit may take.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

For every seed it checks ``checked`` variants of the seed's first sweep,
drawn from the seed, and prints the twin horizon, the span compared and
each number the check reads of the control. It runs on the host
alone; the benchmark's runs do not run it.
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
        yield params, horizon, span, {k: read(got, want) for k, read
                                      in check.NUMBERS.items()}


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args()
    seen = {k: [] for k in check.NUMBERS}
    for seed in args.seeds:
        for params, horizon, span, got in readings(args.workload, seed):
            for k in check.NUMBERS:
                seen[k].append(got[k])
            print(f"seed={seed} {params} horizon={horizon} span={span} "
                  + " ".join(f"control_{k}={got[k]!r}" for k in check.NUMBERS),
                  flush=True)
    for k in check.NUMBERS:
        print(f"control {args.workload} {k}: min={min(seen[k])!r} "
              f"max={max(seen[k])!r} over {len(seen[k])} variants")


if __name__ == "__main__":
    main()
