"""``python3 benchmark/control.py --workload <cell> --seeds 1,2,...
[--control-seeds 3] [--variants fp8_e4m3,half_batch] [--control-only]``
— the readings a cell's limits are set from (run by hand on the chip,
never by a benchmark run).

For every seed: the program against the plain reference, each number
that decides ``correct`` (the lower readings).  For the first
``--control-seeds`` seeds: the reference put in the program's place,
computed in the precision below the one the configuration states, and
with each fault planted (the upper readings); each compared number is
printed with the harness's verdict at the cell's committed limit
(``ok``), and the control has to come out not ok.  ``--control-only``
leaves the program out (its readings do not change with the control).
One process, so set-up and compilation are paid once.  ``PERF.md``
holds what was read and the limits set from it.
"""

import argparse
import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0])
from benchmark import run as R      # noqa: E402


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--variants", default=None)
    parser.add_argument("--seconds", type=float, default=0.0)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--control-only", action="store_true")
    args = parser.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    args.seed, args.trace = seeds[0], 0
    ctx, driver, _device = R.prepare(args)
    variants = (args.variants or ctx.workload["control_variants"]
                ).split(",")
    driver.control(ctx, seeds, args.control_seeds, variants,
                   args.control_only)
    return 0


if __name__ == "__main__":
    sys.exit(main())
