"""``tools/sweep.py`` for a cell of the power-retention model: the same sweep
(one engine, one frontend session a rate, the same line printed), with the
sweep's ``program`` pointing at ``program_retention`` for the length of the
call, as ``drivers/serve_retention.py`` does for the driver.

    python3 -m benchmark.tools.sweep_retention --workload <cell> --per-block 10,15,20 --seconds 30 --seed 1
"""
import sys

from benchmark import program_retention
from benchmark.tools import sweep


def main(argv=None) -> int:
    before = sweep.program
    sweep.program = program_retention
    try:
        return sweep.main(argv)
    finally:
        sweep.program = before


if __name__ == "__main__":
    sys.exit(main())
