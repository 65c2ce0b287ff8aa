"""``tools/sweep.py`` for a cell of the ``nemotron_h`` hybrid model: the same
sweep (one engine, one frontend session a rate, the same line printed), with
the sweep's ``program`` pointing at ``program_nemotron_h`` for the length of
the call, as ``drivers/serve_nemotron_h.py`` does for the driver.

    python3 -m benchmark.tools.sweep_nemotron_h --workload <cell> --per-block 10,15,20 --seconds 30 --seed 1
"""
import sys

from benchmark import program_nemotron_h
from benchmark.tools import sweep


def main(argv=None) -> int:
    before = sweep.program
    sweep.program = program_nemotron_h
    try:
        return sweep.main(argv)
    finally:
        sweep.program = before


if __name__ == "__main__":
    sys.exit(main())
