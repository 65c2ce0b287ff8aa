"""``tools/sweep.py`` for a cell of the sparse-expert / latent-attention
model: the same sweep (one engine, one frontend session a rate, the same line
printed), with the sweep's ``program`` pointing at ``program_moe_mla`` for the
length of the call, as ``drivers/serve_moe_mla.py`` does for the driver.

    python3 -m benchmark.tools.sweep_moe_mla --workload <cell> --per-block 15,20,25 --seconds 30 --seed 1
"""
import sys

from benchmark import program_moe_mla
from benchmark.tools import sweep


def main(argv=None) -> int:
    before = sweep.program
    sweep.program = program_moe_mla
    try:
        return sweep.main(argv)
    finally:
        sweep.program = before


if __name__ == "__main__":
    sys.exit(main())
