"""Run one cell as ``benchmark.run`` does and, beside the program's numbers,
judge the control's by the same limits through the same ``harness.decide``:
the plain reference put in the program's place and computed in the nearest
precision below the configuration's (fp8 for bf16), and for a training cell
the planted fault "half of the batch left out". Each has to come out
``"correct": false`` at the cell's own size; the exit code is 1 where one
does not, or where the program itself is not correct. The benchmark's own
runs never run this; ``PERF.md`` lists what it read.

    python3 -m benchmark.tools.control --workload <cell> --seed <n> --seconds <s>
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from benchmark import compare, harness  # noqa: E402


def judge(detail: dict, limits: dict) -> dict:
    """Each control's numbers under the cell's own limits, through the
    harness's own ``decide``."""
    out = {}
    for name, numbers in detail["control"].items():
        checks = compare.checks_of(numbers, limits)
        out[name] = {
            "correct": harness.decide(checks),
            "compared": {k: {"value": v, "limit": lim} for k, v, lim in checks}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default="fp8")
    args = ap.parse_args(argv)
    loaded = harness.load_cell(args.workload)
    device = harness.device_gate(loaded["cell"]["chips"])
    result = harness.run_cell(loaded, args.workload, args.seed, args.seconds,
                              False, T_PROCESS, device, control=args.control)
    detail = result["notes"]["reference"]
    controls = judge(detail, loaded["limits"])
    for name, c in controls.items():
        harness.log(f"control {name}: correct {c['correct']} {c['compared']} "
                    f"on {device['kind']}")
    print(json.dumps({"seed": args.seed, "correct": result["correct"],
                      "compared": result["compared"], "controls": controls,
                      "reference": detail, "device": result["device"]}),
          flush=True)
    return 0 if result["correct"] and not any(
        c["correct"] for c in controls.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
