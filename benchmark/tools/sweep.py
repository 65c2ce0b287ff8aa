"""Find the knee of an open-loop mix once, by a sweep on the chip.

One engine, warmed once; for each rate (requests per block) a fresh frontend
session runs the mix's ramp and a window, and the line printed says what was
sent, what failed, the queue (pending less the slots in use) at the window's
middle and at its end, and the client's numbers. The knee is the highest rate
at which nothing is rejected and the queue at the end is no longer than at the
middle; the mix's file then gets 0.75 of it. The engine is built by the
program module that the cell's configuration names.

    python3 -m benchmark.tools.sweep --workload <cell> --per-block 30,40,50 --seconds 30 --seed 1
"""
import argparse
import contextlib
import importlib
import json
import sys

from benchmark import harness, stats
from benchmark.drivers import serve


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--per-block", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    loaded = harness.load_cell(args.workload)
    device = harness.device_gate(loaded["cell"]["chips"])
    import paddle_tpu as paddle
    from paddle_tpu.models.frontend import ServingFrontend

    paddle.jit.enable_compilation_cache()
    config, traffic = loaded["config"], dict(loaded["traffic"])
    program = harness.module_of(config, "program")
    model = program.build_model(config, args.seed)
    engine, fe = program.build_serving(model, config)
    fe.warmup()
    gen = importlib.import_module(traffic["generator"])
    e = config["deployment"]["engine"]
    for k, per_block in enumerate(int(x) for x in args.per_block.split(",")):
        traffic["requests_per_block"] = per_block
        requests = gen.generate(traffic, args.seed + k, args.seconds,
                                config["model"]["vocab_size"])
        if k:
            fe = ServingFrontend(
                engine, segment=e["segment"],
                max_queue=config["deployment"]["frontend"]["max_queue"])
        ses = serve.Session(fe, engine, requests,
                            lambda name: contextlib.nullcontext())
        w0 = ses.start() + float(traffic["ramp_s"])
        w1 = w0 + args.seconds
        ses.pump(w0)
        ses.pump(w1)
        ses.pump(None)
        sent = [ses.stamps[r["rid"]] for r in requests[:ses.sent]]
        failed = sum(1 for st in sent if st.status != "ok")
        rows = [s for s in ses.samples if w0 <= s[0] < w1]

        def queue_at(t):
            near = min(rows, key=lambda s: abs(s[0] - t))
            return near[6] - near[5]

        ttft = stats.ttft_each_ms(sent, w0, w1)
        prompt_tok, out_tok = stats.serve_tokens(sent, w0, w1)
        print(json.dumps({
            "per_block": per_block, "rate_rps": gen.rate_rps(traffic),
            "sent": len(sent), "failed": failed,
            "queue_mid": queue_at((w0 + w1) / 2), "queue_end": queue_at(w1),
            "queue_max": max(s[6] - s[5] for s in rows),
            "occupancy": sum(s[1] for s in rows) / len(rows),
            "pages_peak": max(s[2] for s in rows),
            "tpot_mean_ms": stats.tpot_mean_ms(sent, w0, w1),
            "ttft_p50_ms": stats.percentile(ttft, 50),
            "ttft_p90_ms": stats.percentile(ttft, 90),
            "tok_s": (prompt_tok + out_tok) / (w1 - w0),
            "device": device["kind"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
