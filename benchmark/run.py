#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell (``benchmark/workloads/<cell>.json``) on the machine it is
started on and prints, as the last line of its output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and, in
a traced run, ``breakdown``). ``--trace 0`` gives the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics. Labelled lines (``# name:
{...}``) before it carry what is not a metric: medians, sample counts, the
generator's lateness, MFU, peak bytes, which bound held.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 2. ``--rehearse`` runs the same code at a tiny width on
whatever device is there and says so in ``device``: for the benchmark's own
tests only, never a measurement.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()     # set-up is timed from here

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--root", default=HERE,
                    help="the directory holding workloads/, configs/, "
                         "traffic/, layer_metrics/, readers/, runners/")
    ap.add_argument("--keep-trace", default=None,
                    help="a directory to keep the traced window's events "
                         "in, for looking at one trace by hand")
    ap.add_argument("--traffic-param", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="lay a value over the cell's traffic parameters: "
                         "for the sweep that finds a cell's rate, never "
                         "for a measured run")
    args = ap.parse_args(argv)

    from benchmark.harness import common, resolve
    try:
        cell = resolve.load_cell(args.workload, args.root)
    except resolve.UnknownName as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    for kv in args.traffic_param:
        k, v = kv.split("=", 1)
        cell["mix"][k] = json.loads(v)
    if not os.path.isdir(os.path.join(os.path.dirname(HERE), "paddle_tpu")):
        print("benchmark: the system under test (paddle_tpu/) is not in "
              "this checkout", file=sys.stderr)
        return 2

    cache_dir = common.enable_compile_cache()
    devices = common.check_devices(int(cell["chips"]), args.rehearse)
    if devices is None:
        return 2
    watch = common.CompileWatch()
    common.say("cell", {"name": cell["name"], "config": cell["config"],
                        "traffic": cell["traffic"], "chips": cell["chips"],
                        "seed": args.seed, "seconds": args.seconds,
                        "trace": args.trace, "rehearse": args.rehearse,
                        "compile_cache_dir": cache_dir})

    runner = resolve.load_module("runners", cell["runner"], args.root)
    out = runner.run(cell, args, devices, T_START, watch)
    ctx = out["ctx"]
    if not args.rehearse:
        from benchmark.peaks import peaks
        ctx["peaks"] = peaks(devices[0].device_kind)
        if "mfu_inputs" in ctx:      # labelled, never in the last line
            m = ctx["mfu_inputs"]
            common.say("mfu", {
                "model_flops_utilization": out["e2e"]["train_tok_s"]
                * m["flops_per_token"]
                / (m["chips"] * ctx["peaks"]["bf16_flops"]),
                "peak_bf16_flops": ctx["peaks"]["bf16_flops"],
                "recomputation_counted": False})
    common.say("compilations_in_process", len(watch.times))

    last = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"]}
    if args.trace:
        from benchmark.harness import trace as trace_mod
        try:
            last["metrics"] = common.read_layer_metrics(
                cell, ctx, args.root, lenient=args.rehearse)
        except trace_mod.TraceError as e:
            print(f"benchmark: {e}", file=sys.stderr)
            return 1
        last["device"] = common.device_line(devices, args.rehearse,
                                            ctx["trace"])
        last["breakdown"] = {
            "device_ops": trace_mod.top_device_ops(ctx["trace"]),
            "idle_gaps": trace_mod.idle_gaps(ctx["trace"])}
        for note in ctx.get("notes", ()):
            common.say("note", note)
    else:
        with open(os.path.join(os.path.dirname(HERE),
                               "BENCHMARK.json")) as f:
            units = {m["name"]: m["unit"]
                     for m in json.load(f)["end_to_end"]}
        missing = [m for m in cell["end_to_end"] if m not in out["e2e"]]
        if missing:
            print(f"benchmark: the run produced no sample for {missing}",
                  file=sys.stderr)
            return 1
        last["metrics"] = {m: {"value": float(out["e2e"][m]),
                               "unit": units[m]}
                           for m in cell["end_to_end"]}
        last["device"] = common.device_line(devices, args.rehearse)
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
