#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it finds the cell's files by name (``harness/files.py``),
makes the data from ``--seed``, warms up with one whole call at the cell's
own shapes (set-up), measures whole calls for ``--seconds``, reads the
device's peak memory, frees the program's state, compares the last call's
answer with the plain reference, and prints one JSON line last on stdout.
``--trace 1`` wraps the window in the profiler and reports the per-layer
metrics instead of the end-to-end ones.

No TPU, or fewer chips than the cell asks for: exit 1, no result line.
An explicit ``JAX_PLATFORMS=cpu`` is the CPU rehearsal: the cell's
``rehearsal`` sizes, every line marked, no result line, exit code 2.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import contextlib
import glob
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

REHEARSAL_MARK = "[cpu-rehearsal, not a device number] "


def run_cell(args, rehearsal: bool, say) -> dict:
    """One run of one cell: the result line as a dict."""
    from harness import compiles, device, files, window

    workload, config = files.cell(args.workload, rehearsal)
    if args.workload not in files.listed_cells():
        say(f"note: BENCHMARK.json does not list {args.workload}")
    # the program's own switch for the persistent compile cache: a fixed
    # directory inside the checkout unless JAX_COMPILATION_CACHE_DIR is set
    from flink_ml_tpu.utils.backend import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = device.require(int(workload["chips"]), rehearsal)
    peaks = None if rehearsal else files.peaks(devices[0].device_kind)

    import jax

    t = time.perf_counter()
    data = files.generate(config, args.seed)
    say(f"data from seed {args.seed}: {time.perf_counter() - t:.2f} s")

    runner = files.module("runners", workload["runner"])
    session = runner.prepare(config, data, args.seed, devices)
    t = time.perf_counter()
    with compiles.counting() as compiled:
        model = session.call()
        requests, hits = compiled()
    say(f"warm-up call: {time.perf_counter() - t:.2f} s, {requests} compile "
        f"requests, {hits} served by the cache at {cache_dir}")
    if not rehearsal:
        session.check_plan(model)
    del model

    trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        annotate = jax.profiler.TraceAnnotation
    else:
        annotate = lambda name: contextlib.nullcontext()  # noqa: E731

    setup_s = time.perf_counter() - T_START
    with compiles.counting() as compiled:
        calls, model, t0 = window.run(session, args.seconds, annotate)
        requests, hits = compiled()
    say(f"window: {len(calls)} calls, {requests} compile requests, {hits} "
        "served by the cache")
    if args.trace:
        jax.profiler.stop_trace()
    device_line = device.line(devices)

    answer = session.answer(model)
    ctx = {
        "cell": args.workload, "config": config, "workload": workload,
        "rows": session.rows, "calls": calls, "window_start": t0,
        "setup_s": setup_s, "compile_requests": requests,
        "compile_cache_hits": hits,
        "memory_peak_bytes": device_line["memory_peak_bytes"],
        "peaks": peaks, "trace": None,
    }
    del model, session
    if args.trace:
        from harness import trace_reduce

        t = time.perf_counter()
        (pb,) = glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        raw = trace_reduce.load_xplane(pb, host_names=trace_reduce.SPANS)
        if trace_reduce.device_planes(raw):
            ctx["trace"] = trace_reduce.reduce(raw)
            device_line["busy_s"] = ctx["trace"]["busy_mean_ns"] / 1e9
            device_line["window_s"] = ctx["trace"]["window_ns"] / 1e9
        elif not rehearsal:
            raise SystemExit("the trace has no device plane")
        say(f"trace read and reduced in {time.perf_counter() - t:.2f} s")

    metrics = {}
    group = "per_layer" if args.trace else "end_to_end"
    for m in files.manifest()[group]:
        value = files.module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    t = time.perf_counter()
    reference = files.module("references", config["reference"])
    numbers = reference.compare(config, data, answer, args.seed)
    limits = config["limits"]
    compared = {name: {"value": numbers[name], "limit": limits[name]}
                for name in limits}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    say(f"reference and comparison: {time.perf_counter() - t:.2f} s")

    result = {
        "correct": bool(correct), "attempted": len(calls), "failed": 0,
        "metrics": metrics, "device": device_line,
    }
    if ctx["trace"]:
        result["breakdown"] = {"device_ops": ctx["trace"]["device_ops"],
                               "idle_gaps": ctx["trace"]["idle_gaps"]}
    result["compared"] = compared
    for name, c in compared.items():
        say(f"compared {name}: {c['value']:.6g} (limit {c['limit']:.6g})")
    say(f"correct: {correct}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import device

    rehearsal = device.rehearsing()
    mark = REHEARSAL_MARK if rehearsal else ""

    def say(text: str) -> None:
        print(mark + text, file=sys.stderr, flush=True)

    result = run_cell(args, rehearsal, say)
    if rehearsal:
        say(json.dumps(result))
        say("a rehearsal prints no result line and exits 2")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
