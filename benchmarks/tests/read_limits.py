#!/usr/bin/env python3
"""Read, on the chip and at a cell's own size, the numbers the limits of
``correct`` are set from (PERF.md section 2 keeps the readings).

    python3 benchmarks/tests/read_limits.py --workload <cell> \
        --seeds 101,102,... --control-seeds 3 --fault-seeds 3 --out <file.jsonl>

One process.  For every seed: the data, one whole call of the timed path,
and the numbers ``references/<name>.compare`` gives for its answer (the
lower readings).  For the first ``--control-seeds`` seeds also the
reference in the control's precision put in the program's place (the upper
readings), and for the first ``--fault-seeds`` the reference with each
fault planted.  An explicit ``JAX_PLATFORMS=cpu`` runs the rehearsal sizes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--also-control", default="",
                    help="further control precisions, for the record")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    from harness import device, files

    rehearsal = device.rehearsing()
    workload, config = files.cell(args.workload, rehearsal)
    from flink_ml_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()
    devices = device.require(int(workload["chips"]), rehearsal)
    runner = files.module("runners", workload["runner"])
    reference = files.module("references", config["reference"])
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    def record(**line) -> None:
        line.update(cell=args.workload, platform=devices[0].platform)
        print(json.dumps(line), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")

    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        data = files.generate(config, seed)
        session = runner.prepare(config, data, seed, devices)
        t = time.perf_counter()
        model = session.call()
        fit_s = time.perf_counter() - t
        answer = session.answer(model)
        del model, session
        t = time.perf_counter()
        record(seed=seed, what="program", fit_s=fit_s,
               numbers=reference.compare(config, data, answer, seed),
               compare_s=time.perf_counter() - t)
        if i < args.control_seeds:
            stand_in = reference.control(config, data, seed)
            record(seed=seed, what="control",
                   numbers=reference.compare(config, data, stand_in, seed))
            for dtype in filter(None, args.also_control.split(",")):
                stand_in = reference.control(config, data, seed, dtype)
                record(seed=seed, what="control:" + dtype,
                       numbers=reference.compare(config, data, stand_in,
                                                 seed))
        if i < args.fault_seeds:
            for kind in reference.FAULTS:
                stand_in = reference.fault(config, data, seed, kind)
                record(seed=seed, what="fault:" + kind,
                       numbers=reference.compare(config, data, stand_in,
                                                 seed))
    return 0


if __name__ == "__main__":
    sys.exit(main())
