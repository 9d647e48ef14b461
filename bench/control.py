"""Readings that set a cell's limits: the program's on many seeds, and the
control's (the plain reference one precision below the configuration's,
put in the program's place) on a few, in one process on the chip.

    python3 -m bench.control --workload <name> --seeds 1-12 \
        --control-seeds 1-3 --seconds 8

Serving cells: each seed runs a short window at the cell's own load, then
reads the sampled requests' served tokens against the float32 reference
(the program's reading) and, on the control seeds, the tokens the fp8
reference would sample at the same positions (the control's). Sort cells:
each seed sorts its inputs once through the timed program and checks the
outputs (the program's reading); on the control seeds the bfloat16-keyed
reference sort takes the program's place in the same check.

The benchmark's own runs never run this. Prints one JSON line of
readings per seed and, last, their worst values.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench import common, run as R, serve, sort


def _seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b or a) + 1))
    return out


def _serve(run, seeds, control_seeds) -> list:
    rows = []
    for seed in sorted(set(seeds) | set(control_seeds)):
        run.seed = seed
        run.compiles.count = 0
        w = serve.serve_window(run)
        dev = run.devices[0]
        row = {"seed": seed, "requests": w["n"],
               "unfinished": w["n"] - len(w["done"]),
               "compiles_in_window": run.compiles.count}
        if seed in seeds:
            row["program"] = serve.readings(w, run.traffic, dev)
        if seed in control_seeds:
            row["control"] = serve.readings(w, run.traffic, dev,
                                            control=True)
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def _sort(run, seeds, control_seeds) -> list:
    import jax

    from bench import reference

    rows = []
    devs = run.devices
    for seed in sorted(set(seeds) | set(control_seeds)):
        fn, mesh = sort._program(run.config, run.traffic, devs)
        keys, pay = sort.inputs(run.config, run.traffic, seed, devs, mesh)
        row = {"seed": seed}
        if seed in seeds:
            worst = {}
            for k in keys:
                parts, overflow = sort.output_parts(fn(k, pay), len(devs))
                got = sort.check_output(k, parts, overflow, devices=devs)
                worst = {n: max(worst.get(n, 0), v) for n, v in got.items()}
            row["program"] = worst
        if seed in control_seeds:
            worst = {}
            for k in keys:
                # the reference sort by bfloat16 keys, cut into one equal
                # part per rank, in the program's place
                ck, cv = reference.bf16_sort(
                    jax.device_put(k, devs[0]))
                per = ck.shape[0] // len(devs)
                parts = [(ck[r * per:(r + 1) * per], cv[r * per:(r + 1) * per],
                          per) for r in range(len(devs))]
                got = sort.check_output(k, parts, 0, devices=devs)
                worst = {n: max(worst.get(n, 0), v) for n, v in got.items()}
            row["control"] = worst
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    try:
        run = R.prepare(["--workload", args.workload, "--seed", "0",
                         "--seconds", str(args.seconds), "--trace", "0"])
    except common.SetupError as e:
        common.log(f"bench.control: {e}")
        return 2
    t0 = time.perf_counter()
    drive = _serve if run.traffic["kind"] == "serve_backlog" else _sort
    rows = drive(run, _seeds(args.seeds), _seeds(args.control_seeds))
    worst = {}
    for side in ("program", "control"):
        vals = [r[side] for r in rows if side in r]
        if vals:
            worst[side] = {k: max(v[k] for v in vals) for k in vals[0]}
            worst[side + "_min"] = {k: min(v[k] for v in vals)
                                    for k in vals[0]}
    print(json.dumps({"workload": args.workload, "seconds": args.seconds,
                      "elapsed_s": time.perf_counter() - t0,
                      "worst": worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
