#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, one configuration or one metric is
found by the name BENCHMARK.json gives it:

    benchmark/configs/<config>.json     the deployment
    benchmark/workloads/<cell>.json     the traffic mix and its driver
    benchmark/drivers/<driver>.py       set-up, window, correctness pass
    benchmark/metrics/<metric>.json     a per-layer metric: reader, parameters
    benchmark/readers/<reader>.py       takes the metric from facts or trace

This file holds no name of a cell, a configuration or a metric.  One
process, one import of JAX, no child.  Without a TPU it exits non-zero and
prints no result.
"""

import time

T_START = time.monotonic()

import argparse
import faulthandler
import importlib
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit("benchmark: BENCHMARK.json has no %s %r" % (what, name))


def applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def per_layer(bench: dict, cell: str, run: dict) -> dict:
    out = {}
    for metric in bench["per_layer"]:
        if not applies(metric, cell):
            continue
        spec = load(HERE, "metrics", metric["name"] + ".json")
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        value = reader.read(spec.get("params", {}), run)
        if value is not None:   # nothing to read: left out, never 0
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def main(argv=None, bench_file: str = os.path.join(ROOT, "BENCHMARK.json"),
         mixes: str = os.path.join(HERE, "workloads"),
         guard: bool = False) -> int:
    """`bench_file` and `mixes` are for the rehearsal under
    benchmark/tests, whose tiny cells no BENCHMARK.json names; `guard`
    ends a run that hangs with its stacks, which only the command asks."""
    if guard:   # set-up may compile for minutes: the first run's allowance
        faulthandler.dump_traceback_later(1150, exit=True)
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    bench = load(bench_file)
    cell = named(bench["workloads"], args.workload, "workload")
    config = load(ROOT, named(bench["configs"], cell["config"],
                              "configuration")["file"])
    mix = load(mixes, cell["name"] + ".json")

    from benchmark.harness import device, peaks, trace
    from benchmark.harness.session import Session
    dev = device.require_chips(cell["chips"])
    chip_peaks = peaks.peaks_for(dev["kind"])
    import ceph_tpu  # noqa: F401  (turns x64 on before any computation)
    from ceph_tpu.utils.jaxenv import enable_compile_cache
    cache_dir = enable_compile_cache()

    trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    s = Session(T_START, args.seed, args.seconds, bool(args.trace),
                cell, config, mix, dev, trace_dir, guard)
    driver = importlib.import_module("benchmark.drivers." + mix["driver"])
    driver.run(s)

    line = {"correct": s.correct, "attempted": s.attempted,
            "failed": s.failed}
    if args.trace:
        reduced = trace.reduce(trace.load(trace_dir))
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        line["metrics"] = per_layer(bench, cell["name"], {
            "facts": s.facts, "trace": reduced, "peaks": chip_peaks,
            "config": config, "mix": mix})
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    else:
        line["metrics"] = {
            m["name"]: {"value": s.end_to_end[m["name"]], "unit": m["unit"]}
            for m in bench["end_to_end"] if applies(m, cell["name"])}
    line["device"] = dev
    line["compile_cache"] = cache_dir
    line["facts"] = {k: v for k, v in s.facts.items()
                     if isinstance(v, (int, float, str, bool, dict))}
    line["compared"] = s.compared
    sys.stdout.flush()
    for name, c in s.compared.items():
        print("compared %s = %r (sound: %s %r)"
              % (name, c["value"], c["relation"], c["limit"]),
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(guard=True))
