#!/usr/bin/env python3
"""Make several runs in one call to the chip, one process each, one after
another, and keep every result line.  Never imports JAX: the chip belongs
to the run's process.

    python3 benchmark/tests/chip_runs.py out.jsonl <cell>:<seed>:<seconds>:<trace>[:<fault>] ...

Each line of out.jsonl is the run's result line with `cell`, `seed`,
`seconds`, `trace`, `fault`, `rc` and `wall_s` added; a run with a fault goes
through control.py and has to read `correct: false`.
"""

import json
import os
import subprocess
import sys
import time

LIMIT_S = 420      # a run may take 360 s; the first of a checkout, more
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(out: str, specs: list) -> int:
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    for spec in specs:
        cell, seed, seconds, traced, *fault = spec.split(":")
        entry = (["benchmark/tests/control.py", fault[0]] if fault
                 else ["benchmark/run.py"])
        t0 = time.monotonic()
        try:
            p = subprocess.run(
                [sys.executable, *entry, "--workload", cell, "--seed", seed,
                 "--seconds", seconds, "--trace", traced],
                cwd=ROOT, capture_output=True, text=True, timeout=LIMIT_S)
            said, err, rc = p.stdout, p.stderr, p.returncode
        except subprocess.TimeoutExpired as e:
            said, err, rc = "", (e.stderr or b"").decode(errors="replace"), 124
        try:
            line = json.loads(said.strip().splitlines()[-1])
        except (IndexError, ValueError):
            line = {"stderr": err[-3000:]}
        line.update(cell=cell, seed=int(seed), seconds=float(seconds),
                    trace=int(traced), fault=fault[0] if fault else None,
                    rc=rc, wall_s=round(time.monotonic() - t0, 1))
        with open(out, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps({k: line.get(k) for k in (
            "cell", "seed", "trace", "fault", "rc", "wall_s", "correct",
            "attempted", "failed", "metrics")}), flush=True)
        if not line.get("correct") and not fault:
            print(err[-3000:], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2:]))
