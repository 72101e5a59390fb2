#!/usr/bin/env python3
"""That `correct` can come out false. Run by hand (it starts three children a
run, so it is no part of tests/ or of selftest.py):

    python3 benchmark/broken_path_test.py [cell ...]

For each cell (default: every cell of BENCHMARK.json) it drives run.py's
whole run at the configuration's rehearsal sizes on the CPU, which skips only
the harness's look for a chip, three times:

    sound     nothing planted: the reference agrees (exit code 4)
    token     `--break token`: worker_entry.py alters the second token of
              every block the engine emits, where it is produced; the
              reference has to disagree (exit code 1, `reference_agrees` false)
    int8      `--control int8`: the reference itself judged in the program's
              place with its matrices rounded to int8, the nearest precision
              below the bf16 every configuration states (reference.py). On
              the chip, at the cell's own size, it has to fail a limit
              (PERF.md section 4 has the readings). At rehearsal widths the
              float32 program reads some 0.003 and no limit made for bf16 can
              separate, so here the control's mean log-probability gap has to
              read at least twice the program's: the control is computed, is
              judged by the same code, and lies on the far side. (`--control
              bf16`, the reference at the TPU's default matmul precision, is
              a chip check alone: on the CPU that precision is float32.)

The other faults the contract lists do not exist in a cell of one chip that
serves: no training step whose state could stay unchanged, no batch mean, no
exchange between chips. Exit code 0 when every assertion held.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def run(cell: str, *extra: str):
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--rehearsal",
         "--workload", cell, "--seed", "2147483777", *extra],
        cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, timeout=900)
    lines = p.stdout.decode().strip().splitlines()
    return p.returncode, json.loads(lines[-1]) if lines else {}


def main(cells: list) -> int:
    if not cells:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            cells = [w["name"] for w in json.load(f)["workloads"]]
    for cell in cells:
        rc, line = run(cell)
        assert rc == 4 and line["reference_agrees"], (cell, "sound", rc, line)
        print("ok", cell, "sound", json.dumps(line["checked"]), flush=True)
        rc, line = run(cell, "--break", "token")
        assert rc == 1 and line["reference_agrees"] is False, (cell, "token", rc, line)
        assert line["correct"] is False and line["why_not_correct"], (cell, line)
        outside, allowed = line["checked"]["positions_outside"]
        assert outside > allowed, (cell, "the altered tokens were not what failed", line)
        print("ok", cell, "token altered: not correct", json.dumps(line["checked"]),
              flush=True)
        rc, line = run(cell, "--control", "int8")
        # the family's mean log-probability gap: the worst request's (dense)
        # or the one pooled over the run's kept positions (routed)
        mean = next(k for k in line["checked"] if k.endswith("mean_sigmas"))
        control = line["controls"]["int8"]["checked"]
        ours, low = line["checked"][mean][0], control[mean][0]
        assert rc == 4 and low > 2 * ours, (cell, "int8", rc, ours, low)
        print("ok", cell, f"int8 control reads {low:.4f}, the program {ours:.4f}",
              json.dumps(control), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
