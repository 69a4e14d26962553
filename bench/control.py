"""Readings that set a cell's limit: for each seed, the number its check
compares, from the program and from the control (the reference in the
program's place, in fp8, the precision below the configuration's bf16).

    python bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

All seeds run in one process, one after the other, each a whole run of
the cell (set-up, window, check).  The benchmark's own runs never run the
control.  Prints one JSON line per seed.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHECKOUT / "src"))
sys.path.insert(0, str(CHECKOUT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import harness
    for seed in args.seeds:
        run = types.SimpleNamespace(seed=seed, seconds=args.seconds, trace=0)
        try:
            rec = harness.run_record(args.workload, run, time.perf_counter())
        except harness.NoChip as e:
            harness.log(str(e))
            return 3
        program = rec.checks[0]
        control = rec.recheck("fp8")
        print(json.dumps({"seed": seed, "check": program.name,
                          "program": program.value,
                          "control": control.value,
                          "limit": program.limit,
                          "attempted": rec.attempted, "failed": rec.failed}),
              flush=True)
        del rec
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
