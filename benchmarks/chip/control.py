#!/usr/bin/env python3
"""Read a cell's compared numbers for the program and for its control.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...] [--fault <name>]

For each seed, in one process: the cell's set-up, a window of ``--seconds``
at the cell's own load, then the numbers that decide ``correct``, read
twice on the same requests: once for the program (the sound reading) and
once for the control, the plain reference put in the program's place with
one step less: computed in bfloat16 for the float32 tuner cells, and, for
the engine cells, a key-value model that loses every hundredth loaded
record and the acknowledged updates of every second request.  Prints one
JSON line per seed and, last, the largest sound and the smallest control
reading of each number.

With ``--fault <name>`` one of the faults of the cell's kind (its
``faults(traffic)``) is planted in the program first, and only the
program's readings are taken: each seed's line holds the fault's reading
of each number, and the last line the smallest.

The limits in the traffic files are set from these readings; the
benchmark's own runs do not run the control.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (HERE, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as bench  # noqa: E402
from chipbench import generator  # noqa: E402


def readings(spec: dict, seed: int, seconds: float, need_chip: bool = True,
             control: bool = True) -> dict:
    """Sound and control readings of one seed, as {number: (sound,
    control)}; the control's is None where ``control`` is false."""
    if need_chip:
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
        bench.require_chips(int(spec["cell"]["chips"]))
    import ml_dtypes
    gen = generator.kind(spec["traffic"]["generator"])(
        spec["config"], spec["traffic"], seed)
    gen.setup()
    if hasattr(gen, "control_model"):
        gen.control_model()
    bench.run_window(gen, seconds, lambda _: contextlib.nullcontext())
    sound = gen.checks()
    if not control:
        return {n: (v, None) for n, v, _ in sound}
    low = gen.control_checks(ml_dtypes.bfloat16)
    return {n: (v, c) for (n, v, _), (_, c, _) in zip(sound, low)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", help="one of the faults the cell's kind "
                    "names (its ``faults(traffic)``)")
    args = ap.parse_args(argv)
    spec = bench.load_cell(args.workload)
    if args.fault:
        kind = generator.kind(spec["traffic"]["generator"])
        planted = kind.faults(spec["traffic"])
        if args.fault not in planted:
            ap.error(f"--fault {args.fault}: {args.workload} can have "
                     f"{sorted(planted)}")
        planted[args.fault](types.SimpleNamespace(setattr=setattr))
    try:
        rows = []
        for seed in args.seeds:
            r = readings(spec, seed, args.seconds,
                         control=args.fault is None)
            rows.append(r)
            print(json.dumps({"seed": seed, "readings": r}), flush=True)
    except bench.NoChip as e:
        bench.say(f"control.py: {e}")
        return 2
    if args.fault:
        summary = {n: {"fault_min": min(r[n][0] for r in rows)}
                   for n in rows[0]}
    else:
        summary = {n: {"sound_max": max(r[n][0] for r in rows),
                       "control_min": min(r[n][1] for r in rows)}
                   for n in rows[0]}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
