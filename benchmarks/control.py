"""Read the numbers a limit is set from: the program's over many seeds and
the control's, at the cell's own size, on the chip, in one process.

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 1,2] [--control bfloat16,float8] [--seconds 6]

For every seed it drives a run as ``run.py`` does (set-up, a window of
``--seconds``, the reference) and prints each number compared; for the
control seeds it also puts the reference, computed in the config's
``precision.control`` (or each of ``--control``), in the program's place. The
benchmark's own runs never come here. Prints one JSON object at the end:
per number the largest sound reading and the smallest control reading.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", default=None)
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}

    sound, low = {}, {}
    for seed in seeds:
        cell, runner, ctx = run.prepare(args.workload, seed, args.seconds)
        control = (args.control or cell.config["precision"]["control"]).split(",")
        job = runner.setup(ctx)
        samples = job.window(args.seconds)
        job.release()
        checks = job.check(samples, control if seed in control_seeds else None)
        for c in checks:
            run.say(f"seed {seed} {c.name}: value={c.value!r} limit={c.limit!r} "
                    f"{'ok' if c.ok else 'NOT OK'}")
            book, name = (low, c.name[8:]) if c.name.startswith("control.") \
                else (sound, c.name)
            book.setdefault(name, []).append(c.value)
    print(json.dumps({
        "workload": args.workload, "control": control,
        "seeds": seeds, "control_seeds": sorted(control_seeds),
        "sound_largest": {k: max(v) for k, v in sound.items()},
        "sound_all": sound,
        "control_smallest": {k: min(v) for k, v in low.items()},
        "control_all": low,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
