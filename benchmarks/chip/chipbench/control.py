"""The control of each cell's comparison: the plain reference put in the
program's place and computed in the precision below the one its
configuration states.  Its answers must be judged not correct by the
same comparison that judges the program.  Each reference module makes
its own control's answers (``control_answers(cell, seed)``), so a new
configuration brings its control in its own reference file.

    python benchmarks/chip/chipbench/control.py --workload <cell> \
        --seeds <n> [<n> ...] [--steps N]

prints one JSON line per seed with the comparison's numbers beside their
limits.  ``--steps`` overrides the traffic's ``span_steps`` (the replay
steps a PIC control computes).  The benchmark's own runs never run the
control.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import harness  # noqa: E402


def run_control(cell, seed):
    """``(correct, checks)`` of the control's answers for one seed: the
    cell's reference module makes them (``control_answers``) and judges
    them (``judge``), as it judges the program's."""
    reference = harness.load_module(
        harness.BENCH_DIR / "references" / f"{cell.config['reference']}.py")
    answers = reference.control_answers(cell, seed)
    checks, _ = reference.judge(cell.config, cell.traffic, seed, answers)
    return all(v <= lim for v, lim in checks.values()), checks


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--steps", type=int, default=None)
    args = ap.parse_args(argv)
    cell = harness.Cell.load(
        args.workload,
        traffic_overrides={"span_steps": args.steps} if args.steps else None)
    import jax

    dev = jax.devices()[0]
    for seed in args.seeds:
        t = time.perf_counter()
        ok, checks = run_control(cell, seed)
        print(json.dumps({
            "control": args.workload, "seed": seed, "correct": ok,
            "device": dev.device_kind, "seconds": time.perf_counter() - t,
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in checks.items()}}), flush=True)


if __name__ == "__main__":
    main()
