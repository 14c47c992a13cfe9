"""Readings that set the limits of a training cell's comparison; the
benchmark's own runs never run this.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3

For each seed it runs the plain reference three ways, at the cell's own
size, on the accelerator: in float32 (the reference), in float8 with one
scale per tensor (the control: the reference in the program's place, one
precision step below the configuration's bfloat16), and in float32 with
a planted fault: half of the batch's tokens left out, or every step's
state returned unchanged.  It judges the
control's and the fault's numbers against the float32 reference with the
cell's limits, by the rule the benchmark applies to the program's
(``kinds/train.judge``): one JSON line per seed and variant, each number
beside its limit and ``correct``, which a control has to read false.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Dict, Iterable, Iterator

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import run as harness  # noqa: E402
from chipbench.kinds import train  # noqa: E402

VARIANTS = {"fp8": {"precision": "fp8"}, "half_batch": {"half_batch": True},
            "unchanged": {"unchanged": True}}


def readings(cell, seeds: Iterable[int], variants: Iterable[str],
             log=harness.log) -> Iterator[Dict]:
    """For each seed and variant, the numbers compared, their limits and
    whether the variant would pass as correct."""
    variants = list(variants)
    for seed in seeds:
        want = train.reference_numbers(cell, seed, log=log)
        for name in variants:
            got = train.reference_numbers(cell, seed, log=log,
                                          **VARIANTS[name])
            found = train.compare(got, want)
            checks, correct = train.judge(found, cell.limits, log=log)
            yield {"workload": cell.name, "seed": seed, "variant": name,
                   "correct": correct, "checks": checks,
                   "worst": {k: found[k][1] for k in checks}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default=",".join(VARIANTS))
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    peaks = harness.load_json(cell.bench_dir / "peaks.json")
    try:
        harness.find_devices(cell.workload["chips"], peaks)
    except harness.NoAccelerator as e:
        harness.log(f"control: {e}; nothing was run")
        return 3
    harness.setup_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    for row in readings(cell, seeds, args.variants.split(",")):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
