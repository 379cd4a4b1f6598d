#!/usr/bin/env python3
"""Read a cell's control at its own size on the chip, over several seeds.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 [--seconds 20]

The control is what the comparison that decides ``correct`` must refuse.
A traffic kind names it with ``CONTROL``:

* ``"reference"``: the configuration's reference with one guarantee
  broken takes the program's place (``control_checks(ctx)``), as for a
  join with no lower-precision path of its own;
* ``"program"``: the program with its own lower-precision path switched
  on runs a short window of the cell through the whole harness
  (``ControlCell``).

Prints one line per seed with each number compared and the limit the
benchmark holds it to, and exits 1 unless every seed fails some check.
Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402


def program_control(spec, seed: int, seconds: float, root: str,
                    require_tpu: bool) -> list[tuple[str, int]]:
    spec.kind.Cell = spec.kind.ControlCell
    load_cell, run.load_cell = run.load_cell, lambda name, root: spec
    try:
        line = json.loads(run.run(
            ["--workload", spec.name, "--seed", str(seed), "--seconds",
             str(seconds), "--trace", "0"], root=root,
            require_tpu=require_tpu))
    finally:
        run.load_cell = load_cell
    return [(n, c["value"]) for n, c in line["checks"].items()]


def reference_control(spec, seed: int, root: str,
                      require_tpu: bool) -> list[tuple[str, int]]:
    run.import_program(root)
    import jax

    chips = int(spec.workload["chips"])
    devices = (run.require_chips(jax, chips) if require_tpu
               else jax.devices()[:chips])
    ctx = run.Context(spec.name, seed, spec.mix["params"], spec.config,
                      spec.reference, devices, root)
    return spec.kind.control_checks(ctx)


def main(argv=None, *, root: str = BENCH, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    all_fail = True
    for seed in (int(s) for s in args.seeds.split(",")):
        spec = run.load_cell(args.workload, root)
        if spec.kind.CONTROL == "program":
            got = program_control(spec, seed, args.seconds, root,
                                  require_tpu)
        else:
            got = reference_control(spec, seed, root, require_tpu)
        limits = spec.kind.LIMITS
        fails = [n for n, v in got if v > limits[n]]
        all_fail &= bool(fails)
        print(f"control {args.workload} seed={seed}: " + " ".join(
            f"{n}={v} (limit {limits[n]})" for n, v in got)
            + f" fails={fails}", flush=True)
    return 0 if all_fail else 1


if __name__ == "__main__":
    sys.exit(main())
