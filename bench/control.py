"""The control: runs of a cell, in one process, whose comparison also
puts lower-precision copies of the reference in the program's place.

    python3 -m bench.control --workload <name> --seeds 11,12,13 --seconds <s>

For each seed it runs the cell as ``bench.run`` does (set-up, ramp, window
at the cell's own load) and prints one JSON line with the program's
readings and each control's (``int8`` and ``fp8`` matrix products; see
``bench.reference``), and each control's verdict against the cell's
limits (``check.verdict``): a control has to come out not correct.  The limits in ``bench/cells/<workload>.json`` lie
between the largest program reading over a dozen seeds or more and the
smallest reading of the cell's control kind.  The benchmark's own runs do
not run this.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from bench import check, spec  # noqa: E402


def main(argv=None) -> int:
    from bench import reference, run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    cell = spec.resolve(args.workload)
    run.enable_compile_cache(spec.ROOT)
    t0 = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            res = run.run(cell, seed, args.seconds, False,
                          kinds=reference.KINDS[1:], t_start=t0)
        except run.NoChip as e:
            print(f"bench.control: {e}", file=sys.stderr)
            return 2
        limits = cell.cell["check"]["limits"]
        controls = {k: check.verdict(v, limits)
                    for k, v in res["readings"]["control"].items()}
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": res["correct"],
                          "setup_s": res["metrics"]["setup_s"]["value"],
                          "readings": res["readings"],
                          "control_verdicts": controls}), flush=True)
        del res
        gc.collect()
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
