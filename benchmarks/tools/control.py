"""The control of `correct`: the reference put in the program's place and
computed in lower precision has to come out as NOT correct.

    python3 benchmarks/tools/control.py --workload tpch10_light --seeds 1,2,3

The configurations state a guarantee, not a precision: every answer exact.
The step that would tempt a later PR is a device sum in float32 without the
exact limb arithmetic; where float32 holds every value of a class exactly
(the ranked classes of the heavy mix), it is keys or sums in bfloat16. So for each class of the cell whose oracle has
`control_rows`, this builds the cell's data from the seed at the cell's own
size, renders (a) the exact reference and (b) the lower-precision one as wire
rows, and gives both to the class's `compare`: (a) has to pass, (b) has to
fail, on every seed. numpy on the host: no chip is touched.
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def run(workload: str, seeds: list[int], scale: float) -> bool:
    from benchmarks.datagen import tpch
    from benchmarks.harness import manifest

    cell = manifest.load_cell(manifest.load_manifest(ROOT), workload)
    sf = cell["config"]["lineitem_scale_factor"] * scale
    ok = True
    for seed in seeds:
        data = {"lineitem": tpch.generate_lineitem(sf, seed)["columns"]}
        n = len(data["lineitem"]["l_orderkey"])
        failed_some = False
        for cls, st in cell["classes"].items():
            mod = importlib.import_module(f"benchmarks.oracles.{st['oracle']}")
            if not hasattr(mod, "control_rows"):
                continue
            ref = mod.reference(data)
            exact = mod.compare(
                mod.render_exact(data, ref) if hasattr(mod, "render_exact")
                else mod.render(ref[0]), ref)
            low = mod.compare(mod.control_rows(data), ref)
            print(f"seed {seed} {cls}: exact reference -> "
                  f"{'agrees' if exact is None else 'WRONG: ' + exact}; "
                  f"{getattr(mod, 'CONTROL', 'float32')} control -> "
                  f"{'AGREES (no control)' if low is None else 'fails: ' + low[:160]}",
                  flush=True)
            ok = ok and exact is None
            failed_some = failed_some or low is not None
        print(f"seed {seed}: control {'fails' if failed_some else 'PASSES'} "
              f"the comparison of {workload} ({n} lineitem rows)", flush=True)
        ok = ok and failed_some
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="a test's size; the cell's own is 1")
    args = ap.parse_args(argv)
    ok = run(args.workload, [int(s) for s in args.seeds.split(",")],
             args.scale)
    print("control: as it has to be" if ok else "control: FAULT")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
