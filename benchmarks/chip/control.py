"""The readings a cell's ``served_gap_max`` limit is set from, on the
chip at the cell's own size: for each seed, one run of the cell's
traffic through the program with a short window, then the served
tokens' widest gap below the float32 reference's best logit (the
program's reading) and, at each position of the same prompts and
tokens, the gap of the token that the control puts first (the control's
reading: the reference in the precision the configuration names under
``limits.control``).  All seeds in one process, one JSON line each.
The control's gap is one more check against the same limit, so
``correct`` reads false where the control fails it, as it has to;
``program_correct`` is the verdict on the program's run alone.

    python3 benchmarks/chip/control.py --workload olmo1b.chat_shared \
        --seconds 20 --seeds 5,6,7
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    import bench
    from chipbench import cell as cell_mod
    from chipbench import spec
    c = spec.cell(json.loads((ROOT / "BENCHMARK.json").read_text()),
                  args.workload, ROOT)
    bench.init_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = cell_mod.run_cell(c, seed, args.seconds, False, t,
                                control=True, log=bench.log)
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "program_correct": all(
                ck["ok"] for name, ck in out["checks"].items()
                if name != "control_gap_max"),
            "served_gap_max": out["checks"]["served_gap_max"]["value"],
            "control_gap_max": out["checks"]["control_gap_max"]["value"],
            "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
