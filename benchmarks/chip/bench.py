"""The chip benchmark: one run of one cell.

    python3 benchmarks/chip/bench.py --workload olmo1b.chat_shared \
        --seed 7 --seconds 51 --trace 0

Run from the root of a checkout.  The cell, its configuration, its
traffic mix and its metrics are found by name from ``BENCHMARK.json``
(see ``chipbench/spec.py``).  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics
with ``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``,
and last the ``checks`` that decided ``correct``, each number beside
its limit; the same checks are the last lines of standard error.

Exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the program under test is not
in the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def init_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), for every
    program however quick to compile, so that only a cell's first run
    in a checkout compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    Path(path).mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro.serving.engine  # noqa: F401
    except ImportError as e:
        log(f"bench: the program under test is not in this checkout: {e}")
        return 2
    from chipbench import cell as cell_mod
    from chipbench import spec
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    c = spec.cell(bench, args.workload, ROOT)
    init_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < c.chips:
        log(f"bench: needs {c.chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
        return 1
    out = cell_mod.run_cell(c, args.seed, args.seconds, bool(args.trace),
                            T_START, log=log)
    for name, ck in out["checks"].items():
        log(f"check {name}: {ck['value']} (limit {ck['rule']} "
            f"{ck['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
