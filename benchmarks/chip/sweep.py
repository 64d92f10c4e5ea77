"""Find a cell's knee once: serve its traffic mix at several fixed rates
in one process (one set-up).  At each rate the load runs the mix's
``preroll_s`` first, so that the measured window starts in the steady
state, then ``--seconds`` of window; the next rate follows at once,
and one drain ends the sweep.  Printed per rate:
output tokens/s (in the steady state below the knee, the rate times the
mean output), time to first token, and the requests in the system and
those still waiting for a first token at the window's start and end.
The knee is the highest rate at which the waiting requests do not grow
through the window; the cell's mix is then set below it.

    python3 benchmarks/chip/sweep.py --workload olmo1b.chat_shared \
        --seed 11 --seconds 51 --rates 0.4,0.5,0.6,0.7
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent


def in_system(plan, t: float) -> int:
    """Requests submitted by ``t`` and not finished by then."""
    return sum(1 for p in plan if p.handle is not None and p.submitted <= t
               and not (p.handle.done and p.token_times
                        and p.token_times[-1] <= t))


def waiting(plan, t: float) -> int:
    """Requests submitted by ``t`` with no output token by then."""
    return sum(1 for p in plan if p.handle is not None and p.submitted <= t
               and not (p.token_times and p.token_times[0] <= t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    import bench
    from chipbench import spec, stats
    from chipbench import traffic as traffic_mod
    from chipbench.sut import (LoadGen, build_engine, new_tracer,
                               program_config, weight_generator)
    from chipbench.weights import base_key
    c = spec.cell(json.loads((ROOT / "BENCHMARK.json").read_text()),
                  args.workload, ROOT)
    bench.init_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 1
    cfg, eng_cfg = c.config, c.config["engine"]
    pc = program_config(cfg)
    params = weight_generator(cfg, pc)(base_key(args.seed))
    tracer, clock = new_tracer()
    engine = build_engine(pc, params, eng_cfg, tracer)
    loadgen = LoadGen(engine, tracer, clock)
    for wave, live in traffic_mod.warmup(c.traffic, args.seed,
                                        cfg["vocab_size"],
                                        eng_cfg["chunk_size"]):
        loadgen.serve_wave(wave, live_second=live)
    closed = traffic_mod.concurrency(c.traffic, eng_cfg["b_local"])
    preroll = float(c.traffic.get("preroll_s", 0))
    every = []                    # requests of this rate and the earlier
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        block = int(c.traffic.get("block", 1))
        mix = dict(c.traffic, rate_per_s=rate, requests=block * math.ceil(
            rate * (preroll + args.seconds) * 1.2 / block))
        plan = traffic_mod.plan(mix, args.seed + i, cfg["vocab_size"])
        for p in plan:            # the last rate's requests may still run
            p.rid += 100_000 * i
        every += plan
        pending = loadgen.offer(plan, closed)
        loadgen.serve_until(pending, closed, time.perf_counter() + preroll)
        t0 = time.perf_counter()
        end = t0 + args.seconds
        loadgen.serve_until(pending, closed, end)
        ttft = stats.ttft_samples(plan, t0, end)
        row = {"rate_per_s": rate,
               "due": sum(1 for p in plan if t0 <= p.due < end),
               "output_tok_s": stats.tokens_in(every, t0, end)
               / (end - t0),
               "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
               "ttft_p90_ms": 1e3 * stats.percentile(ttft, 90),
               "itl_p95_ms": 1e3 * (stats.percentile(
                   stats.itl_samples(plan, t0, end), 95) or 0),
               "in_system": [in_system(every, t) for t in (t0, end)],
               "waiting": [waiting(every, t) for t in (t0, end)],
               "active_at_end": len(engine.active)}
        print(json.dumps(row), flush=True)
    t = time.perf_counter()
    drained = loadgen.drain(300.0)
    print(json.dumps({"drain_s": time.perf_counter() - t,
                      "drained": drained}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
