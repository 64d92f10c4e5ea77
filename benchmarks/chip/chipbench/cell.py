"""One run of one cell: set-up, the measured window, the drain, the
checks that decide ``correct``, and the metrics."""

from __future__ import annotations

import dataclasses
import gc
import shutil
import tempfile
import time
from contextlib import contextmanager, nullcontext
from pathlib import Path
from typing import List, Optional

import numpy as np

from . import peaks as peaks_mod
from . import spec as spec_mod
from . import traffic as traffic_mod
from . import work, xplane
from .sut import (LoadGen, TreeView, build_engine, new_tracer,
                  program_config, weight_generator)
from .weights import base_key

#: seconds the drain may take after the window before an answer that
#: never came counts against ``correct``
DRAIN_LIMIT_S = 150.0


@dataclasses.dataclass
class Run:
    """What one run observed; the metric readers read this."""
    cell: spec_mod.Cell
    dims: work.Dims
    peak_flops: float
    peak_bw: float
    setup_s: float
    t0: float
    end: float
    steps: list                  # StepRec of the window's steps
    reqs: list                   # Planned requests of the window's plan
    ctr0: dict                   # engine counters at the window's start
    ctr1: dict                   # ... and at its end
    events: list                 # program trace events in the window,
    #                              with "t" on the harness's clock
    trace: Optional[xplane.Trace] = None
    device: Optional[str] = None  # traced device plane
    lo: float = 0.0              # the window on the trace's clock (ns)
    hi: float = 0.0

    @property
    def seconds(self) -> float:
        return self.end - self.t0

    def delta(self, name: str) -> int:
        return self.ctr1.get(name, 0) - self.ctr0.get(name, 0)


class CompileCounter:
    """Compilation events JAX reports, with the host time of each."""

    def __init__(self):
        import jax.monitoring
        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.times.append(time.perf_counter())

    def between(self, a: float, b: float) -> int:
        return sum(1 for t in self.times if a <= t <= b)


def check(name: str, value, limit, rule: str) -> dict:
    ok = {"<=": value <= limit, ">=": value >= limit,
          "==": value == limit}[rule]
    return {"name": name, "value": value, "limit": limit, "rule": rule,
            "ok": bool(ok)}


def sample(reqs, seed: int, tokens: int) -> list:
    """Finished requests to compare, drawn from the seed: the one with
    the longest sequence, then others until ``tokens`` served tokens."""
    done = [p for p in reqs if p.handle is not None and p.handle.done
            and p.handle.out_tokens]
    if not done:
        return []
    longest = max(done, key=lambda p: len(p.prompt) + len(p.handle.out_tokens))
    rest = [p for p in done if p is not longest]
    order = traffic_mod.rng(seed, 4).permutation(len(rest))
    out, n = [longest], len(longest.handle.out_tokens)
    for i in order:
        if n >= tokens:
            break
        out.append(rest[i])
        n += len(rest[i].handle.out_tokens)
    return out


def compare(cell: spec_mod.Cell, gen, seed: int, seqs,
            control: Optional[str] = None):
    """Teacher-force the plain reference on each (prompt, served tokens)
    and return the per-token gaps ``best - logit[served]``, the number
    of served tokens that are the reference's argmax, and with
    ``control`` (a lower precision) the control's gaps: at the same
    positions, the gap of the token the reference computed in that
    precision puts first, read on the float32 logits."""
    import jax.numpy as jnp
    ref = spec_mod.reference(cell)
    cfg = cell.config
    view = TreeView(gen(base_key(seed)), cfg)
    longest = max(len(p) + len(o) for p, o in seqs)
    S = -(-longest // 256) * 256
    tokens = np.zeros((len(seqs), S), np.int32)
    rows, cols, served = [], [], []
    for i, (prompt, out) in enumerate(seqs):
        s = list(prompt) + list(out)
        tokens[i, :len(s)] = s
        for j, t in enumerate(out):
            rows.append(i)
            cols.append(len(prompt) - 1 + j)
            served.append(t)
    rows, cols = jnp.asarray(rows), jnp.asarray(cols)
    h = ref.hidden(cfg, view, jnp.asarray(tokens), rows, cols)
    gaps, best = ref.logits_gap(view, h, jnp.asarray(served, jnp.int32))
    gaps = np.asarray(gaps)
    agree = int((np.asarray(best) == np.asarray(served)).sum())
    ctl = None
    if control:
        hq = ref.hidden(cfg, view, jnp.asarray(tokens), rows, cols,
                        quant=control)
        pick = ref.argmax(view, hq, quant=control)
        ctl = np.asarray(ref.logits_gap(view, h, pick)[0])
    return gaps, agree, ctl


@contextmanager
def _annotations(on: bool):
    if not on:
        yield None
        return
    import jax
    yield lambda name: jax.profiler.TraceAnnotation(name)


def run_cell(cell: spec_mod.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True,
             control: bool = False, log=print) -> dict:
    import jax

    devs = jax.devices()
    kind = devs[0].device_kind
    if require_chip:
        if devs[0].platform != "tpu":
            raise SystemExit(f"no TPU: JAX found {devs[0].platform}")
        if len(devs) < cell.chips:
            raise SystemExit(f"{cell.chips} chips asked, {len(devs)} found")
        peak_flops, peak_bw = peaks_mod.peaks(kind)
    else:
        peak_flops, peak_bw = peaks_mod.peaks("TPU v5 lite")
    cfg, mix, eng_cfg = cell.config, cell.traffic, cell.config["engine"]
    dims = work.dims(cfg)
    compiles = CompileCounter()

    # ------------------------------------------------------------ set-up
    pc = program_config(cfg)
    gen = weight_generator(cfg, pc)
    params = jax.block_until_ready(gen(base_key(seed)))
    tracer, clock = new_tracer()
    engine = build_engine(pc, params, eng_cfg, tracer)
    del params
    with _annotations(trace) as span:
        loadgen = LoadGen(engine, tracer, clock, span=span)
        for wave, live in traffic_mod.warmup(mix, seed, cfg["vocab_size"],
                                            eng_cfg["chunk_size"]):
            loadgen.serve_wave(wave, live_second=live)
        plan = traffic_mod.plan(mix, seed, cfg["vocab_size"])
        closed = traffic_mod.concurrency(mix, eng_cfg["b_local"])
        setup_s = time.perf_counter() - t_start

        # ----------------------------------------- the load, then the window
        start = time.perf_counter()
        pending = loadgen.offer(plan, closed)
        # the load runs ``preroll_s`` before the window opens, so that
        # the window sees it in its steady state and not from idle
        loadgen.serve_until(pending, closed,
                            start + float(mix.get("preroll_s", 0)))
        if trace:
            tdir = tempfile.mkdtemp(prefix="chipbench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        ctr0 = dict(engine.stats)
        n_ev0 = len(tracer.events)
        with span("window") if trace else nullcontext():
            t0 = time.perf_counter()
            end = t0 + seconds
            loadgen.serve_until(pending, closed, end)
        ctr1 = dict(engine.stats)
        if trace:
            jax.profiler.stop_trace()
    window_steps = [s for s in loadgen.steps if t0 <= s.a < end]
    events = []
    for e in list(tracer.events)[n_ev0:]:
        t = clock.first + e["ts"] / 1e6
        if t0 <= t <= end:
            events.append(dict(e, t=t))
    log(f"compilations while the load ran: {compiles.between(start, end)}"
        f", inside the window: {compiles.between(t0, end)}")

    # ----------------------------------------------------------- drain
    drained = loadgen.drain(DRAIN_LIMIT_S)
    engine.flush_pins()
    checks = [
        check("unanswered", len(loadgen.outstanding), 0, "=="),
        check("pages_in_use_after_drain", int(engine.pages_in_use()), 0,
              "=="),
        check("never_dry_margin_min",
              int(engine.telemetry.never_dry_margin_min() or 0), 0, ">="),
        check("step_retries_recoveries_dumps",
              int(sum(engine.stats.get(k, 0) for k in
                      ("retries", "recoveries", "flight_dumps"))), 0, "=="),
        check("trace_events_dropped", int(tracer.dropped), 0, "=="),
    ]
    mem = devs[0].memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0)
                      + mem.get("peak_bytes_reserved", 0))
    submitted = [p for p in plan if p.handle is not None]
    failed = sum(1 for p in submitted if not p.handle.done)
    picked = sample(submitted, seed, int(mix.get("check_tokens", 256)))
    seqs = [(list(p.prompt), list(p.handle.out_tokens)) for p in picked]
    run = Run(cell, dims, peak_flops, peak_bw, setup_s, t0, end,
              window_steps, plan, ctr0, ctr1, events)
    del engine, loadgen, tracer
    for p in plan:
        p.handle = None
    gc.collect()

    # -------------------------------------------------------- the trace
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    if trace:
        path = next(Path(tdir).rglob("*.xplane.pb"))
        run.trace = xplane.load(str(path))
        run.device = run.trace.devices()[0]
        w = run.trace.span("window")
        run.lo, run.hi = w.start, w.end
        busy = xplane.busy_ns(run.trace.ops[run.device], run.lo, run.hi)
        device["busy_s"] = busy / 1e9
        device["window_s"] = (run.hi - run.lo) / 1e9
        shutil.rmtree(tdir, ignore_errors=True)

    # ----------------------------------------------------------- metrics
    wanted = cell.per_layer if trace else cell.end_to_end
    read = spec_mod.readers_for(cell, wanted)
    metrics = {}
    for m in wanted:
        v = read[m["name"]](run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # -------------------------------------------------------- reference
    limit = float(cfg["limits"]["served_gap_max"])
    ctl = None
    if seqs:
        gaps, agree, ctl = compare(
            cell, gen, seed, seqs,
            cfg["limits"]["control"] if control else None)
        n_tok = len(gaps)
        log(f"reference: {len(seqs)} requests, {n_tok} served tokens, "
            f"served token is the float32 argmax for {agree}/{n_tok}")
        checks.append(check("served_gap_max", float(gaps.max()), limit,
                            "<="))
        if ctl is not None:
            # the control in the program's place: it has to fail
            checks.append(check("control_gap_max", float(ctl.max()), limit,
                                "<="))
    else:
        checks.append(check("requests_compared", 0, 1, ">="))
    if not drained:
        log(f"drain did not end within {DRAIN_LIMIT_S}s")
    correct = all(c["ok"] for c in checks)
    out = {"correct": correct, "attempted": len(submitted),
           "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = breakdown(run)
    out["checks"] = {c["name"]: {k: c[k] for k in
                                 ("value", "limit", "rule", "ok")}
                     for c in checks}
    return out


def breakdown(run: Run) -> dict:
    """The device ops that took most time, and the longest idle gaps by
    the harness span that covers them."""
    ops = run.trace.ops[run.device]
    gaps = xplane.idle_gaps(ops, run.lo, run.hi)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "device_ops": [[k, v] for k, v in
                       xplane.top_ops(ops, run.lo, run.hi)],
        "idle_gaps": [[xplane.covering(run.trace.host, (a + b) / 2,
                                       ("submit", "step", "record",
                                        "sleep")), (b - a) / 1e9]
                      for a, b in gaps[:10]],
    }
