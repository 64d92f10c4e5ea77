"""Chip smoke: serve olmo-1b at its published widths on a TPU through the
normal serving entry point, and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the dp mesh plane, on four chips

One chip runs two phases, each through ``repro.launch.serve.main``:

1. prefix sharing: sixteen requests on eight slots behind a shared
   320-token prefix (chunked prefill; the second wave maps the first
   wave's shared and pinned pages; 32 decode steps each);
2. speculation: the same with ``--speculate --repeat-frac 0.5``, so the
   second wave drafts from the first wave's outputs and draft lanes
   run the verify kernel.

Each phase asserts that every request finished, that no page is in use
after the drain, that the never-dry margin stayed >= 0, that no step
was retried or dumped, that every compiled serve-step variant it ran
holds its Pallas kernel (``tpu_custom_call``), and that every served
greedy token is within ``LOGIT_TOL`` of the best logit of a plain
float32 forward of the same weights, teacher-forced on prompt plus
served tokens.

``--chips 4`` runs only the dp mesh plane: a dp=4 engine sharded over
the four chips and a dp=1 engine on one chip, on the same trace, both
in float32 at full matmul precision.  It asserts the engine holds a
mesh, the served tokens are identical, and each compiled step has
exactly one collective (the status all-gather).

Lines before the last are smoke figures from this run (compile seconds,
tokens/s, peak device memory), not benchmark numbers.  The last line is
one JSON object naming the device.  With no TPU the script exits
non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out"

#: tolerance on (best reference logit - reference logit of the served
#: token).  The served path keeps activations in bf16; at olmo-1b widths
#: its logits stay within ~0.1 of float32 (random weights, logits of
#: unit spread), while a token drawn from outside the top few of the
#: 50304 sits several logits below the best.
LOGIT_TOL = 0.25

SERVE = ["--arch", "olmo-1b", "--max-len", "1024", "--hot-prefix", "320",
         "--max-new", "32", "--pin-pages", "16"]


def figure(msg: str) -> None:
    print(f"[smoke figure] {msg}", flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, summed from
    its monitoring events (cache hits included: they skip the compile)."""

    def __init__(self, monitoring):
        self.secs = 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.secs += secs


def step_kernels(hlo: str) -> set:
    """Pallas kernels called by a compiled step, by their jit names."""
    found = set()
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            found |= {k for k in ("paged_attention_chunk", "verify_attention")
                      if f"jit({k})" in line}
    return found


def serve(argv, clock, label):
    """One run of the serving entry point; returns (engine, requests)."""
    from repro.launch import serve as serve_mod
    OUT.mkdir(exist_ok=True)
    argv = argv + ["--flight-recorder", str(OUT / f"flight_{label}.json")]
    c0, t0 = clock.secs, time.perf_counter()
    engine, reqs = serve_mod.main(argv)
    wall = time.perf_counter() - t0
    compile_s = clock.secs - c0
    toks = engine.stats["tokens_out"]
    figure(f"{label}: {len(reqs)} requests, {toks} tokens, "
           f"{engine.stats['steps']} steps, wall {wall:.2f}s, "
           f"compile {compile_s:.2f}s, "
           f"{toks / max(wall - compile_s, 1e-9):.1f} tok/s past compile")
    return engine, reqs


def check_served(engine, reqs, max_new: int) -> None:
    s = engine.stats
    assert all(r.done and len(r.out_tokens) == max_new for r in reqs), (
        "a request did not finish with its full output")
    assert engine.page_occupancy() == 0.0, "pages in use after the drain"
    margin = engine.telemetry.never_dry_margin_min()
    assert margin is not None and margin >= 0, f"never-dry margin {margin}"
    for key in ("flight_dumps", "retries", "recoveries"):
        assert s[key] == 0, f"{key} = {s[key]}: a step failed"


def check_kernels(engine) -> dict:
    """Every serve-step variant the engine ran holds its Pallas kernel;
    returns each variant's compiled HLO text."""
    texts = {}
    for T, spec, sampler in sorted(engine.step_variants):
        hlo = engine.compiled_step_text(T, spec=spec, sampler=sampler)
        want = "verify_attention" if spec else "paged_attention_chunk"
        found = step_kernels(hlo)
        assert want in found, f"T={T} spec={spec}: {want} not in {found}"
        print(f"serve step T={T} spec={spec}: kernels {sorted(found)}")
        texts[T, spec, sampler] = hlo
    return texts


def check_reference(jax, engine, reqs, max_new: int) -> None:
    """Served greedy tokens vs a float32 forward of the same weights."""
    import jax.numpy as jnp
    import numpy as np
    from repro.models.layers import logits_apply
    from repro.models.transformer import forward_train

    cfg = dataclasses.replace(engine.cfg, dtype="float32")
    params = jax.tree.map(lambda a: a.astype(jnp.float32), engine.params)
    seqs = [list(r.prompt) + list(r.out_tokens) for r in reqs]
    toks = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    # the logit at position len(prompt) - 1 + i predicts output token i
    pos = np.asarray([[len(r.prompt) - 1 + i for i in range(max_new)]
                      for r in reqs], np.int32)
    served = np.asarray([r.out_tokens for r in reqs], np.int32)

    @jax.jit
    def margins(params, toks, pos, served):
        h = forward_train(cfg, params, toks, remat=False)
        h = jnp.take_along_axis(h, pos[..., None], axis=1)
        logits = logits_apply(cfg, params["embed"], h)       # [B, n, V]
        got = jnp.take_along_axis(logits, served[..., None], -1)[..., 0]
        return logits.max(-1) - got, jnp.argmax(logits, -1) == served

    with jax.default_matmul_precision("highest"):
        m, exact = margins(params, toks, pos, served)
    m, exact = np.asarray(m), np.asarray(exact)
    print(f"float32 reference: worst margin {float(m.max()):.6f} "
          f"(tolerance {LOGIT_TOL}), served token is the reference argmax "
          f"for {int(exact.sum())}/{exact.size}")
    assert m.max() <= LOGIT_TOL, (
        f"served token {float(m.max())} below the best float32 logit")


def one_chip(jax, clock) -> None:
    phases = [
        ("prefix", SERVE + ["--dp", "1", "--b-local", "8",
                            "--requests", "16"]),
        ("speculate", SERVE + ["--dp", "1", "--b-local", "8",
                               "--requests", "16", "--speculate",
                               "--repeat-frac", "0.5"]),
    ]
    kernels = set()
    for label, argv in phases:
        engine, reqs = serve(argv, clock, label)
        check_served(engine, reqs, 32)
        for hlo in check_kernels(engine).values():
            kernels |= step_kernels(hlo)
        if label == "prefix":
            assert engine.stats["prefix_shared_reqs"] > 0, "nothing shared"
        else:
            assert engine.stats["spec_lanes"] > 0, "no draft lane ran"
        check_reference(jax, engine, reqs, 32)
        mem = jax.devices()[0].memory_stats() or {}
        figure(f"{label}: peak device memory "
               f"{mem.get('peak_bytes_in_use', 'not reported')} B")
        del engine, reqs
        gc.collect()
    assert kernels == {"paged_attention_chunk", "verify_attention"}, kernels


def four_chips(jax, clock) -> None:
    from repro.serving.engine import step_collectives
    # float32 at full matmul precision: the dp=4 and dp=1 programs differ
    # in per-device batch shape, so the compiler may order reductions
    # differently.  In bf16 that noise (the ~0.03-logit margins the
    # one-chip reference check reports) flips near-tied greedy picks
    # among 50304 logits, and the streams part from there; in float32
    # at full precision it sits orders of magnitude below such ties.
    exact = ["--dtype", "float32"]
    with jax.default_matmul_precision("highest"):
        eng4, reqs4 = serve(SERVE + exact + ["--dp", "4", "--b-local", "2",
                                             "--requests", "16"],
                            clock, "dp4_mesh")
        check_served(eng4, reqs4, 32)
        assert eng4.mesh is not None and eng4.mesh.devices.size == 4, (
            "dp=4 did not shard over a four-device mesh")
        for (T, _, _), hlo in sorted(check_kernels(eng4).items()):
            n_gather, n_other = step_collectives(hlo)
            print(f"dp=4 serve step T={T}: {n_gather} all-gather, "
                  f"{n_other} other collectives")
            assert (n_gather, n_other) == (1, 0), (
                "not one collective per step")
        out4 = [r.out_tokens for r in reqs4]
        del eng4, reqs4
        gc.collect()
        eng1, reqs1 = serve(SERVE + exact + ["--dp", "1", "--b-local", "8",
                                             "--requests", "16"],
                            clock, "dp1")
        check_served(eng1, reqs1, 32)
        assert eng1.mesh is None
    out1 = [r.out_tokens for r in reqs1]
    same = sum(a == b for a, b in zip(out4, out1))
    print(f"dp=4 mesh vs dp=1: {same}/{len(out4)} requests token-identical")
    for rid, (a, b) in enumerate(zip(out4, out1)):
        if a != b:
            i = next(i for i, (x, y) in enumerate(zip(a, b)) if x != y)
            print(f"  request {rid}: first differs at output token {i}")
    assert same == len(out4), "dp=4 mesh and dp=1 served different tokens"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the dp=4 mesh plane vs dp=1")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import init_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is missing: {e}",
              file=sys.stderr)
        return 2
    init_compile_cache()
    import jax
    from jax import monitoring

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform})",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devs)} found",
              file=sys.stderr)
        return 1
    print(f"device: {devs[0].device_kind} x{len(devs)}", flush=True)
    clock = CompileClock(monitoring)
    (four_chips if args.chips == 4 else one_chip)(jax, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
