"""The program's device scopes and host phases read from a trace
(``chipbench/scopes.py``) and the three readers built on them.

Two traces recorded on one v5e chip, each ``olmo1b.chat_shared``
``--trace 1 --seconds 0.6``: ``olmo1b_steps`` of a program with neither
scopes nor phases (its readers must read nothing, and the reduction
that ``xplane.py`` and ``cell.breakdown`` make of it must not change),
and ``olmo1b_phases`` of one with both."""

import gzip
import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

from chipbench import cell, scopes, spec, xplane  # noqa: E402
from chipbench.xplane import Event as E  # noqa: E402

DATA = HERE / "tests" / "data"
NEW = ("alloc_dev_share", "kv_relayout_dev_share", "step_host_ms")
HARNESS = ("submit", "step", "record", "sleep")


def _run(tmp_path, name):
    path = tmp_path / f"{name}.xplane.pb"
    path.write_bytes(gzip.decompress((DATA / f"{name}.xplane.pb.gz")
                                     .read_bytes()))
    t = xplane.load(str(path))
    w = t.span("window")
    return types.SimpleNamespace(trace=t, device=t.devices()[0],
                                 lo=w.start, hi=w.end,
                                 scopes=scopes.load(str(path)))


def _read(run, name):
    return spec.load_module(HERE / "metrics" / f"{name}.py",
                            f"test_metric_{name}").read(run)


def test_the_names_agree_with_the_program():
    from repro.serving.trace import DEVICE_SCOPES, PHASE_NAMES
    assert set(scopes.SCOPES) == DEVICE_SCOPES
    assert all(n.startswith(scopes.PHASE_PREFIX) for n in PHASE_NAMES)
    # a phase named like a harness span would double the ``step`` spans
    # that ``traced.step_spans`` pairs with the harness's step records
    assert not PHASE_NAMES & set(xplane.HOST_SPANS)


@pytest.mark.parametrize("tf_op,scope", [
    ("", scopes.NONE),
    ("jit(<unknown>)/while/body/dynamic_slice:", scopes.UNSCOPED),
    ("jit(serve_step)/layers/while/body/dynamic_update_slice:", "layers"),
    ("jit(serve_step)/layers/while/body/closed_call/attn/kv_layout/"
     "jit(paged_attention_chunk)/transpose:", "kv_layout"),
    ("jit(serve_step)/layers/while/body/closed_call/attn/"
     "jit(paged_attention_chunk)/pallas_call:", "attn"),
    ("jit(serve_step)/alloc/vmap()/select_n:", "alloc"),
    # a function named like a scope, under a transform, is no scope
    ("jit(<unknown>)/vmap(alloc)/scatter:", scopes.UNSCOPED),
])
def test_an_op_belongs_to_its_innermost_scope(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def test_relayout_counts_unnamed_copies_of_a_relayout_shape():
    ops = [scopes.Op("a", 0, 10, "kv_layout", "bf16[8,4]"),
           scopes.Op("b", 10, 5, "layers", "bf16[2,8,4]"),
           scopes.Op("c", 20, 7, scopes.NONE, "bf16[2,8,4]"),
           scopes.Op("d", 30, 3, scopes.NONE, "f32[9]"),
           scopes.Op("e", 40, 9, "attn", "bf16[8,4]")]
    assert scopes.relayout_ns(ops) == 22


def test_host_step_time_leaves_out_the_sync_and_idle_steps():
    ph = [E("serve.step", 0, 100), E("serve.admit", 1, 5),
          E("serve.sync", 50, 40), E("serve.step", 200, 10),
          E("serve.admit", 201, 5), E("serve.step", 300, 60),
          E("serve.sync", 310, 20)]
    assert scopes.host_step_ns(ph, 0, 1000) == [60, 40]
    assert scopes.host_step_ns(ph, 250, 1000) == [40]


def test_step_idle_that_no_phase_covers():
    ph = [E("serve.step", 0, 100), E("serve.sync", 20, 50),
          E("serve.post", 70, 20)]
    # idle 10-30 (10 of it before the sync) and 85-120 (15 inside the
    # step: 5 in post, 10 after it); 120-130 is outside every step
    idle, uncovered = scopes.step_idle_uncovered(
        ph, [(10, 30), (85, 120), (120, 130)])
    assert (idle, uncovered) == (35, 20)
    assert scopes.idle_by_phase(ph, [(10, 30), (85, 120)]) == {
        "serve.step": 20e-9, "serve.sync": 10e-9, "serve.post": 5e-9,
        "other": 20e-9}
    host = [E("window", 0, 1000), E("step", 0, 101)]
    assert scopes.label_gaps(host, ph, [(10, 30), (80, 90), (92, 98)],
                             HARNESS) == [["serve.sync", 20e-9],
                                          ["serve.post", 10e-9],
                                          ["serve.step", 6e-9]]


def test_a_program_without_scopes_or_phases(tmp_path):
    """The trace of a program with neither: the new readers read
    nothing, and what the harness reads of it is what it read before
    they existed (measured on the trace when it was recorded)."""
    run = _run(tmp_path, "olmo1b_steps")
    assert run.scopes.phases == []
    assert {_read(run, n) for n in NEW} == {None}
    ops = run.trace.ops[run.device]
    assert xplane.busy_ns(ops, run.lo, run.hi) == 643219157.0
    assert run.hi - run.lo == 675714790.0
    b = cell.breakdown(run)
    assert b["device_ops"][0] == ["paged_attention_chunk.13 "
                                  "bf16[18,16,64,128]", 0.291508447]
    assert b["device_ops"][3] == ["copy.203 bf16[16,1,684,64,16,128]",
                                  0.034814117]
    assert [g[0] for g in b["idle_gaps"]] == ["step"] * 10
    assert b["idle_gaps"][0][1] == 0.004103454
    # the scope reduction covers the same ops, time for time
    assert [(o.start, o.dur) for o in run.scopes.ops[run.device]] == [
        (e.start, e.dur) for e in xplane.leaf_ops(ops)]
    assert set(scopes.device_scopes(run.scopes, run.device, run.lo,
                                    run.hi)) == {scopes.UNSCOPED,
                                                 scopes.NONE}


def test_a_program_with_scopes_and_phases(tmp_path):
    """Five decode steps of the program with scopes and phases."""
    run = _run(tmp_path, "olmo1b_phases")
    got = {n: _read(run, n) for n in NEW}
    # read when the trace was recorded (one v5e chip)
    assert got["alloc_dev_share"] == pytest.approx(0.0655, abs=5e-4)
    assert got["kv_relayout_dev_share"] == pytest.approx(53.08, abs=0.01)
    assert got["step_host_ms"] == pytest.approx(2.234, abs=1e-3)
    ops = run.trace.ops[run.device]
    by = scopes.device_scopes(run.scopes, run.device, run.lo, run.hi)
    assert set(scopes.SCOPES) <= set(by)
    op_s = sum(e.dur for e in xplane.leaf_ops(ops)
               if run.lo <= e.start < run.hi) / 1e9
    assert sum(by.values()) == pytest.approx(op_s, rel=0.01)
    # the program's phases do not double the harness's ``step`` spans
    steps = [e for e in run.trace.spans("step") if run.lo <= e.start < run.hi]
    ph = [p for p in run.scopes.phases if run.lo <= p.start < run.hi]
    assert len(steps) == sum(p.name == "serve.step" for p in ph) == 5
    # every idle gap inside a step is named by a phase, and almost all
    # of that idle time lies inside a phase below ``serve.step``
    gaps = xplane.idle_gaps(ops, run.lo, run.hi)
    inside = [g for g in gaps if any(s.start <= (g[0] + g[1]) / 2 <= s.end
                                     for s in steps)]
    labels = scopes.label_gaps(run.trace.host, run.scopes.phases, inside,
                               HARNESS)
    assert labels and all(n.startswith("serve.") for n, _ in labels)
    assert {n for n, _ in labels} <= {"serve.sync", "serve.dispatch",
                                      "serve.post", "serve.flight",
                                      "serve.feed", "serve.admit"}
    idle, uncovered = scopes.step_idle_uncovered(run.scopes.phases, gaps)
    assert 0 < uncovered < 0.1 * idle
    split = scopes.idle_by_phase(ph, gaps)
    assert sum(split.values()) == pytest.approx(
        sum(b - a for a, b in gaps) / 1e9)
    assert split["serve.sync"] > split["serve.post"] > 0
