"""The trace reduction: the union-of-intervals busy and idle shares on
hand-made events, and the whole reduction on a small trace recorded on
a v5e (``data/olmo1b_steps.xplane.pb.gz``: four serve steps of the
``olmo1b.chat_shared`` cell, ``--trace 1 --seconds 0.6``)."""

import gzip
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

from chipbench import xplane  # noqa: E402
from chipbench.xplane import Event as E  # noqa: E402

RECORDED = HERE / "tests" / "data" / "olmo1b_steps.xplane.pb.gz"


def test_union_merges_overlaps_and_nesting():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (6, 6.5), (8, 9)]) == [
        (0, 3), (5, 7), (8, 9)]


def test_busy_counts_overlapping_ops_once_and_clips_to_the_window():
    ops = [E("a", 0, 10), E("b", 5, 10), E("c", 30, 10), E("d", 90, 20)]
    # window [2, 100]: [2,15] + [30,40] + [90,100] = 13 + 10 + 10
    assert xplane.busy_ns(ops, 2, 100) == 33
    idle = 1 - xplane.busy_ns(ops, 2, 100) / 98
    assert idle == pytest.approx(65 / 98)
    assert xplane.idle_gaps(ops, 2, 100) == [(15, 30), (40, 90)]


def test_idle_gap_is_named_by_the_innermost_covering_span():
    host = [E("window", 0, 100), E("step", 10, 20), E("record", 25, 4),
            E("sleep", 40, 30)]
    assert xplane.covering(host, 26, ("step", "record", "sleep")) == "record"
    assert xplane.covering(host, 15, ("step", "record", "sleep")) == "step"
    assert xplane.covering(host, 80, ("step", "record", "sleep")) == "other"


def test_top_ops_leave_out_containers_and_label_by_shape():
    name = ("%copy.240 = bf16[16,1,608,64,16,128]{5,4,3,2,1,0:T(8,128)} "
            "copy(bf16[16,1,608,64,16,128] %x)")
    ops = [E("%while.26 = (s32[]) while(...)", 0, 100), E(name, 1, 30),
           E(name, 40, 30), E("%fusion.1 = f32[8]{0} fusion(...)", 80, 5)]
    assert xplane.top_ops(ops, 0, 100) == [
        ("copy.240 bf16[16,1,608,64,16,128]", 60e-9),
        ("fusion.1 f32[8]", 5e-9)]
    assert xplane.kernel_events(
        [E("%paged_attention_chunk.11 = bf16[...]", 0, 1)] + ops,
        "paged_attention_chunk")[0].dur == 1


def test_reduction_of_a_recorded_chip_trace(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    t = xplane.load(str(path))
    dev = t.devices()
    assert dev and dev[0].startswith("/device:TPU:")
    w = t.span("window")
    assert w is not None and w.dur > 0
    ops = t.ops[dev[0]]
    busy = xplane.busy_ns(ops, w.start, w.end)
    assert 0 < busy <= w.dur
    steps = [s for s in t.spans("step") if w.start <= s.start < w.end]
    assert steps
    kern = [e for e in xplane.kernel_events(ops, "paged_attention_chunk")
            if w.start <= e.start < w.end]
    # one attention-kernel call per layer (16) per dispatched step
    assert kern and len(kern) % 16 == 0
    mods = [m for m in t.modules[dev[0]] if w.start <= m.start < w.end]
    assert max(m.dur for m in mods) > 0
    gaps = xplane.idle_gaps(ops, w.start, w.end)
    assert sum(b - a for a, b in gaps) == pytest.approx(w.dur - busy)
    # what this window ran: four prefill steps, the kernel on top, the
    # device busy for most of it (4.8% idle, measured on one v5e chip)
    assert xplane.top_ops(ops, w.start, w.end)[0][0].startswith(
        "paged_attention_chunk")
    assert 0.9 < busy / w.dur
    # its longest idle gap falls inside a host step (between the serve
    # step's dispatch and the next program)
    a, b = max(gaps, key=lambda g: g[1] - g[0])
    assert xplane.covering(t.host, (a + b) / 2,
                           ("submit", "step", "record", "sleep")) == "step"
    assert len(t.spans("step")) == 4
