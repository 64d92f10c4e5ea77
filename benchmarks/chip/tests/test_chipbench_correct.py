"""The comparison that decides ``correct``, driven through whole runs of
the harness on the CPU at a small size (the look for a chip skipped): a
sound run is correct, a run whose served tokens are altered where the
engine produces them is not, and the fp8 control fails the limit that
the program meets."""

import json
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent.parent
ROOT = HERE.parent.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import cell as cell_mod  # noqa: E402
from chipbench import spec  # noqa: E402

DATA = HERE / "tests" / "data"


def small_cell():
    cfg = json.loads((DATA / "small.json").read_text())
    mix = json.loads((DATA / "small_chat.json").read_text())
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return spec.Cell("small.chat", 1, cfg, "small", mix, "small_chat",
                     bench["end_to_end"], [], HERE)


def alter_tokens(monkeypatch):
    """Every window request's second output token is replaced, on the
    host, right after the step that produced it."""
    from repro.serving.engine import ServingEngine
    step = ServingEngine.step

    def faulty(self):
        out = step(self)
        for req in self.active.values():
            if req.rid >= 0 and len(req.out_tokens) == 2:
                req.out_tokens[1] = (req.out_tokens[1] + 1) % self.cfg.vocab
        return out
    monkeypatch.setattr(ServingEngine, "step", faulty)


@pytest.mark.parametrize("fault", [None, "token_altered"])
def test_correct_decides_on_the_served_tokens(fault, monkeypatch):
    if fault == "token_altered":
        alter_tokens(monkeypatch)
    out = cell_mod.run_cell(small_cell(), 2**32 + 3, 2.0, False,
                            time.perf_counter(), require_chip=False,
                            log=lambda m: None)
    checks = out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    for name in ("unanswered", "pages_in_use_after_drain",
                 "step_retries_recoveries_dumps", "trace_events_dropped"):
        assert checks[name]["value"] == 0
    assert checks["never_dry_margin_min"]["value"] >= 0
    gap = checks["served_gap_max"]
    if fault is None:
        assert out["correct"] and gap["value"] <= gap["limit"]
        assert set(out["metrics"]) == {"setup_s", "ttft_p50_ms",
                                       "itl_p95_ms", "output_tok_s"}
    else:
        assert not out["correct"] and gap["value"] > gap["limit"]
    assert list(out)[-1] == "checks"


def test_control_fails_the_limit_the_program_meets():
    out = cell_mod.run_cell(small_cell(), 6, 2.0, False, time.perf_counter(),
                            require_chip=False, control=True,
                            log=lambda m: None)
    gap = out["checks"]["served_gap_max"]
    ctl = out["checks"]["control_gap_max"]
    assert gap["value"] <= gap["limit"] and ctl["limit"] == gap["limit"]
    assert ctl["value"] > ctl["limit"] and not out["correct"]
