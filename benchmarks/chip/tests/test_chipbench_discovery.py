"""A configuration, a traffic mix and a per-layer metric added as new
files and new entries are found by name, with no edit to any file the
benchmark already has."""

import hashlib
import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent.parent
ROOT = HERE.parent.parent
sys.path.insert(0, str(HERE))

from chipbench import spec  # noqa: E402


def _digests(d: Path) -> dict:
    return {str(p.relative_to(d)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(d.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_files_and_entries_are_found(tmp_path):
    base = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, base, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = _digests(base)

    # a new configuration, mix and metric: new files only
    cfg = json.loads((base / "configs" / "olmo1b.json").read_text())
    cfg["engine"] = dict(cfg["engine"], b_local=4)
    (base / "configs" / "olmo1b_small.json").write_text(json.dumps(cfg))
    (base / "traffic" / "chat_unique.json").write_text(json.dumps({
        "loop": "open", "rate_per_s": 1.0, "requests": 10,
        "prompt": {"dist": "uniform", "min": 100, "max": 200},
        "output": {"dist": "uniform", "min": 10, "max": 20}}))
    (base / "metrics" / "steps_in_window.py").write_text(
        "def read(run):\n    return len(run.steps)\n")

    # ... and new entries in BENCHMARK.json
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="olmo1b_small",
                                 file="benchmarks/chip/configs/"
                                      "olmo1b_small.json"))
    bench["workloads"].append({"name": "olmo1b_small.chat_unique",
                               "config": "olmo1b_small",
                               "traffic": "chat_unique", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                               "better": "higher",
                               "source": "host_clock", "layer": "device",
                               "moves": "output_tok_s"})

    c = spec.cell(bench, "olmo1b_small.chat_unique", tmp_path, base)
    assert c.config["engine"]["b_local"] == 4
    assert c.traffic["rate_per_s"] == 1.0
    assert "steps_in_window" in [m["name"] for m in c.per_layer]
    assert "ttft_tail_p90_ms" not in [m["name"] for m in c.per_layer]
    read = spec.readers_for(c, c.per_layer)
    assert read["steps_in_window"](type("R", (), {"steps": [1, 2]})) == 2
    assert spec.reference(c).__name__.endswith("dense_decoder")
    after = _digests(base)
    assert {k: v for k, v in after.items() if k in before} == before


def test_every_entry_of_the_benchmark_resolves():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        c = spec.cell(bench, w["name"], ROOT)
        assert set(spec.readers_for(c, c.end_to_end + c.per_layer)) == {
            m["name"] for m in c.end_to_end + c.per_layer}
