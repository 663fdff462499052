"""BENCHMARK.json and the files it names: every cell finds its
configuration, traffic, limits and metric readers by name; bad names and
units are refused; a new cell, configuration, traffic mix or metric is
only new files and new entries."""
import copy
import json
import shutil

import pytest

from benchpath import BENCH, ROOT
from benchlib import device
from benchlib.manifest import Manifest, ManifestError


def load_data():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_cell_finds_its_files():
    man = Manifest.load(ROOT, BENCH)
    assert man.data["paths"] and man.data["command"][0] == "python3"
    for name, cell in man.cells.items():
        assert man.config(cell.config)["name"] == cell.config
        assert man.reference(cell.config) is not None
        assert "kind" in man.traffic(cell.traffic)
        assert man.limits(name)
        assert (BENCH / "benchlib" / "drivers"
                / f"{man.traffic(cell.traffic)['kind']}.py").is_file()
        e2e = man.end_to_end_names(name)
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = man.per_layer(name)
        assert layers, name
        for m in layers:
            assert callable(man.reader(m["name"]))
            assert m["moves"] in e2e


@pytest.mark.parametrize("path,value", [
    (("workloads", 0, "name"), "bad name"),
    (("workloads", 0, "name"), "a/b"),
    (("end_to_end", 0, "unit"), "tokens per second"),
    (("end_to_end", 0, "better"), "up"),
    (("end_to_end", 0, "bound"), 0.3),
    (("per_layer", 0, "moves"), "no_such_metric"),
    (("per_layer", 0, "source"), "guess"),
    (("run_seconds",), 52),
])
def test_bad_entries_are_refused(path, value):
    data = load_data()
    node = data
    for k in path[:-1]:
        node = node[k]
    node[path[-1]] = value
    with pytest.raises(ManifestError):
        Manifest(data, ROOT, BENCH)


def test_extra_key_and_repeated_cell_are_refused():
    data = load_data()
    data["end_to_end"][0]["why"] = "no"
    with pytest.raises(ManifestError):
        Manifest(data, ROOT, BENCH)
    data = load_data()
    data["workloads"].append(copy.deepcopy(data["workloads"][0]))
    with pytest.raises(ManifestError):
        Manifest(data, ROOT, BENCH)


def test_new_cell_config_traffic_and_metric_are_new_files_only(tmp_path):
    """A copy of the benchmark gains a configuration, a traffic mix, a
    cell and a per-layer metric by new files and new entries; no file
    that was there is edited."""
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    data = load_data()
    cfg = json.loads((BENCH / "configs" / "qwen2-0.5b.json").read_text())
    cfg.update(name="qwen2-0.5b-wide", intermediate_size=8192)
    (bench / "configs" / "qwen2-0.5b-wide.json").write_text(json.dumps(cfg))
    shutil.copy(BENCH / "configs" / "qwen2-0.5b.py",
                bench / "configs" / "qwen2-0.5b-wide.py")
    chat = json.loads((BENCH / "traffic" / "chat.json").read_text())
    chat["rate_per_s"] = 1.0
    (bench / "traffic" / "chat-slow.json").write_text(json.dumps(chat))
    (bench / "limits" / "qwen2-0.5b-wide.chat-slow.json").write_text(
        json.dumps({"sample_requests": 2, "gap_max": 1.0}))
    (bench / "metrics" / "slots_busy.py").write_text(
        "def read(ctx):\n    return ctx.get('slot_steps')\n")
    data["configs"].append({
        "name": "qwen2-0.5b-wide", "source": "https://example.org/cfg",
        "file": "benchmarks/chip/configs/qwen2-0.5b-wide.json",
        "reduced": [], "why": "a wider feed-forward"})
    data["workloads"].append({
        "name": "qwen2-0.5b-wide.chat-slow", "config": "qwen2-0.5b-wide",
        "traffic": "chat-slow", "chips": 1, "why": "a slow chat stream"})
    data["end_to_end"][0]["workloads"].append("qwen2-0.5b-wide.chat-slow")
    data["per_layer"].append({
        "name": "slots_busy", "unit": "slot-steps", "better": "higher",
        "source": "program_counter", "layer": "request layer",
        "moves": "ttft_p95_ms", "workloads": ["qwen2-0.5b-wide.chat-slow"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))

    man = Manifest.load(tmp_path, bench)
    cell = man.cell("qwen2-0.5b-wide.chat-slow")
    assert man.config(cell.config)["intermediate_size"] == 8192
    assert man.traffic(cell.traffic)["rate_per_s"] == 1.0
    assert man.limits(cell.name)["sample_requests"] == 2
    assert [m["name"] for m in man.per_layer(cell.name)] == ["slots_busy"]
    assert man.reader("slots_busy")({"slot_steps": 5}) == 5
    assert {p: p.read_bytes() for p in before} == before


def test_unknown_device_kind_raises():
    with pytest.raises(device.NoChip):
        device.peaks_for("TPU v99")
    p = device.peaks_for("TPU v5 lite")
    assert (p.flops, p.bytes_per_s) == (197e12, 819e9)


def test_no_tpu_is_refused():
    # the tests run with JAX held to the CPU
    with pytest.raises(device.NoChip):
        device.require_chips(1)
