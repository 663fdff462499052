"""BENCHMARK.json and the files it names: every cell finds its
configuration, architecture, traffic, limits and metric readers by name;
bad names and units are refused; a new cell, configuration,
architecture, traffic mix or metric is only new files and new
entries."""
import copy
import json
import shutil

import jax
import pytest

from benchpath import BENCH, ROOT
from benchlib import device
from benchlib.flops import Decoder
from benchlib.manifest import Manifest, ManifestError
from test_bench_correct import tiny_run
import run as bench_run

ARCH_API = ("model_config", "weight_shapes", "to_program", "from_program",
            "counts")


def load_data():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_cell_finds_its_files():
    man = Manifest.load(ROOT, BENCH)
    assert man.data["paths"] and man.data["command"][0] == "python3"
    for name, cell in man.cells.items():
        assert man.config(cell.config)["name"] == cell.config
        assert man.reference(cell.config) is not None
        if "architecture" in man.config(cell.config):
            arch = man.architecture(cell.config)
            assert all(callable(getattr(arch, f)) for f in ARCH_API)
            shapes = arch.weight_shapes(man.config(cell.config))
            assert set(arch.UNSTACKED) < set(shapes)
        else:
            assert man.architecture(cell.config) is None
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


def test_missing_architecture_is_refused_at_prepare(tmp_path):
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__"))
    path = bench / "configs" / "qwen2-0.5b.json"
    cfg = json.loads(path.read_text())
    cfg["architecture"] = "no_such_arch"
    path.write_text(json.dumps(cfg))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(load_data()))
    args = bench_run.parse(["--workload", "qwen2-0.5b.chat", "--seed", "1",
                            "--seconds", "1"])
    with pytest.raises(ManifestError, match=r"archs/no_such_arch\.py"):
        bench_run.prepare(args, tmp_path, bench, devices=jax.devices())


TOY_ARCH = '''"""A stand-in architecture: the dense decoder under another name,
whose counts charge each token's matrix products twice."""
from pathlib import Path

from benchlib.flops import Decoder
from benchlib.manifest import load_architecture

_dense = load_architecture("dense_decoder", Path(__file__).parents[1])
model_config = _dense.model_config
weight_shapes = _dense.weight_shapes
to_program = _dense.to_program
from_program = _dense.from_program
UNSTACKED = _dense.UNSTACKED


class Counts(Decoder):
    @property
    def matmul_params(self):
        return 2 * super().matmul_params


def counts(c):
    return Counts.from_config(c)
'''


def test_new_cell_config_traffic_and_metric_are_new_files_only(tmp_path):
    """A copy of the benchmark gains an architecture, a configuration, a
    traffic mix, cells and a per-layer metric by new files and new
    entries; the serve and train drivers run the new cells on the CPU to
    a correct result; no file that was there is edited."""
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    data = load_data()
    (bench / "archs" / "toy_decoder.py").write_text(TOY_ARCH)
    cfg = json.loads((BENCH / "configs" / "qwen2-0.5b.json").read_text())
    cfg.update(name="toy", architecture="toy_decoder")
    (bench / "configs" / "toy.json").write_text(json.dumps(cfg))
    shutil.copy(BENCH / "configs" / "qwen2-0.5b.py",
                bench / "configs" / "toy.py")
    chat = json.loads((BENCH / "traffic" / "chat.json").read_text())
    chat["rate_per_s"] = 1.0
    (bench / "traffic" / "chat-slow.json").write_text(json.dumps(chat))
    for cell, real in (("toy.chat-slow", "qwen2-0.5b.chat"),
                       ("toy.train", "qwen2-0.5b.train")):
        shutil.copy(BENCH / "limits" / f"{real}.json",
                    bench / "limits" / f"{cell}.json")
    (bench / "metrics" / "steps_traced.py").write_text(
        "def read(ctx):\n    return ctx.get('serve_steps_traced')\n")
    data["configs"].append({
        "name": "toy", "source": "https://example.org/cfg",
        "file": "benchmarks/chip/configs/toy.json",
        "reduced": [], "why": "a new architecture"})
    data["workloads"] += [
        {"name": "toy.chat-slow", "config": "toy", "traffic": "chat-slow",
         "chips": 1, "why": "a slow chat stream"},
        {"name": "toy.train", "config": "toy", "traffic": "train",
         "chips": 1, "why": "training"}]
    for m in data["end_to_end"]:
        if "qwen2-0.5b.chat" in m.get("workloads", []):
            m["workloads"].append("toy.chat-slow")
        if "qwen2-0.5b.train" in m.get("workloads", []):
            m["workloads"].append("toy.train")
    data["per_layer"].append({
        "name": "steps_traced", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "request layer",
        "moves": "ttft_p95_ms", "workloads": ["toy.chat-slow"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))

    man = Manifest.load(tmp_path, bench)
    cell = man.cell("toy.chat-slow")
    assert man.traffic(cell.traffic)["rate_per_s"] == 1.0
    assert man.limits(cell.name)["sample_requests"] == 6
    assert [m["name"] for m in man.per_layer(cell.name)] == ["steps_traced"]
    assert man.reader("steps_traced")({"serve_steps_traced": 5}) == 5

    driver, r = tiny_run("toy.chat-slow", root=tmp_path, bench_dir=bench)
    assert r.arch.__file__ == str(bench / "archs" / "toy_decoder.py")
    out = driver.run(r)
    assert out.correct and out.failed == 0, out.checks
    driver, r = tiny_run("toy.train", root=tmp_path, bench_dir=bench)
    out = driver.run(r)
    assert out.correct, out.checks
    b, s = r.traffic["batch"], r.traffic["seq_len"]
    dense = Decoder.from_config(r.config).train_step_flops(b, s)
    assert out.layer["train_step_flops"] > dense
    assert out.layer["train_step_flops"] == \
        r.arch.counts(r.config).train_step_flops(b, s)
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
