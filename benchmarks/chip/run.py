#!/usr/bin/env python3
"""On-chip benchmark: one run of one cell of `BENCHMARK.json`.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs from the root of a checkout, on a machine whose JAX sees the chips
the cell asks for; it exits non-zero, printing no result, without them.
The cell names a configuration and a traffic mix; the traffic file's
"kind" names the general driver (`benchlib/drivers/<kind>.py`) that runs
it, and a model configuration's "architecture" the file
(`archs/<architecture>.py`) that maps it onto the program. Set-up
(weights made on the device from the seed, every shape warmed, programs
compiled or loaded from the compile cache at `<checkout>/.jax_cache`)
is timed as `setup_s`; then the window measures for `--seconds`. Once it
closes, the program's output is compared with the configuration's plain
reference (`configs/<config>.py`).

With `--trace 0` the result line carries the cell's end-to-end metrics,
with `--trace 1` its per-layer metrics (`metrics/<name>.py`), the device's
busy time and the trace's breakdown. The numbers compared for `correct`
are printed beside their limits as the last lines on standard error and
under "checks", the last key of the result line, which is the last line
on standard output.

`--control 1` is for calibration only: it also reads the control (the
reference in the next lower precision) on the same outputs, puts its
numbers in the program's place and judges them by the same limits, under
"control" in the result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

from benchlib.common import (CompileCounter, GcPauses, Outcome,  # noqa: E402
                             Run, judge, log)
from benchlib.manifest import Manifest, ManifestError  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def prepare(args, root: Path = ROOT, bench: Path = BENCH,
            devices=None) -> tuple:
    """The manifest, the cell and a Run; `devices` given skips the chip
    check (tests drive the rest of a run on the CPU that way)."""
    from benchlib import device as dev
    man = Manifest.load(root, bench)
    cell = man.cell(args.workload)
    traffic = man.traffic(cell.traffic)
    config = man.config(cell.config)
    limits = man.limits(cell.name)
    reference = man.reference(cell.config)
    arch = man.architecture(cell.config)
    if devices is None:
        devices = dev.require_chips(cell.chips)
        peaks = dev.peaks_for(devices[0].device_kind)
    else:
        peaks = dev.peaks_for("TPU v5 lite")
    run = Run(workload=cell.name, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), config=config, traffic=traffic,
              limits=limits, reference=reference, arch=arch,
              devices=devices, peaks=peaks, root=root, t_start=T_START,
              control=bool(args.control))
    return man, cell, run


def execute(man: Manifest, cell, run: Run) -> Outcome:
    driver = importlib.import_module(f"benchlib.drivers.{run.traffic['kind']}")
    run.compiles = CompileCounter()
    gcs = GcPauses(run.t_start)
    out = driver.run(run)
    out.notes["gc"] = gcs.close()
    out.notes["setup_marks_s"] = run.marks
    if run.trace:
        ctx = dict(out.layer, reduced=out.reduced, peaks=run.peaks,
                   config=run.config, traffic=run.traffic)
        metrics = {}
        for m in man.per_layer(cell.name):
            value = man.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out.notes["per_layer"] = metrics
    return out


def result_line(man: Manifest, cell, run: Run, out: Outcome,
                all_devices: int) -> dict:
    from benchlib import device as dev
    if run.trace:
        metrics = out.notes.get("per_layer", {})
    else:
        units = {m["name"]: m["unit"] for m in man.end_to_end(cell.name)}
        metrics = {k: {"value": out.e2e[k], "unit": u}
                   for k, u in units.items()}
    device = dev.describe(run.devices, all_devices)
    device["memory_peak_bytes"] = out.memory_peak_bytes
    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if run.trace and out.reduced is not None:
        device["busy_s"] = out.reduced.busy_s
        device["window_s"] = out.reduced.window_s
        line["breakdown"] = out.reduced.breakdown()
    if out.control:
        line["control"] = {
            name: {"correct": judge(checks),
                   "checks": {k: {"value": v, "limit": lim}
                              for k, (v, lim) in checks.items()}}
            for name, checks in out.controls().items()}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out.checks.items()}
    return line


def main(argv=None) -> int:
    args = parse(argv)
    from benchlib import device as dev
    if not (ROOT / "src" / "repro").is_dir():
        log(f"the program is not in this checkout ({ROOT}/src/repro)")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax  # noqa: F401
    t_jax = time.perf_counter() - T_START
    try:
        man, cell, run = prepare(args)
    except ManifestError as e:
        log(f"benchmark files: {e}")
        return 2
    except dev.NoChip as e:
        log(str(e))
        return 3
    run.marks["jax_imported"] = round(t_jax, 3)
    run.mark("chip")
    from repro.launch.compile_cache import use_compile_cache
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"cell {cell.name}: config {cell.config}, traffic {cell.traffic}, "
        f"seed {args.seed}, {args.seconds:g} s, trace {args.trace}; "
        f"compile cache {use_compile_cache()}")
    out = execute(man, cell, run)
    line = result_line(man, cell, run, out, len(jax.devices()))
    for k, v in out.notes.items():
        if k != "per_layer":
            log(f"{k}: {v}")
    print(json.dumps(line), flush=True)
    for k, (v, lim) in out.checks.items():
        log(f"check {k} {v!r} limit {lim!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
