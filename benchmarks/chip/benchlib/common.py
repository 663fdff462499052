"""What every driver shares: the run's context, its outcome, a compile
counter, the traced window and percentiles."""
from __future__ import annotations

import math
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from . import tracered

TRACE_DIR_NAME = ".bench_trace"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclass
class Run:
    """One run of one cell, as the command line and the files give it."""
    workload: str
    seed: int
    seconds: float
    trace: bool
    config: dict                 # configs/<config>.json
    traffic: dict                # traffic/<traffic>.json
    limits: dict                 # limits/<cell>.json
    reference: Any               # configs/<config>.py, loaded
    arch: Any                    # archs/<architecture>.py, loaded, or None
    devices: List[Any]
    peaks: Any                   # device.Peaks
    root: Path                   # the checkout
    t_start: float               # perf_counter at process start
    control: bool = False        # also read the control (calibration only)
    compiles: "CompileCounter" = None
    marks: Dict[str, float] = field(default_factory=dict)

    def mark(self, name: str) -> None:
        """Notes how far set-up had come, in seconds from process start."""
        self.marks[name] = round(time.perf_counter() - self.t_start, 3)

    @property
    def trace_dir(self) -> Path:
        return self.root / TRACE_DIR_NAME / self.workload


@dataclass
class Outcome:
    """What a driver hands back to the harness."""
    e2e: Dict[str, float]                       # end-to-end metrics
    attempted: int
    failed: int
    checks: Dict[str, Tuple[float, float]]      # name -> (value, limit)
    memory_peak_bytes: int
    layer: Dict[str, Any] = field(default_factory=dict)   # readers' input
    reduced: Optional[tracered.Reduced] = None
    control: Dict[str, float] = field(default_factory=dict)
    notes: Dict[str, Any] = field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return judge(self.checks)

    def controls(self) -> Dict[str, Dict[str, Tuple[float, float]]]:
        """The control's readings, and those of any fault planted in the
        reference (keys "<fault>.<check>"), each put in the program's
        place: the program's checks with those numbers replaced, to be
        judged by `judge` as the program is."""
        out: Dict[str, Dict[str, Tuple[float, float]]] = {}
        for key, v in self.control.items():
            name, _, check = key.rpartition(".")
            checks = out.setdefault(name or "control", dict(self.checks))
            checks[check] = (float(v), self.checks[check][1])
        return out


def judge(checks: Dict[str, Tuple[float, float]]) -> bool:
    """Correct: every number compared is a number and within its limit."""
    return bool(checks) and all(
        not math.isnan(v) and v <= lim for v, lim in checks.values())


class CompileCounter:
    """Counts programs lowered for XLA (a persistent-cache hit included):
    any new program inside a measured window shows here."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self) -> None:
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, _dur: float, **_kw) -> None:
        if name == self.EVENT:
            self.count += 1


class GcPauses:
    """Python's garbage collections during a run: how many of each
    generation, and the longest, with when it ended (seconds from process
    start). A host stall that is a collection shows here."""

    def __init__(self, t_start: float) -> None:
        import gc
        self.t_start = t_start
        self.began = 0.0
        self.count = [0, 0, 0]
        self.total_s = [0.0, 0.0, 0.0]
        self.longest = (0.0, 0, 0.0)          # seconds, generation, end
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self.began = now
            return
        g, d = info["generation"], now - self.began
        self.count[g] += 1
        self.total_s[g] += d
        if d > self.longest[0]:
            self.longest = (d, g, now - self.t_start)

    def close(self) -> str:
        import gc
        gc.callbacks.remove(self._on)
        d, g, at = self.longest
        total_ms = [round(1e3 * t, 1) for t in self.total_s]
        return (f"{self.count} by generation, {total_ms} ms; longest "
                f"{1e3 * d:.1f} ms (generation {g}) ending at {at:.1f} s")


class Window:
    """The traced stretch of a run: starts the profiler, opens the
    "bench:window" host span, and closes both."""

    def __init__(self, run: Run):
        self.run = run
        self.span = None
        self.t0 = self.t1 = None

    def start(self) -> None:
        import jax
        d = self.run.trace_dir
        shutil.rmtree(d, ignore_errors=True)
        d.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the harness's spans suffice
        jax.profiler.start_trace(str(d), profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation(tracered.WINDOW_SPAN)
        self.span.__enter__()
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        """Closes the span and the profiler. The trace is read by
        `reduce`, after the measured loop, so that reading it stalls no
        request or step."""
        import jax
        self.t1 = time.perf_counter()
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self) -> Optional[tracered.Reduced]:
        """The traced window, reduced; None if it never closed."""
        if self.t1 is None:
            return None
        ev = tracered.extract(tracered.find_xplane(str(self.run.trace_dir)))
        red = tracered.reduce(ev)
        shutil.rmtree(self.run.trace_dir, ignore_errors=True)
        return red


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile by linear interpolation (numpy's default)."""
    import numpy as np
    return float(np.percentile(np.asarray(values, dtype=float), q))


def span(name: str):
    """A host span on the profiler's clock (cheap when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(tracered.SPAN_PREFIX + name)
