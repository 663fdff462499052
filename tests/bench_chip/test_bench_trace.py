"""Trace reduction: busy union, idle share, per-op time, and idle gaps
put down to the host span open at the time."""
import json
from pathlib import Path

import pytest

from benchpath import BENCH  # noqa: F401
from benchlib import tracered
from benchlib.readers import idle_pct, roofline_pct
from benchlib.device import peaks_for

DEV = "/device:TPU:0"


def events(ops, spans, modules=None):
    return tracered.TraceEvents(ops={DEV: ops},
                                modules={DEV: modules or []}, spans=spans)


def test_busy_union_idle_share_and_per_op_time():
    ev = events(
        ops=[("fusion.1", 1.0, 3.0), ("dot.2", 2.0, 4.0),   # overlap
             ("fusion.1", 6.0, 7.0), ("copy", 9.5, 12.0)],  # clipped at 10
        spans=[("bench:window", 0.0, 10.0), ("bench:step", 0.5, 4.5),
               ("bench:track", 4.5, 8.0)],
        modules=[("jit_step(1)", 1.0, 4.0), ("jit_step(1)", 6.0, 7.0)])
    red = tracered.reduce(ev)
    assert red.window_s == 10.0
    assert red.busy_s == pytest.approx(3.0 + 1.0 + 0.5)
    assert red.idle_share == pytest.approx(0.55)
    assert red.op_time["fusion.1"] == (2, pytest.approx(3.0))
    assert red.op_time["copy"] == (1, pytest.approx(0.5))
    assert tracered.module_stats(red, "jit_step") == (2, pytest.approx(4.0))
    # gaps [0, 1): no span to 0.5, then step; [4, 6): step to 4.5, then
    # track; [7, 9.5): track to 8, then no span
    assert red.idle_by_span["step"] == pytest.approx(0.5 + 0.5)
    assert red.idle_by_span["track"] == pytest.approx(1.5 + 1.0)
    assert red.idle_by_span["other"] == pytest.approx(0.5 + 1.5)
    bd = red.breakdown()
    assert bd["device_ops"][0][0] == "fusion.1"
    assert [n for n, _ in bd["idle_gaps"]][0] == "track"
    assert idle_pct({"reduced": red}) == pytest.approx(55.0)


def test_innermost_span_wins_and_chips_average():
    ev = tracered.TraceEvents(
        ops={DEV: [("a", 2.0, 4.0)], "/device:TPU:1": [("a", 0.0, 4.0)]},
        modules={},
        spans=[("bench:window", 0.0, 4.0), ("bench:outer", 0.0, 4.0),
               ("bench:inner", 0.5, 1.5)])
    red = tracered.reduce(ev)
    assert red.chips == 2
    assert red.busy_s == pytest.approx(3.0)
    assert red.idle_by_span == {"inner": pytest.approx(0.5),
                                "outer": pytest.approx(0.5)}


def test_no_window_or_device_is_an_error():
    with pytest.raises(ValueError):
        tracered.reduce(events([("a", 0, 1)], []))
    with pytest.raises(ValueError):
        tracered.reduce(tracered.TraceEvents(
            spans=[("bench:window", 0.0, 1.0)]))


def test_roofline_takes_the_larger_bound():
    p = peaks_for("TPU v5 lite")
    # memory-bound call: 16 MiB at 819 GB/s is 20.5 us
    assert roofline_pct(2 * 1024 ** 3, 16 * 2 ** 20, 10, 10 * 41e-6, p) == \
        pytest.approx(100 * (16 * 2 ** 20 / 819e9) / 41e-6)
    # compute-bound call
    assert roofline_pct(137e9, 1e6, 1, 1e-3, p) == pytest.approx(
        100 * 137e9 / 197e12 / 1e-3)
    assert roofline_pct(1.0, 1.0, 0, 1.0, p) is None


RECORDED = Path(__file__).parent / "data" / "gemm16k-b4096.trace.json"


def test_recorded_trace_of_the_4096_block_graph():
    """0.2 s of a traced taskrt-ddast.gemm16k-b4096 run on one TPU v5e
    (ops, programs and harness spans as `extract` kept them)."""
    d = json.loads(RECORDED.read_text())
    ev = tracered.TraceEvents(
        ops={k: [tuple(x) for x in v] for k, v in d["ops"].items()},
        modules={k: [tuple(x) for x in v] for k, v in d["modules"].items()},
        spans=[tuple(x) for x in d["spans"]])
    red = tracered.reduce(ev)
    assert red.window_s == pytest.approx(0.2, rel=1e-6)
    # busy + idle tile the window
    assert red.busy_s + sum(red.idle_by_span.values()) == pytest.approx(
        red.window_s, rel=1e-9)
    # the union, counted independently on a 1 us grid
    lo, hi = tracered.window_of(ev)
    grid = set()
    for _, s, e in ev.ops[DEV]:
        a, b = max(s, lo), min(e, hi)
        grid.update(range(round((a - lo) * 1e6), round((b - lo) * 1e6)))
    assert red.busy_s == pytest.approx(len(grid) * 1e-6, abs=2e-5 * 50)
    n, secs = tracered.module_stats(red, "_gemm_block")
    assert n > 0 and 0 < secs <= red.busy_s
    share = roofline_pct(2 * 4096 ** 3, 4 * 4096 ** 2 * 4, n, secs,
                         peaks_for("TPU v5 lite"))
    assert 50 < share <= 105
    bd = red.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0] == "convolution_add_fusion"


def serve_trace():
    """Two decode steps of 30 ms, one carrying a 4 ms prompt chunk."""
    return tracered.reduce(events(
        ops=[("a", 0.0, 0.064)], spans=[("bench:window", 0.0, 0.1)],
        modules=[("jit_prefill_chunk(7)", 0.0, 0.004),
                 ("jit_serve_step(3)", 0.004, 0.034),
                 ("jit_serve_step(3)", 0.034, 0.064)]))


def test_prefill_share_is_the_chunks_device_time():
    from benchlib.manifest import Manifest
    from benchpath import ROOT
    read = Manifest.load(ROOT, BENCH).reader("prefill_share")
    red = serve_trace()
    assert read({"reduced": red, "prefill_chunked": True}) == \
        pytest.approx(100 * 4 / 64)
    # prompts teacher-forced through the decode step: the trace cannot
    # part them from decoding
    assert read({"reduced": red, "prefill_chunked": False}) is None
    assert read({"reduced": None, "prefill_chunked": True}) is None


def test_serve_step_mfu_counts_the_tokens_of_the_traced_steps():
    from benchlib.manifest import Manifest
    from benchpath import ROOT
    read = Manifest.load(ROOT, BENCH).reader("serve_step_mfu")
    p = peaks_for("TPU v5 lite")
    ctx = {"reduced": serve_trace(), "peaks": p, "serve_steps_traced": 2,
           "serve_flops_traced": 2 * 197e12 * 0.03 / 100}
    assert read(ctx) == pytest.approx(1.0)
    assert read(dict(ctx, serve_flops_traced=0.0)) is None


def test_decode_contexts_follow_each_prompt_path():
    from types import SimpleNamespace
    from benchlib.drivers.serve import Tracked, decode_contexts
    req = SimpleNamespace(prompt=[5] * 600, admitted_step=10)
    x = Tracked(due=0.0, req=req, token_steps=[12, 13, 14, 15])
    # chunked: the first token came from the last chunk (step 12)
    assert decode_contexts([x], True, 11, 15) == [601, 602, 603]
    assert decode_contexts([x], True, 13, 14) == [602]
    # teacher-forced: prompt token j at step 11 + j, context j + 1; the
    # last one (step 610) also made output token 0 at context 600
    req = SimpleNamespace(prompt=[5] * 4, admitted_step=10)
    x = Tracked(due=0.0, req=req, token_steps=[14, 15])
    assert sorted(decode_contexts([x], False, 10, 15)) == [1, 2, 3, 4, 5]
    assert decode_contexts([x], False, 12, 14) == [4, 3]
