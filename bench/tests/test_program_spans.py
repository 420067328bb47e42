"""The per-layer metrics read from the program's own spans, on the CPU at
a tiny size: a traced run reports all five; the window they read holds
as many rounds and steps as the driver counted; they split what the
harness times from outside; and a program without spans, or a ring
that no longer reaches the window's open, leaves them out.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench/tests
"""
from __future__ import annotations

import sys
import time

import pytest

from bench import harness, program_spans
from bench.tests import util

NEW = ("queue_wait_ms", "prefill_ms", "admit_host_ms",
       "prefill_useful_share", "decode_host_ms")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(run, result) of one traced run of the tiny cell."""
    root = util.make_root(tmp_path_factory.mktemp("checkout"))
    args = harness.parse(["--workload", util.CELL, "--seed",
                          str(2**33 + 9), "--seconds", "2.0", "--trace", "1"])
    run, driver, devices = harness.build(root, args, require_tpu=False)
    result = harness.execute(root, run, driver, devices, time.perf_counter())
    assert result["correct"], result["checks"]
    return root, run, result


def test_span_metrics_reported(traced):
    _, _, res = traced
    for name in NEW:
        assert res["metrics"][name]["value"] is not None, name
    assert 0 < res["metrics"]["prefill_useful_share"]["value"] <= 100


def test_window_counts_what_the_driver_counted(traced):
    _, run, _ = traced
    w = program_spans.window(run)
    assert len(w.rounds) == run.counters["admit_rounds"] > 0
    assert len(w.steps) == run.counters["steps"] > 0
    assert all(len(pre) == 1 for _, pre in w.rounds)
    assert all(len(dec) == 1 for _, dec in w.steps)
    due = [s for s in run.state["seen"] if 0 <= s.req.due < run.seconds]
    admitted = [s for s in due if s.t_first is not None]
    assert len(w.queue) >= len(admitted) > 0


def test_spans_split_the_outside_timings(traced):
    _, _, res = traced
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["admit_host_ms"] + m["prefill_ms"] <= m["admit_ms"]
    assert 0 < m["decode_host_ms"] < m["decode_step_ms"]


@pytest.mark.parametrize("cut", ["no_recorder", "ring_too_short"])
def test_metrics_left_out_without_the_window(traced, monkeypatch, cut):
    root, run, _ = traced
    import repro.launch
    from repro.launch import spans
    if cut == "no_recorder":         # the program as it was before spans
        monkeypatch.delattr(repro.launch, "spans")
        monkeypatch.setitem(sys.modules, "repro.launch.spans", None)
    else:
        monkeypatch.setattr(spans, "between", lambda t0, t1: None)
    for name in NEW:
        reader = harness.load_module(root / "bench/metrics" / f"{name}.py",
                                     f"bench_metric_{name}")
        assert reader.read(run) is None, name
