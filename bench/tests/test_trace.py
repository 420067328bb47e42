"""The trace reduction, on a short trace of the chat cell recorded on one
v5e chip (``bench/tools.py record-trace``) and kept in
``bench/testdata/``."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import harness, trace

BENCH = Path(__file__).resolve().parents[1]
TRACE = BENCH / "testdata" / "serve.xplane.pb"
SPANS = harness.load_module(BENCH / "drivers" / "serve_open_loop.py",
                            "serve_open_loop").SPANS


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(str(TRACE), SPANS)


def test_union_of_intervals():
    assert trace._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3], [5, 9]]


def test_stable_names_drop_numeric_suffixes():
    assert trace.stable_name("fusion.12") == "fusion"
    assert trace.stable_name("paged_decode_bhd.6.3") == "paged_decode_bhd"
    assert trace.stable_name("copy-start") == "copy-start"


def test_busy_and_idle_fill_the_window(reduced):
    assert reduced.n_devices == 1
    assert 0 < reduced.busy_s <= reduced.window_s
    idle = sum(reduced.idle_by_span.values())
    assert idle + reduced.busy_s == pytest.approx(reduced.window_s, rel=1e-6)


def test_idle_gaps_are_named_by_harness_spans(reduced):
    assert set(reduced.idle_by_span) <= set(SPANS) | {"other"}
    assert "step" in reduced.idle_by_span


def test_the_paged_decode_kernel_is_found(reduced):
    assert reduced.kernel_s("paged_decode") > 0
    ops = reduced.top_ops()
    assert len(ops) <= 10
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    assert sum(reduced.op_s.values()) >= reduced.busy_s - 1e-9
