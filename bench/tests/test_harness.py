"""The harness on the CPU at a tiny size: a cell added as data runs, a
run without a chip prints nothing, and a broken timed path reads as
``correct: false``.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import subprocess
import sys

import pytest

from bench.tests import util


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = util.make_root(tmp_path_factory.mktemp("checkout"), extra_metric={
        "name": "dummy_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "serving engine",
        "moves": "itl_p99_ms", "workloads": [util.CELL]})
    return root


def test_cell_added_as_data_runs(root):
    (root / "bench/metrics/dummy_steps.py").write_text(
        "def read(run):\n    return run.counters.get('steps')\n")
    rc, res = util.run_cell(root, trace=1)
    assert rc == 0 and res["correct"], res
    assert res["metrics"]["dummy_steps"]["value"] > 0
    assert {"admit_ms", "decode_step_ms"} <= set(res["metrics"])
    assert list(res)[-1] == "checks"
    rc, res = util.run_cell(root, trace=0)
    assert rc == 0 and res["correct"], res
    assert set(res["metrics"]) == {"ttft_p50_ms", "itl_p99_ms", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0


def test_same_seed_same_answers(root):
    from bench import traffic_gen
    mix = json.loads((root / "bench/traffic/tinymix.json").read_text())
    a = traffic_gen.build(mix, 7, 503, 2.0)
    b = traffic_gen.build(mix, 7, 503, 2.0)
    assert [r.tokens.tolist() for r in a] == [r.tokens.tolist() for r in b]


def test_no_tpu_no_result(root):
    rc, res = util.run_cell(root, require_tpu=True)
    assert rc != 0 and res is None


def test_unknown_device_kind_is_an_error(root, monkeypatch):
    from bench import harness

    class Dev:
        platform, device_kind = "tpu", "TPU v99"
    monkeypatch.setattr("jax.devices", lambda: [Dev()])
    _, _, err = harness.device_or_error(1, {"TPU v5 lite": {}}, True)
    assert "not in peaks.json" in err
    _, _, err = harness.device_or_error(4, {"TPU v99": {}}, True)
    assert "needs 4 chips" in err


def test_bench_alone_exits_nonzero(tmp_path):
    (tmp_path / "bench").symlink_to(util.REPO / "bench")
    (tmp_path / "BENCHMARK.json").write_text(
        (util.REPO / "BENCHMARK.json").read_text())
    p = subprocess.run(
        [sys.executable, str(tmp_path / "bench/run.py"), "--workload",
         "qwen3-0.6b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path, timeout=300,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and not p.stdout.strip()


# -- faults planted under the timed path: each must read as not correct --
def _alter_token(monkeypatch):
    from repro.launch import engine as eng
    orig = eng.ServingEngine.step

    def step(self):
        out = orig(self)
        if self.decode_steps % 3 == 0:
            for rec in self.slots:
                if rec is not None and rec.out_tokens:
                    rec.out_tokens[-1] = (rec.out_tokens[-1] + 1) % 503
        return out
    monkeypatch.setattr(eng.ServingEngine, "step", step)


def _stale_cache(monkeypatch):
    """The decode step returns its cache unchanged: the new token's K/V
    never reach the pool."""
    import jax

    from repro.train import steps
    orig = steps.make_serve_steps

    def make(cfg, ctx, **kw):
        prefill, _ = orig(cfg, ctx, donate_cache=False)
        dec = jax.jit(steps.make_decode_step(cfg, ctx))
        return prefill, lambda p, b, c, pos: (dec(p, b, c, pos)[0], c)
    monkeypatch.setattr(steps, "make_serve_steps", make)


def _half_batch(monkeypatch):
    """The prefill leaves out half of the round's rows: the lower half,
    which holds slot 0, the slot every admission round fills first."""
    import jax.numpy as jnp

    from repro.train import steps
    orig = steps.make_serve_steps

    def make(cfg, ctx, **kw):
        prefill, decode = orig(cfg, ctx, **kw)

        def half(params, batch, cache, lengths=None, starts=None):
            B = lengths.shape[0]
            lengths = jnp.where(jnp.arange(B) >= B // 2, lengths, 0)
            return prefill(params, batch, cache, lengths, starts)
        return half, decode
    monkeypatch.setattr(steps, "make_serve_steps", make)


@pytest.mark.parametrize("fault", [_alter_token, _stale_cache, _half_batch])
def test_fault_reads_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    rc, res = util.run_cell(root, seed=11)
    assert rc == 0
    assert res["correct"] is False, res["checks"]
