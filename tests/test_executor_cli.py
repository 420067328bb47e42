"""The three launch CLIs are parse-to-spec layers over one executor
(ISSUE-3 acceptance): each builds a ``JobSpec`` and runs it through
``repro.launch.executor.execute``."""
import json

import pytest

from repro.core.jobspec import JobSpec
from repro.launch import dryrun, serve, train
from repro.launch.executor import execute


def test_train_cli_builds_and_executes_jobspec():
    spec = train.parse_spec(["--arch", "paper-overhead-100m", "--reduced",
                             "--steps", "2", "--batch", "2", "--seq", "16",
                             "--remat", "dots", "--lr", "2e-3"])
    assert isinstance(spec, JobSpec)
    assert spec.kind == "train" and spec.framework == "paper-overhead-100m"
    t = spec.train
    assert (t.total_steps, t.global_batch, t.seq_len) == (2, 2, 16)
    assert t.remat_policy == "dots" and t.learning_rate == 2e-3 and t.reduced
    assert execute(spec) == 0


def test_serve_cli_builds_jobspec():
    spec = serve.parse_spec(["--arch", "qwen3-0.6b", "--reduced",
                             "--batch", "2", "--prompt-len", "16", "--gen",
                             "6", "--continuous", "--requests", "4",
                             "--page-budget", "3"])
    assert spec.kind == "serve" and spec.framework == "qwen3-0.6b"
    sv = spec.serve
    assert (sv.batch, sv.prompt_len, sv.gen) == (2, 16, 6)
    assert sv.continuous and sv.requests == 4 and sv.page_budget == 3
    # serve.main IS execute(parse_spec(...)) — executed end-to-end by the
    # serving smoke tests in test_paged_cache.py


def test_dryrun_cli_builds_jobspec_and_executes_cached(monkeypatch, tmp_path):
    spec, args = dryrun.parse_spec(["--arch", "qwen3-0.6b",
                                    "--shape", "decode_32k"])
    assert spec.kind == "dryrun" and not args.cell_worker
    assert spec.resources.gpus_per_replica == 0
    (cell,) = spec.dryrun.cells
    assert (cell.arch, cell.shape, cell.multi_pod) == \
        ("qwen3-0.6b", "decode_32k", False)

    # executor dispatch without compiling: the cell's artifact is cached
    monkeypatch.setattr(dryrun, "ARTIFACTS", tmp_path)
    (tmp_path / "qwen3-0.6b__decode_32k__16x16.json").write_text(
        json.dumps({"ok": True}))
    assert execute(spec) == 0


def test_dryrun_cli_sweep_all_spec():
    spec, _ = dryrun.parse_spec(["--all", "--force"])
    assert spec.dryrun.sweep_all and spec.dryrun.force
    from repro.core.jobspec import resolve_cells
    cells = resolve_cells(spec.dryrun)
    assert len(cells) > 20                 # arch × shape × both meshes
    assert all(c.arch != "paper-overhead-100m" for c in cells)


def test_executor_rejects_invalid_spec():
    with pytest.raises(SystemExit, match="unknown framework"):
        execute(JobSpec(name="x", framework="not-a-framework"))


def test_train_cli_refuses_pallas_kernels():
    # no Pallas kernel has a backward pass: refused before any tracing
    spec = train.parse_spec(["--reduced", "--steps", "1", "--use-pallas"])
    assert spec.train.use_pallas
    with pytest.raises(SystemExit, match="no backward pass"):
        execute(spec)


def test_serve_use_pallas_follows_the_backend(monkeypatch):
    import repro.kernels.ops as ops
    from repro.launch.executor import serve_ctx
    auto = serve.parse_spec(["--reduced"]).serve
    on = serve.parse_spec(["--reduced", "--use-pallas"]).serve
    off = serve.parse_spec(["--reduced", "--no-use-pallas"]).serve
    assert (auto.use_pallas, on.use_pallas, off.use_pallas) == \
        (None, True, False)
    assert [serve_ctx(sv).use_pallas for sv in (auto, on, off)] == \
        [False, True, False]                     # this host is not a TPU
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert [serve_ctx(sv).use_pallas for sv in (auto, on, off)] == \
        [True, True, False]
