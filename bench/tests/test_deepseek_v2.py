"""The DeepSeek-V2 cell's pieces on the CPU at a tiny DeepSeek-shaped
size: the reference (its shapes, and the fp8 control coming out not
correct), the ``serve_deepseek_v2`` driver through the harness, and the
two metric readers it adds (``mla_decode_roofline``,
``expert_useful_share``).

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_deepseek_v2.py
"""
from __future__ import annotations

import json
import shutil
import types

import numpy as np
import pytest

from bench.tests import util

CELL = "tinyds.tinydoc"


def tiny_config() -> dict:
    c = json.loads((util.REPO / "bench/configs/deepseek-v2-236b.json")
                   .read_text())
    c.update(name="tinyds", hidden_size=64, num_attention_heads=4,
             num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             intermediate_size=96, moe_intermediate_size=32,
             num_experts_per_tok=3, n_routed_experts=4, first_held_expert=4,
             num_hidden_layers=3, vocab_size=384)
    c["published"] = dict(c["published"], n_routed_experts=16)
    c["rope_scaling"] = dict(c["rope_scaling"], factor=4,
                             original_max_position_embeddings=32)
    return c


def tiny_mix() -> dict:
    t = json.loads((util.REPO / "bench/traffic/doc-qa.json").read_text())
    t.update(rate_per_s=20.0, preroll_s=0.5, slots=4, page_size=8,
             prompt_cap=64, output_cap=16)
    t["sessions"].update(system_prompts=3, system_prompt_tokens=40,
                         assistant_tokens=[2, 6])
    t["user_tokens"].update(median=6, min=2, max=8)
    t["output_tokens"].update(median=6, min=2, max=16)
    return t


def make_root(tmp, limits: dict):
    root = tmp
    shutil.copytree(util.REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    (root / "bench/configs/tinyds.json").write_text(json.dumps(tiny_config()))
    (root / "bench/traffic/tinydoc.json").write_text(json.dumps(tiny_mix()))
    (root / f"bench/limits/{CELL}.json").write_text(
        json.dumps({"limits": limits}))
    b = json.loads((util.REPO / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tinyds", "source": "test",
                         "file": "bench/configs/tinyds.json", "reduced": [],
                         "why": "tiny"})
    b["workloads"].append({"name": CELL, "config": "tinyds",
                           "traffic": "tinydoc", "chips": 1, "why": "tiny"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "deepseek-v2-236b.doc-qa" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


# the tiny program computes in bfloat16 as the cell does.  Over five seeds
# its mean served gap read at most 0.028, the fp8 control's at least 0.16;
# the widest gap is not compared: a routing flip under bfloat16 (group-
# limited, weights x 16) reads up to 2.4 in sound runs, against a
# control's 1.6
LIMITS = {"served_mean_gap": 0.07}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"), LIMITS)


def test_reference_shapes_and_held_picks():
    import jax

    from bench import deepseek_v2 as ds
    from bench.reference import deepseek_v2 as ref
    cj = tiny_config()
    cfg = ds.model_config(cj, 8)
    w = ds.reference_weights(ds.make_params(cfg, 3), cj)
    assert w["moe"]["e_gate"].shape == (2, 4, 64, 32)      # 2 layers x held
    assert w["moe"]["router"].shape == (2, 64, 16)         # all 16 scored
    assert w["lm_head"].shape == (64, 384)
    toks = np.arange(50) % 384
    lg = ref.logits(w, cj, toks, np.array([0, 49]))
    assert lg.shape == (2, 384) and bool(jax.numpy.isfinite(lg).all())
    picks = np.asarray(ref.held_picks(w, cj, toks))
    # 2 MoE layers x 3 picks a token at most; some land here, some not
    assert picks.shape == (50,) and 0 < picks.sum() < 50 * 6


def test_cell_runs_correct_and_reads_its_metrics(root):
    rc, res = util.run_cell(root, cell=CELL, trace=1, seconds=3.0)
    assert rc == 0 and res["correct"] is True, res
    m = res["metrics"]
    assert 0 < m["expert_useful_share"]["value"] < 100
    # the CPU has no peaks: shares of a peak read NaN here
    assert {"admit_ms", "decode_step_ms", "prefill_useful_share",
            "mfu.decode"} <= set(m)
    # interpret mode on the CPU: the kernel's name is not in the trace
    assert "mla_decode_roofline" not in m
    rc, res = util.run_cell(root, cell=CELL, trace=0, seconds=3.0)
    assert rc == 0 and res["correct"] is True, res
    assert set(res["metrics"]) == {"ttft_p50_ms", "itl_p99_ms", "setup_s"}


def test_control_in_the_programs_place_is_not_correct(root):
    for seed in (5, 2**32 + 9):
        rc, res = util.run_cell(root, cell=CELL, seed=seed, seconds=2.0)
        assert rc == 0 and res["correct"] is True, res["checks"]
        rc, res = util.run_cell(root, cell=CELL, seed=seed, seconds=2.0,
                                control=True)
        assert rc == 0 and res["correct"] is False, res["checks"]


def test_a_program_without_the_fields_fails_at_set_up(monkeypatch):
    """A program whose ``ModelConfig`` has no held-expert, routing or YaRN
    field (the parent's) fails in the driver's first step, before any
    weight is drawn."""
    import dataclasses

    import repro.configs
    from bench import deepseek_v2 as ds

    @dataclasses.dataclass(frozen=True)
    class Older:                       # the fields the parent has
        name: str = "deepseek-v2-236b"
        num_layers: int = 60
        num_experts: int = 160
        cache_layout: str = "dense"
        page_size: int = 128
        dtype: str = "bfloat16"
        param_dtype: str = "float32"

    monkeypatch.setattr(repro.configs, "get_config", lambda name: Older())
    with pytest.raises(TypeError):
        ds.model_config(tiny_config(), 8)


def _span(attrs):
    return types.SimpleNamespace(attrs=attrs)


def test_expert_useful_share_reader(monkeypatch):
    from bench import program_spans
    reader = util.REPO / "bench/metrics/expert_useful_share.py"
    from bench.harness import load_module
    mod = load_module(reader, "m_eus")
    win = program_spans.Window(rounds=[], queue=[], steps=[
        (None, [_span({"picks_here": 3, "expert_rows": 80})]),
        (None, [_span({"picks_here": 5, "expert_rows": 80})])])
    monkeypatch.setattr(program_spans, "window", lambda run: win)
    assert mod.read(None) == pytest.approx(100 * 8 / 160)
    win.steps = [(None, [_span({})])]           # a program without counts
    assert mod.read(None) is None


def test_mla_decode_roofline_reader():
    from bench import flops_deepseek_v2
    from bench.harness import load_module
    mod = load_module(util.REPO / "bench/metrics/mla_decode_roofline.py",
                      "m_mla")
    c = json.loads((util.REPO / "bench/configs/deepseek-v2-236b.json")
                   .read_text())
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    steps = [(0.0, 1.0, [6999, 7200]), (1.0, 2.0, [7000, 7201, 300])]
    fl = 0.0
    for *_, pos in steps:
        f, b = flops_deepseek_v2.mla_decode_cost(c, pos, 256)
        fl += max(f / 197e12, b / 819e9)
    least = fl * 6
    run = types.SimpleNamespace(
        reduced=types.SimpleNamespace(
            kernel_s=lambda pat: 2 * least if pat == "mla_paged_decode"
            else 0.0),
        steps=steps, traced=lambda a, b: True, peak=peak,
        cell=types.SimpleNamespace(config=c,
                                   traffic={"page_size": 256}),
        log=lambda msg: None)
    assert mod.read(run) == pytest.approx(50.0)
    # at 7k context, 128 heads over a 576-wide latent sit near the ridge
    f, b = flops_deepseek_v2.mla_decode_cost(c, [7000], 256)
    assert 200 < f / b < 260
    run.reduced = None
    assert mod.read(run) is None
