"""A checkout with one more cell, made of data files only: a tiny copy
of a configuration, a tiny copy of a mix, its limits, and the entries
in ``BENCHMARK.json``.  The files that are there are not edited."""
from __future__ import annotations

import json
import shutil
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
CELL = "tiny.tinymix"

TINY = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, vocab_size=503)


def tiny_config(moe: bool) -> dict:
    if moe:
        c = json.loads((REPO / "bench/configs/granite-moe-1b-a400m.json")
                       .read_text())
        c.update(TINY, intermediate_size=32, num_local_experts=4,
                 num_experts_per_tok=2, attention_multiplier=0.25)
    else:
        c = json.loads((REPO / "bench/configs/qwen3-0.6b.json").read_text())
        c.update(TINY, intermediate_size=128)
    c["name"] = "tiny"
    return c


def tiny_mix(rate: float = 20.0) -> dict:
    t = json.loads((REPO / "bench/traffic/chat.json").read_text())
    t.update(rate_per_s=rate, preroll_s=0.5, slots=4, page_size=8,
             page_budget=0, prompt_cap=64, output_cap=16)
    t["sessions"].update(system_prompt_tokens=16, assistant_tokens=[4, 8])
    t["user_tokens"].update(median=12, min=4, max=40)
    t["output_tokens"].update(median=6, min=2, max=16)
    return t


def make_root(tmp: Path, moe: bool = False, limits: dict = None,
              extra_metric: dict = None) -> Path:
    root = Path(tmp)
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    (root / "bench/configs/tiny.json").write_text(
        json.dumps(tiny_config(moe)))
    (root / "bench/traffic/tinymix.json").write_text(json.dumps(tiny_mix()))
    (root / f"bench/limits/{CELL}.json").write_text(
        json.dumps({"limits": limits or {"served_gap": 0.05}}))
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "tiny"})
    b["workloads"].append({"name": CELL, "config": "tiny",
                           "traffic": "tinymix", "chips": 1, "why": "tiny"})
    # the tiny cell reports what the chat cell reports
    for m in b["end_to_end"] + b["per_layer"]:
        if "qwen3-0.6b.chat" in m.get("workloads", []):
            m["workloads"].append(CELL)
    if extra_metric is not None:
        b["per_layer"].append(extra_metric)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


def run_cell(root: Path, seed: int = 2**33 + 5, seconds: float = 2.0,
             trace: int = 0, require_tpu: bool = False, cell: str = CELL,
             control: bool = False):
    """One run of the tiny cell in this process; (exit code, result).
    ``control`` puts the fp8 control in the program's place."""
    import contextlib
    import io

    from bench.harness import main
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=str(root),
                  start=time.perf_counter(), require_tpu=require_tpu,
                  control=control)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if rc == 0 and lines else None)
