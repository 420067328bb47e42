"""The yardstick's arithmetic: FLOPs per token, paged-decode bytes, and
the peaks table."""
from __future__ import annotations

import json

from bench import flops
from bench.tests import util


def _cfg(name):
    return json.loads((util.REPO / f"bench/configs/{name}.json").read_text())


def test_qwen3_forward_flops():
    c = _cfg("qwen3-0.6b")
    # per layer: q,k,v,o 12582912 + SwiGLU 18874368; unembed 311164928
    assert flops.forward_flops(c, 0) == 28 * 31_457_280 + 311_164_928
    # attention: 4 * H * hd per visible key per layer
    assert flops.forward_flops(c, 100) - flops.forward_flops(c, 0) \
        == 28 * 4 * 16 * 128 * 100


def test_granite_forward_flops_count_routed_experts_only():
    c = _cfg("granite-moe-1b-a400m")
    # per layer: projections 6291456 + router 65536 + 8 of 32 experts
    # 25165824; unembed over the real 49155 rows
    assert flops.forward_flops(c, 0) == 24 * 31_522_816 + 100_669_440
    dense_sweep = 24 * 32 * 6 * 1024 * 512
    assert flops.forward_flops(c, 0) < dense_sweep


def test_paged_decode_bytes_follow_resident_pages():
    c = _cfg("qwen3-0.6b")
    fl, by = flops.paged_decode_cost(c, [0, 127, 128], 128)
    # pages 1 + 1 + 2, K and V of 8 heads x 128 in bf16, plus q and o
    assert by == 4 * 128 * 8 * 128 * 2 * 2 + 3 * 2 * 16 * 128 * 2
    assert fl == 4 * 16 * 128 * (1 + 128 + 129)
    assert flops.paged_decode_cost(c, [], 128) == (0.0, 0.0)


def test_peaks_table_is_keyed_by_device_kind():
    peaks = json.loads((util.REPO / "bench/peaks.json").read_text())
    assert "TPU v5e" in peaks["source"]
    row = peaks["devices"]["TPU v5 lite"]
    assert row["bf16_flops"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
