"""The control, put in the program's place, comes out not correct.

At a size a test run holds, each kind of cell runs through the harness
twice on the same seed: once as the benchmark runs it, which must come
out correct, and once with the fp8 reference's first tokens standing in
for the served ones, which must not.  The limits are held as each cell
kind holds them: the dense configuration compares the widest and the
mean served-token gap, the routed-expert one only the mean (a routing
flip under bfloat16 makes its widest gap swing).  The tiny limits lie
between the tiny program's largest readings over five seeds (dense:
widest 0.012, mean 0.00017; routed: mean 0.0036) and the tiny control's
smallest (dense: 0.063 and 0.0051; routed: mean 0.013).
On the chip the same comparison runs at each cell's own size
(``bench/tools.py control``)."""
from __future__ import annotations

import pytest

from bench.tests import util

LIMITS = {"qwen3": {"served_gap": 0.03, "served_mean_gap": 0.001},
          "granite": {"served_mean_gap": 0.007}}


@pytest.mark.parametrize("kind", ["qwen3", "granite"])
def test_control_in_the_programs_place_is_not_correct(tmp_path, kind):
    root = util.make_root(tmp_path, moe=kind == "granite",
                          limits=LIMITS[kind])
    for seed in (7, 2**32 + 3):
        rc, res = util.run_cell(root, seed=seed)
        assert rc == 0 and res["correct"] is True, res["checks"]
        rc, res = util.run_cell(root, seed=seed, control=True)
        assert rc == 0 and res["correct"] is False, res["checks"]
