"""The benchmark's correctness check has to fail when it should: a run
with ``--control`` (the reference computed in float8 in the program's
place) comes out not correct where the same run of the program is correct,
and so does every fault planted under the harness's timed path.  On the CPU
at small widths; the same runs at the cells' own sizes on the chip are in
PERF.md."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_faults  # noqa: E402
import bench_tiny  # noqa: E402
from bench import cells, check  # noqa: E402

WORKLOADS = [w["name"] for w in cells.benchmark()["workloads"]]

# Wide enough that float8 rounding moves the logits by more than the
# limits allow, small enough for the CPU.
CONTROL_SIZE = {
    "model": {"d_model": 256, "n_heads": 4, "n_kv_heads": 4, "head_dim": 64,
              "vocab_size": 2048},
    "moe": {"n_experts": 16, "top_k": 4, "d_expert": 128, "d_shared": 256},
    "config": {"hidden_size": 256, "num_attention_heads": 4,
               "num_key_value_heads": 4, "n_routed_experts": 16,
               "num_experts_per_tok": 4, "moe_intermediate_size": 128,
               "vocab_size": 2048},
    "traffic": {"batch": 8, "prompt": 16, "gen": 16},
    "check": {"requests": 8},
    "peak": bench_tiny.TINY["peak"],
}


def test_gap_numbers_by_hand():
    ref = __import__("numpy").array([[[1.0, 3.0, 2.0], [0.5, 0.0, 0.25]]])
    g = check.gaps(ref, __import__("numpy").array([[2, 0]]))
    assert g.tolist() == [[1.0, 0.0]]
    n = check.numbers(g)
    assert n == {"max_logit_gap": 1.0, "mean_logit_gap": 0.5,
                 "not_first_share": 0.5}


@pytest.mark.parametrize("seed", [7, 2**32 + 7, 3_000_000_001])
@pytest.mark.parametrize("name", WORKLOADS)
def test_control_run_is_not_correct_where_the_program_is(name, seed):
    runs = {}
    for control in (False, True):
        rc, res = bench_tiny.run_tiny(name, seed=seed, seconds=0.2,
                                      size=CONTROL_SIZE, control=control)
        assert rc == 0
        runs[control] = res
    assert runs[False]["correct"] is True, runs[False]["checks"]
    assert runs[True]["correct"] is False, runs[True]["checks"]


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_batch"])
@pytest.mark.parametrize("name", WORKLOADS)
def test_planted_fault_comes_out_not_correct(name, fault, monkeypatch):
    bench_faults.plant(fault, setattr=monkeypatch.setattr)
    rc, res = bench_tiny.run_tiny(name)
    assert rc == 0 and res["correct"] is False, res["checks"]
