"""Helpers for the benchmark's tests: each cell at small widths, driven
through the harness on the CPU (``run_cell(..., require_tpu=False)``)."""
from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# every cell at small widths, sizes the reference reads set alike; computed
# in float32, so that the program and the reference agree token for token
# and a sound run reads gaps of 0
TINY = {
    "model": {"d_model": 64, "n_heads": 4, "n_kv_heads": 4, "head_dim": 16,
              "vocab_size": 512, "dtype": "float32"},
    "moe": {"n_experts": 8, "top_k": 2, "d_expert": 32, "d_shared": 64},
    "config": {"hidden_size": 64, "num_attention_heads": 4,
               "num_key_value_heads": 4, "n_routed_experts": 8,
               "num_experts": 8, "num_experts_per_tok": 2,
               "moe_intermediate_size": 32, "vocab_size": 512},
    "traffic": {"batch": 4, "prompt": 8, "gen": 4},
    "check": {"requests": 8},
    "peak": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
}


def run_tiny(cell_name: str, seed: int = 1234567890123, seconds: float = 0.5,
             trace: int = 0, size=None, control: bool = False):
    """One run of the cell at ``size`` (default :data:`TINY`)."""
    from bench import run

    argv = ["--workload", cell_name, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace)]
    args = run.parse(argv + (["--control"] if control else []))
    return run.run_cell(args, require_tpu=False,
                        override=json.loads(json.dumps(size or TINY)))

