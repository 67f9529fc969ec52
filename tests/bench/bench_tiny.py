"""Helpers for the benchmark's tests: each cell at small widths, driven
through the harness on the CPU (``run_cell(..., require_tpu=False)``).  A
cell on more than one chip runs in a child process with as many CPU
devices, since this process sees one."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
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


RESULT = "bench_tiny results: "


def _run(cell_name, seed, seconds, trace, size, control):
    from bench import run

    argv = ["--workload", cell_name, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace)]
    args = run.parse(argv + (["--control"] if control else []))
    return run.run_cell(args, require_tpu=False,
                        override=json.loads(json.dumps(size or TINY)))


def runs_tiny(cell_name: str, controls=(False,), seed: int = 1234567890123,
              seconds: float = 0.5, trace: int = 0, size=None, fault=None,
              setattr=setattr) -> list:
    """One run of the cell at ``size`` (default :data:`TINY`) for each of
    ``controls`` (whether it runs ``--control``), each a ``(exit code,
    result)``, with the fault ``fault`` of ``bench_faults`` planted by
    ``setattr`` (in the child, where the cell takes more than one chip)."""
    from bench import cells

    chips = cells.resolve(cell_name).chips
    if chips > 1:
        return _in_child(chips, cell_name, list(controls), seed, seconds,
                         trace, size, fault)
    if fault:
        import bench_faults

        bench_faults.plant(fault, setattr=setattr)
    return [_run(cell_name, seed, seconds, trace, size, c) for c in controls]


def run_tiny(cell_name: str, seed: int = 1234567890123, seconds: float = 0.5,
             trace: int = 0, size=None, control: bool = False, fault=None,
             setattr=setattr):
    """One run of the cell, as :func:`runs_tiny`."""
    return runs_tiny(cell_name, (control,), seed, seconds, trace, size,
                     fault, setattr)[0]


def _in_child(chips, cell_name, controls, seed, seconds, trace, size, fault):
    """:func:`runs_tiny` in a child process with ``chips`` CPU devices; its
    standard error is passed on to this process's."""
    script = (
        "import json, sys\n"
        f"sys.path.insert(0, {str(HERE)!r})\n"
        "import bench_faults, bench_tiny\n"
        f"fault = {fault!r}\n"
        "if fault:\n"
        "    bench_faults.plant(fault)\n"
        f"out = [bench_tiny._run({cell_name!r}, {seed!r}, {seconds!r}, "
        f"{trace!r}, {json.loads(json.dumps(size))!r}, c) "
        f"for c in {controls!r}]\n"
        f"print({RESULT!r} + json.dumps(out), flush=True)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=str(REPO))
    sys.stderr.write(proc.stderr)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith(RESULT)]
    if proc.returncode != 0 or not lines:
        raise AssertionError(f"child run failed ({proc.returncode}):\n"
                             f"{proc.stdout[-4000:]}\n{proc.stderr[-4000:]}")
    return [tuple(r) for r in json.loads(lines[-1][len(RESULT):])]

