"""The cell's serving window: closed-loop static batches through the
program's serving entry (``repro.launch.serve``), unchanged.

A round is ``batch`` requests of ``prompt`` tokens each, drawn from the
seed, and ``gen`` greedy tokens.  Rounds run back to back until the window's
seconds have passed; the last round that started inside the window
completes and counts.  Every request of a round is due at the round's
start and done when its round's tokens are on the host.

Nothing is traced or compiled inside the window: the round's set-up (its
prompts and an empty KV cache) runs programs compiled before it, and the
window counts JAX's trace and compile events to show it.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding

from bench import weights

# JAX's monitoring events of a trace, and of a compile or a load from the
# persistent compile cache
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")
PHASES = ("setup", "generate", "wait")


@dataclass
class Round:
    index: int
    start: float            # host clock: due time of the round's requests
    end: float              # host clock: all tokens on the host
    tokens: object          # (batch, gen) int32, on the host
    phases: dict = field(default_factory=dict)  # host seconds per PHASES


@dataclass
class Window:
    start: float
    end: float
    rounds: list = field(default_factory=list)
    compile_events: int = 0     # COMPILE_EVENTS recorded inside the window

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Server:
    """The cell's compiled programs, weights and prompt stream on one
    chip, or on the mesh of ``dist`` (a ``DistCtx``), where the KV cache is
    placed by ``cache_pspecs`` and the prompt goes through the decode step
    (``serve.compile_steps`` builds no prefill on a model-axis mesh)."""

    def __init__(self, cfg, params, traffic: dict, seed: int, dist=None):
        from repro.distributed.sharding import cache_pspecs
        from repro.launch import serve
        from repro.models import model_zoo as Z

        self.serve, self.cfg, self.params = serve, cfg, params
        self.batch = int(traffic["batch"])
        self.prompt = int(traffic["prompt"])
        self.gen = int(traffic["gen"])
        self.max_len = self.prompt + self.gen
        self.key = jax.random.fold_in(weights.seed_key(seed), 1)
        # the initialiser that serve.new_cache wraps, compiled once:
        # new_cache jits it afresh on every call, which would trace and
        # compile it (or load it from the compile cache) in every round
        init = partial(Z.init_cache, cfg, self.batch, self.max_len,
                       jnp.dtype(cfg.dtype))
        out = None
        if dist is not None:
            specs = cache_pspecs(cfg, dist, jax.eval_shape(init), self.batch)
            out = jax.tree.map(lambda s: NamedSharding(dist.mesh, s), specs)
        self.new_cache = jax.jit(init, out_shardings=out).lower().compile()
        self.prefill, self.step = serve.compile_steps(
            cfg, dist, params, self.new_cache(), self.prompts(-1))

    def prompts(self, index: int):
        return weights.prompts(self.key, index, self.batch, self.prompt,
                               self.cfg.vocab_size)

    def round(self, index: int) -> Round:
        import numpy as np

        marks = [time.perf_counter()]
        with TraceAnnotation("bench.round", index=index):
            with TraceAnnotation("bench.round.setup"):
                prompts, cache = self.prompts(index), self.new_cache()
            marks.append(time.perf_counter())
            with TraceAnnotation("bench.round.generate"):
                tokens, logits = self.serve.generate(
                    self.cfg, self.prefill, self.step, self.params, cache,
                    prompts, self.gen)
            marks.append(time.perf_counter())
            with TraceAnnotation("bench.round.wait"):
                tokens = np.asarray(jax.block_until_ready(tokens))
                jax.block_until_ready(logits)
                del logits
            marks.append(time.perf_counter())
        phases = {p: b - a for p, a, b in zip(PHASES, marks, marks[1:])}
        return Round(index, marks[0], marks[-1], tokens, phases)

    def probe(self) -> None:
        """One call of each compiled program outside the window, each inside
        a host span ``bench.probe.<role>``, so that a trace tells the
        programs apart (``bench/trace.py``)."""
        prompts = self.prompts(-2)
        with TraceAnnotation("bench.probe.decode_step"):
            jax.block_until_ready(self.step(self.params, self.new_cache(),
                                            prompts[:, :1], jnp.int32(0)))
        if self.prefill is not None:
            with TraceAnnotation("bench.probe.prefill"):
                jax.block_until_ready(self.prefill(
                    self.params, self.new_cache(), prompts))

    def run(self, seconds: float, rounds=None) -> Window:
        """Rounds back to back for ``seconds``, or until ``rounds`` have
        completed if that comes first; the last one that started inside
        the window completes."""
        win = Window(start=time.perf_counter(), end=0.0)

        def count(event, *_, **__):
            win.compile_events += event in COMPILE_EVENTS

        jax.monitoring.register_event_duration_secs_listener(count)
        try:
            index = 0
            while True:
                win.rounds.append(self.round(index))
                index += 1
                if (win.rounds[-1].end - win.start >= seconds
                        or len(win.rounds) == rounds):
                    break
        finally:
            jax.monitoring.unregister_event_duration_listener(count)
        win.end = win.rounds[-1].end
        return win

    def free(self) -> None:
        """Drop the compiled programs (the caches were donated)."""
        self.prefill = self.step = self.new_cache = None


def latency_p95(win: Window, batch: int) -> float:
    """95th percentile over every request of the window of its due-to-done
    time (``batch`` requests per round, nearest-rank)."""
    import math

    lat = sorted(r.end - r.start for r in win.rounds for _ in range(batch))
    return lat[max(0, math.ceil(0.95 * len(lat)) - 1)]


def served_tokens(win: Window, batch: int, gen: int) -> int:
    return len(win.rounds) * batch * gen


def sample_requests(win: Window, batch: int, n: int, seed: int) -> list:
    """``n`` (round index, row) pairs drawn from the seed among the
    window's requests, without repeats."""
    import numpy as np

    rng = np.random.default_rng([int(seed) % (1 << 64), 2])
    total = len(win.rounds) * batch
    picks = rng.choice(total, size=min(n, total), replace=False)
    return sorted((win.rounds[p // batch].index, int(p % batch))
                  for p in picks)


