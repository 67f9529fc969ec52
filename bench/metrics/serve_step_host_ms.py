"""Median host time of one decode-step call in the serving loop: the
program's spans ``repro.serve.step`` (``launch/serve.py::generate``) inside
the traced window (``bench/layers.py``)."""
from bench import layers


def read(ctx):
    return layers.METRICS["serve_step_host_ms"](ctx.trace)
