"""Reduction of a profiler trace to what the per-layer metrics read.

The JAX profiler writes an XSpace (``*.xplane.pb``).  Each TPU has a plane
``/device:TPU:<n>`` with a line ``XLA Modules`` (one event per call of a
compiled program, named ``jit_<function>(<id>)``) and a line ``XLA Ops``
(one event per operation).  The benchmark's own host spans
(``bench.round`` and its parts, from ``jax.profiler.TraceAnnotation``) are
on the host plane, on the same clock.  The traced window runs from the
start of the first ``bench.round`` span to the end of the last.

Programs are told apart by the calls the harness makes of each outside the
window, inside a host span ``bench.probe.<role>``: the module that runs for
the longest part of that span is the program of that role
(``decode_step``, ``prefill``), whatever name the program gave it.  (The
device's clock reads up to about a millisecond early against the host's,
so a module may start just before the span that dispatched it, and the
next probe's module may start just before this span ends.)  Operation events carry
the HLO instruction's text; an op is named by the text before `` = ``.
"""
from __future__ import annotations

import glob
import gzip
import re
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ROUND_SPAN = "bench.round"
SPAN_PREFIX = "bench."
PROBE_PREFIX = "bench.probe."


@dataclass
class Device:
    ops: list = field(default_factory=list)       # (name, start_ns, end_ns)
    modules: list = field(default_factory=list)   # (name, start_ns, end_ns)


@dataclass
class Trace:
    devices: dict                                  # device index -> Device
    spans: list                                    # (name, start_ns, end_ns)

    @property
    def window(self) -> tuple:
        rounds = [s for s in self.spans if s[0] == ROUND_SPAN]
        if not rounds:
            raise ValueError("the trace holds no bench.round span")
        return min(s[1] for s in rounds), max(s[2] for s in rounds)

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[64,2048]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.12 bf16[64,2048]``: the instruction's name and result shape."""
    name, _, rest = text.partition(" = ")
    shape = re.match(r"[^{ ]*", rest).group(0) if rest else ""
    return f"{name.lstrip('%')} {shape}".strip()


def _events(line, rename=None):
    names = {}
    for e in line.events:
        name = e.name
        if rename:
            if name not in names:
                names[name] = rename(name)
            name = names[name]
        yield name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def from_xspace(data: bytes) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(data)
    devices, spans = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = Device()
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops.extend(_events(line, op_name))
                elif line.name == MODULES_LINE:
                    dev.modules.extend(_events(line))
            devices[int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(s for s in _events(line)
                             if s[0].startswith(SPAN_PREFIX))
    return Trace(devices=devices, spans=sorted(spans, key=lambda s: s[1]))


def load(path) -> Trace:
    """A trace from an ``.xplane.pb`` file (optionally gzipped), or from
    the one file under a profiler output directory."""
    path = Path(path)
    if path.is_dir():
        found = glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True)
        if len(found) != 1:
            raise ValueError(f"expected one .xplane.pb under {path}, found "
                             f"{len(found)}")
        path = Path(found[0])
    data = path.read_bytes()
    if path.suffix == ".gz":
        data = gzip.decompress(data)
    return from_xspace(data)


def clip(intervals, lo: float, hi: float) -> list:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in intervals
            if e > lo and s < hi]


def union_ns(intervals) -> float:
    """Length of the union of the (name, start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for _, s, e in sorted(intervals, key=lambda iv: iv[1]):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(trace: Trace) -> dict:
    """Per device: seconds of the window in which an operation ran."""
    lo, hi = trace.window
    return {i: union_ns(clip(d.ops, lo, hi)) * 1e-9
            for i, d in trace.devices.items()}


def program(trace: Trace, role: str, device: int = None):
    """The module name of the program the harness probed as ``role``: the
    module with the longest overlap with the probe's span.  None where the
    trace holds no such probe."""
    if not trace.devices:
        return None
    if device is None:
        device = min(trace.devices)
    for name, lo, hi in trace.spans:
        if name == PROBE_PREFIX + role:
            over = clip(trace.devices[device].modules, lo, hi)
            if over:
                return max(over, key=lambda m: m[2] - m[1])[0]
    return None


def module_calls(trace: Trace, device: int, module: str) -> list:
    """Device seconds of each call, inside the window, of ``module``."""
    lo, hi = trace.window
    return [(e - s) * 1e-9 for n, s, e in trace.devices[device].modules
            if n == module and s >= lo and e <= hi]


def leaves(ops) -> list:
    """The ops of some duration that hold no other (a ``while`` holds its
    body's ops).  An op of no duration holds nothing and is left out: one
    that starts where a long op starts would make that op read as its
    body."""
    ops = sorted((o for o in ops if o[2] > o[1]), key=lambda o: (o[1], -o[2]))
    out = []
    for i, (n, s, e) in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if not (nxt and nxt[1] < e and nxt[2] <= e):
            out.append((n, s, e))
    return out


def ops_within(trace: Trace, device: int, module: str, op_pattern) -> list:
    """Leaf operations on ``device`` that match ``op_pattern`` (a compiled
    regex, searched in the op's name) and lie inside a call of ``module``,
    inside the window."""
    lo, hi = trace.window
    mods = sorted((s, e) for n, s, e in trace.devices[device].modules
                  if n == module and s >= lo and e <= hi)
    out, j = [], 0
    for n, s, e in leaves(trace.devices[device].ops):
        while j < len(mods) and mods[j][1] < s:
            j += 1
        if j < len(mods) and mods[j][0] <= s and e <= mods[j][1] \
                and op_pattern.search(n):
            out.append((n, s, e))
    return out


def breakdown(trace: Trace, device: int = None, top: int = 10) -> dict:
    """The device operations that took most time (leaf ops, summed over
    the calls of each) and the longest idle gaps with the innermost host span
    running over each gap's middle, on the first device of the trace."""
    if not trace.devices:
        return {"device_ops": [], "idle_gaps": []}
    if device is None:
        device = min(trace.devices)
    lo, hi = trace.window
    ops = clip(trace.devices[device].ops, lo, hi)
    per = {}
    for n, s, e in leaves(ops):
        per[n] = per.get(n, 0.0) + (e - s) * 1e-9
    device_ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    gaps, cur = [], lo
    for _, s, e in sorted(ops, key=lambda o: o[1]):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    idle = []
    for s, e in gaps:
        mid = (s + e) / 2
        over = [sp for sp in trace.spans if sp[1] <= mid <= sp[2]]
        name = min(over, key=lambda sp: sp[2] - sp[1])[0] if over else "none"
        idle.append([name, (e - s) * 1e-9])
    return {"device_ops": [[n, v] for n, v in device_ops],
            "idle_gaps": idle}
