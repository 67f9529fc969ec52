"""The program's layer scopes and serve-loop spans in a profiler trace, and
the per-layer times read from them.

  python3 bench/run.py --workload moonlight.decode --seed 7 --seconds 51 \\
      --trace 1 --trace-dir DIR
  python3 bench/layers.py DIR

The program names its jitted steps (``jit_decode_step``, ``jit_prefill``)
and wraps each layer in a ``jax.named_scope`` (``LAYER_SCOPES``).  The
scope reaches the trace as the op's JAX path, the stat ``tf_op`` of the
op's event metadata (for example
``jit(decode_step)/while/body/closed_call/attention/dot_general:``), next
to ``program_id``, the number in the module's name.  ``ProfileData`` does
not expose event metadata, so ``op_paths`` reads it from the serialized
XSpace itself; an op's layer is the innermost layer scope on its path.
The serve loop's host spans (``repro.serve.*``, ``repro.host.gc``:
``launch/tracing.py``) are on the host plane, on the same clock as the
harness's ``bench.*`` spans.

``load`` gives a ``trace.Trace`` that holds both, so ``trace.breakdown``
of it names an idle gap by the program's innermost span.  ``bench/run.py``
loads its traces with it; the readers ``bench/metrics/<name>.py`` of the
per-layer metrics named in ``METRICS`` return its readings, and this
module's command prints them all for a kept trace.
"""
from __future__ import annotations

import glob
import gzip
import json
import re
import statistics
import sys
from dataclasses import dataclass, field
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace  # noqa: E402

SPAN_PREFIX = "repro."
# the jax.named_scope names of the program's layers (models/, core/)
LAYER_SCOPES = ("cast", "embed", "attention", "mamba", "moe.router",
                "moe.dispatch", "moe.experts", "moe.combine", "moe.shared",
                "lm_head")


@dataclass
class ScopedTrace(trace.Trace):
    # device index -> {(program id, op name): JAX path}
    paths: dict = field(default_factory=dict)
    memo: dict = field(default_factory=dict, repr=False, compare=False)


def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a serialized protobuf
    message: an int for a varint, a memoryview for a length-delimited field
    (a string, bytes or a message); fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind} in a trace")
        yield key >> 3, value


def op_paths(data: bytes) -> dict:
    """Per TPU of an XSpace: ``(program id, op name) -> JAX path`` of every
    op whose event metadata carries a ``tf_op`` stat.

    Field numbers of ``xplane.proto``: XSpace.planes 1; XPlane.name 2,
    .event_metadata 4 and .stat_metadata 5 (maps: key 1, value 2);
    XEventMetadata.name 2, .stats 5; XStat.metadata_id 1, .int64_value 4,
    .uint64_value 3, .str_value 5, .ref_value 7; XStatMetadata.name 2.
    The planes' lines (field 3) are skipped unread."""
    def text(v) -> str:
        return bytes(v).decode(errors="replace")

    out = {}
    for field_no, plane in _fields(memoryview(data)):
        if field_no != 1:
            continue
        name, events, stat_names = None, [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = text(v)
            elif f in (4, 5):
                entry = dict(_fields(v))
                if f == 4:
                    events.append(entry.get(2, b""))
                else:
                    meta = dict(_fields(entry.get(2, b"")))
                    stat_names[entry.get(1, 0)] = text(meta.get(2, b""))
        m = trace.DEVICE_PLANE.match(name or "")
        if not m:
            continue
        paths = out.setdefault(int(m.group(1)), {})
        for meta in events:
            op, stats = None, {}
            for f, v in _fields(meta):
                if f == 2:
                    op = trace.op_name(text(v))
                elif f == 5:
                    stat = dict(_fields(v))
                    key = stat_names.get(stat.get(1))
                    if 7 in stat:
                        stats[key] = stat_names.get(stat[7])
                    elif 5 in stat:
                        stats[key] = text(stat[5])
                    else:
                        stats[key] = stat.get(4, stat.get(3))
            if op and "tf_op" in stats and "program_id" in stats:
                paths[(stats["program_id"], op)] = stats["tf_op"].rstrip(":")
    return out


def from_xspace(data: bytes) -> ScopedTrace:
    """``trace.from_xspace`` with the ops' paths and the program's spans."""
    from jax.profiler import ProfileData

    base = trace.from_xspace(data)
    spans = list(base.spans)
    for plane in ProfileData.from_serialized_xspace(data).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(s for s in trace._events(line)
                             if s[0].startswith(SPAN_PREFIX))
    return ScopedTrace(devices=base.devices,
                       spans=sorted(spans, key=lambda s: s[1]),
                       paths=op_paths(data))


def load(path) -> ScopedTrace:
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


def layer_scope(path: str):
    """The innermost of ``LAYER_SCOPES`` on an op's JAX path, or None."""
    for part in reversed(path.split("/")):
        if part in LAYER_SCOPES:
            return part
    return None


def named_calls(tr: trace.Trace, device: int, function: str) -> list:
    """(program id, start_ns, end_ns) of each call, inside the window, of
    the program that ``jax.jit`` named after ``function``
    (``jit_<function>(<program id>)``), in order."""
    lo, hi = tr.window
    pattern = re.compile(rf"jit_{re.escape(function)}\((\d+)\)")
    out = []
    for n, s, e in tr.devices[device].modules:
        m = pattern.fullmatch(n)
        if m and s >= lo and e <= hi:
            out.append((int(m.group(1)), s, e))
    return sorted(out, key=lambda c: c[1])


def _leaves(tr: ScopedTrace, device: int) -> list:
    if ("leaves", device) not in tr.memo:
        tr.memo[("leaves", device)] = trace.leaves(tr.devices[device].ops)
    return tr.memo[("leaves", device)]


def scope_seconds(tr: ScopedTrace, device: int, function: str) -> list:
    """Per call, inside the window, of ``jit_<function>``: the seconds of
    its leaf ops by layer scope, ``{scope: s}`` (``None`` for ops under no
    layer scope).  Empty where no op of any call carries a layer scope (a
    program without ``named_scope``s, or a trace without op paths)."""
    key = ("scope_seconds", device, function)
    if key not in tr.memo:
        paths = tr.paths.get(device, {})
        calls = named_calls(tr, device, function)
        per = [{} for _ in calls]
        scoped, j = False, 0
        for n, s, e in _leaves(tr, device):
            while j < len(calls) and calls[j][2] < s:
                j += 1
            if j == len(calls):
                break
            program, lo, hi = calls[j]
            if lo <= s and e <= hi:
                scope = layer_scope(paths.get((program, n), ""))
                scoped |= scope is not None
                per[j][scope] = per[j].get(scope, 0.0) + (e - s) * 1e-9
        tr.memo[key] = per if scoped else []
    return tr.memo[key]


def scope_ms(tr: ScopedTrace, function: str, select):
    """Median over the calls of ``jit_<function>`` of the leaf-op time
    under the layer scopes that ``select`` accepts (``None`` stands for no
    scope), in ms, the mean over the chips.  None where the trace holds no
    scoped call of the program, or no time under the selected scopes."""
    per_chip = []
    for d in tr.devices:
        calls = scope_seconds(tr, d, function)
        if calls:
            per_chip.append(statistics.median(
                sum(v for k, v in c.items() if select(k)) for c in calls))
    if not any(per_chip):
        return None
    return 1e3 * sum(per_chip) / len(per_chip)


def op_seconds(tr: ScopedTrace, device: int, function: str,
               prefix: str) -> list:
    """Device seconds of each leaf op whose HLO name starts with
    ``prefix`` (``grouped_swiglu_pallas``: each call of that kernel) inside
    the calls, in the window, of ``jit_<function>``."""
    calls = named_calls(tr, device, function)
    out, j = [], 0
    for n, s, e in _leaves(tr, device):
        while j < len(calls) and calls[j][2] < s:
            j += 1
        if j == len(calls):
            break
        if calls[j][1] <= s and e <= calls[j][2] and n.startswith(prefix):
            out.append((e - s) * 1e-9)
    return out


def op_ms(tr: ScopedTrace, function: str, prefix: str):
    """Median over the ops of :func:`op_seconds`, in ms, the mean over the
    chips that ran any.  None where none ran."""
    per_chip = [statistics.median(ops) for ops in (
        op_seconds(tr, d, function, prefix) for d in tr.devices) if ops]
    if not per_chip:
        return None
    return 1e3 * sum(per_chip) / len(per_chip)


def span_seconds(tr: trace.Trace, name: str) -> list:
    """Host seconds of each span ``name`` inside the window."""
    lo, hi = tr.window
    return [(e - s) * 1e-9 for n, s, e in tr.spans
            if n == name and s >= lo and e <= hi]


def _is_moe(scope) -> bool:
    return scope is not None and scope.startswith("moe.")


def serve_step_host_ms(tr: trace.Trace):
    """Median host time of one decode-step call in the serving loop (the
    spans ``repro.serve.step`` of ``launch/serve.py::generate``)."""
    steps = span_seconds(tr, "repro.serve.step")
    return 1e3 * statistics.median(steps) if steps else None


# Per-layer times in ms, each None where the trace holds nothing to read:
# the device time per call of the decode step (``jit_decode_step``) under
# the weight cast, attention (``ln1`` through ``wo`` with the KV-cache
# write), the MoE layer's scopes and no layer scope (the layer scan's own
# copies and slices, norms and residual adds between the layers); the same
# of the batched prefill (``jit_prefill``) under the MoE layer's scopes;
# the host time of a decode-step call; the decode step's time under the EP
# exchanges (``moe.dispatch``, ``moe.combine``: the all-to-alls and their
# packing and combining) and of one call of the grouped expert kernel.
METRICS = {
    "decode_cast_ms": lambda tr: scope_ms(tr, "decode_step",
                                          lambda s: s == "cast"),
    "decode_attention_ms": lambda tr: scope_ms(tr, "decode_step",
                                               lambda s: s == "attention"),
    "decode_moe_ms": lambda tr: scope_ms(tr, "decode_step", _is_moe),
    "decode_unscoped_ms": lambda tr: scope_ms(tr, "decode_step",
                                              lambda s: s is None),
    "prefill_moe_ms": lambda tr: scope_ms(tr, "prefill", _is_moe),
    "serve_step_host_ms": serve_step_host_ms,
    "a2a_ms_per_step": lambda tr: scope_ms(
        tr, "decode_step", lambda s: s in ("moe.dispatch", "moe.combine")),
    "expert_kernel_ms": lambda tr: op_ms(tr, "decode_step",
                                         "grouped_swiglu_pallas"),
}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 bench/layers.py TRACE_DIR_OR_FILE",
              file=sys.stderr)
        return 2
    tr = load(argv[0])
    out = {name: read(tr) for name, read in METRICS.items()}
    out["breakdown"] = trace.breakdown(tr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
