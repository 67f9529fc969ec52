"""The per-layer readings of the program's layer scopes and host spans
(``bench/layers.py``): the event metadata decoded from a serialized trace,
each reading on a trace made by hand and on a round recorded on a TPU
v5e, and no reading on the older recording, whose program had no scopes."""
from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from bench import cells, layers, trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "moonlight_prefill_v5e.xplane.pb.gz"
# One traced round of moonlight.decode on a TPU v5e (bench/run.py --seconds
# 0 --trace 1 --trace-dir): the probes, then a 64 x 128 prefill and 127
# decode steps, with layer scopes and serve-loop spans.  Cut to what the
# readers read, to stay under 1 MB: the plane /device:TPU:0 with its lines
# "XLA Ops" and "XLA Modules" and its metadata, and of the plane /host:CPU
# only the events named bench.* or repro.*.  The expected numbers come from
# a separate reading of the file through the protobuf runtime and a schema
# of xplane.proto, in picoseconds; the harness reads nanoseconds.
SCOPED = DATA / "moonlight_decode_v5e.xplane.pb.gz"
NEW_READERS = ("decode_cast_ms", "decode_attention_ms", "decode_moe_ms",
               "decode_unscoped_ms", "prefill_moe_ms", "serve_step_host_ms")
MS = 1e6                       # ns in a millisecond


def _read(name, tr):
    if name in layers.METRICS:
        return layers.METRICS[name](tr)
    return cells.metric_reader(name).read(SimpleNamespace(trace=tr))


@pytest.mark.parametrize("path,scope", [
    ("jit(decode_step)/cast/convert_element_type", "cast"),
    ("jit(decode_step)/while/body/closed_call/attention/dot_general",
     "attention"),
    ("jit(prefill)/while/body/closed_call/moe.experts/shard_map/"
     "moe.dispatch/all_to_all", "moe.dispatch"),
    ("jit(decode_step)/while/body/closed_call/moe.shared/dot_general",
     "moe.shared"),
    ("jit(decode_step)/while/body/dynamic_slice", None),
    ("jit(<unknown>)/while/body/closed_call/attention_like/add", None),
    ("", None),
])
def test_layer_scope_is_the_innermost_named_scope(path, scope):
    assert layers.layer_scope(path) == scope


def _hand_trace():
    """Two decode-step calls, one prefill and one unnamed program, in ms.

    decode 1 (100-200): cast 20, attention 20, experts 20 + router 10,
    an op with no path 8 (unscoped), lm_head 8; decode 2 (300-420): cast
    25, attention 10, experts 30 + shared 5, unscoped 20, lm_head 5;
    prefill (500-800): experts 90, attention 50, combine 10."""
    def iv(name, lo, hi):
        return (name, lo * MS, hi * MS)

    ops = [iv("cvt.1", 100, 120), iv("while.1", 120, 190),
           iv("fusion.1", 125, 145), iv("fusion.2", 150, 170),
           iv("fusion.3", 170, 180), iv("copy.1", 180, 188),
           iv("fusion.9", 190, 198),
           iv("fusion.50", 210, 230),                # between the calls
           iv("cvt.1", 300, 325), iv("while.1", 325, 410),
           iv("fusion.1", 330, 340), iv("fusion.2", 345, 375),
           iv("fusion.4", 375, 380), iv("copy.1", 380, 400),
           iv("fusion.9", 410, 415),
           iv("fusion.7", 510, 600), iv("fusion.2", 600, 650),
           iv("fusion.8", 650, 660),
           iv("fusion.1", 860, 890)]                 # the unnamed program
    modules = [iv("jit_decode_step(7)", 100, 200),
               iv("jit_decode_step(7)", 300, 420),
               iv("jit_prefill(9)", 500, 800),
               iv("jit__unknown(5)", 850, 900)]
    step = "jit(decode_step)/while/body/closed_call/"
    paths = {(7, "cvt.1"): "jit(decode_step)/cast/convert_element_type",
             (7, "while.1"): "jit(decode_step)/while",
             (7, "fusion.1"): step + "attention/dot_general",
             (7, "fusion.2"): step + "moe.experts/td,edf->tef/dot_general",
             (7, "fusion.3"): step + "moe.router/dot_general",
             (7, "fusion.4"): step + "moe.shared/dot_general",
             (7, "fusion.9"): "jit(decode_step)/lm_head/dot_general",
             (7, "fusion.50"): step + "attention/add",
             (9, "fusion.7"): "jit(prefill)/while/body/moe.experts/mul",
             (9, "fusion.2"): "jit(prefill)/while/body/attention/mul",
             (9, "fusion.8"): "jit(prefill)/while/body/moe.combine/add",
             (5, "fusion.1"): "jit(x)/moe.experts/mul"}
    spans = [iv("bench.round", 0, 1000), iv("repro.serve.step", 95, 99),
             iv("repro.serve.step", 200, 206), iv("repro.serve.step", 420,
                                                  429),
             iv("repro.serve.step", -50, -40)]       # before the window
    return layers.ScopedTrace(
        devices={0: trace.Device(ops=ops, modules=modules)}, spans=spans,
        paths={0: paths})


def test_scope_seconds_per_call_by_hand():
    tr = _hand_trace()
    calls = layers.scope_seconds(tr, 0, "decode_step")
    ms = [{k: round(v * 1e3, 9) for k, v in c.items()} for c in calls]
    assert ms == [{"cast": 20, "attention": 20, "moe.experts": 20,
                   "moe.router": 10, None: 8, "lm_head": 8},
                  {"cast": 25, "attention": 10, "moe.experts": 30,
                   "moe.shared": 5, None: 20, "lm_head": 5}]
    assert [c[0] for c in layers.named_calls(tr, 0, "prefill")] == [9]
    assert layers.named_calls(tr, 0, "unknown") == []


@pytest.mark.parametrize("name,want", [
    ("decode_cast_ms", 22.5),            # median of 20 and 25
    ("decode_attention_ms", 15.0),       # of 20 and 10
    ("decode_moe_ms", 32.5),             # of 20 + 10 and 30 + 5
    ("decode_unscoped_ms", 14.0),        # of 8 and 20
    ("prefill_moe_ms", 100.0),           # 90 + 10
    ("serve_step_host_ms", 6.0),         # of 4, 6 and 9 in the window
])
def test_new_readers_by_hand(name, want):
    assert _read(name, _hand_trace()) == pytest.approx(want, rel=1e-9)


def test_readers_give_none_where_nothing_is_scoped():
    tr = _hand_trace()
    tr.paths.clear()
    tr.spans = [s for s in tr.spans if not s[0].startswith("repro.")]
    for name in NEW_READERS:
        assert _read(name, tr) is None, name
    empty = layers.ScopedTrace(devices={},
                               spans=[("bench.round", 0.0, 1.0)])
    for name in NEW_READERS:
        assert _read(name, empty) is None, name


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_read_nothing_in_the_recorded_trace(name):
    # recorded before the programs had names, layer scopes or spans
    assert _read(name, layers.load(RECORDED)) is None


def test_new_readers_on_a_recorded_round_with_scopes():
    tr = layers.load(SCOPED)
    want = cells.load_json(DATA / "moonlight_decode_v5e.expected.json")
    assert tr.window_s == pytest.approx(want["window_s"], rel=1e-9)
    # the probes and the programs' names agree
    assert trace.program(tr, "decode_step") == want["decode_step_module"]
    assert trace.program(tr, "prefill") == want["prefill_module"]
    assert len(layers.named_calls(tr, 0, "decode_step")) == want["decode_steps"]
    assert len(layers.named_calls(tr, 0, "prefill")) == want["prefill_calls"]
    assert len(layers.span_seconds(tr, "repro.serve.step")) == \
        want["serve_steps"]
    for name in NEW_READERS:
        assert _read(name, tr) == pytest.approx(want[name], abs=1e-3), name
    # the four decode-step metrics cover the step but its idle time
    step = _read("decode_step_ms", tr)
    four = sum(_read(name, tr) for name in NEW_READERS[:4])
    assert 0.97 * step < four <= step
    # the round's longest idle gap falls inside the program's own span
    assert trace.breakdown(tr)["idle_gaps"][0][0] == "repro.serve.stack"


def test_recorded_trace_carries_op_paths():
    tr = layers.load(RECORDED)
    paths = tr.paths[0]
    decode = 4746173099650427042          # jit__unknown(<id>) of the probe
    assert paths[(decode, "convert_element_type.70 bf16[2,64,2048,1408]")] \
        == "jit(<unknown>)/convert_element_type"
    assert paths[(decode, "compare_select_fusion.6 f32[4,16]")] == \
        "jit(<unknown>)/while/body/closed_call/jit(_where)/select_n"
    assert all(not p.endswith(":") for p in paths.values())


@pytest.mark.parametrize("path", [RECORDED, SCOPED])
def test_the_harness_metrics_read_alike_on_either_load(path):
    plain, scoped = trace.load(path), layers.load(path)
    assert scoped.devices == plain.devices
    assert [s for s in scoped.spans if s[0].startswith("bench.")] == \
        plain.spans
    for name in ("device_idle_share", "decode_step_ms", "prefill_ms"):
        assert _read(name, scoped) == _read(name, plain), name


def test_the_command_prints_every_reading(capsys):
    assert layers.main([str(SCOPED)]) == 0
    out = json.loads(capsys.readouterr().out)
    want = cells.load_json(DATA / "moonlight_decode_v5e.expected.json")
    for name in NEW_READERS:
        assert out[name] == pytest.approx(want[name], abs=1e-3), name
    assert out["breakdown"]["idle_gaps"][0][0] == "repro.serve.stack"
    assert layers.main([]) == 2


def test_an_op_of_no_duration_holds_nothing():
    # recorded on the v5e: a custom-call of no duration at the very start
    # of a 15.6-ms fusion of the prefill
    ops = [("while", 0.0, 100.0), ("fusion", 10.0, 25.0), ("cc", 10.0, 10.0),
           ("copy", 30.0, 31.0)]
    assert [n for n, _, _ in trace.leaves(ops)] == ["fusion", "copy"]


def test_breakdown_names_a_gap_by_the_innermost_program_span():
    tr = trace.Trace(
        devices={0: trace.Device(ops=[("a", 0.0, 10.0), ("b", 30.0, 40.0)])},
        spans=[("bench.round", 0.0, 40.0),
               ("bench.round.generate", 0.0, 40.0),
               ("repro.serve.step", 12.0, 25.0),
               ("repro.host.gc", 18.0, 22.0)])
    assert trace.breakdown(tr)["idle_gaps"] == [
        ["repro.host.gc", pytest.approx(20e-9)]]


# --- a serialized XSpace made by hand (xplane.proto field numbers) --------
def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    out = b""
    for no, value in fields:
        if isinstance(value, int):
            out += _varint(no << 3) + _varint(value)
        elif isinstance(value, float):          # a double: wire type 1
            out += _varint(no << 3 | 1) + b"\0" * 8
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(no << 3 | 2) + _varint(len(value)) + value
    return out


def _plane(name, events, stat_names):
    fields = [(1, 3), (2, name), (3, _msg((2, "XLA Ops"), (4, b"\x08\x01")))]
    for sid, sname in stat_names.items():
        fields.append((5, _msg((1, sid), (2, _msg((1, sid), (2, sname))))))
    for eid, (ename, stats) in events.items():
        meta = _msg((1, eid), (2, ename), (4, "short"),
                    *[(5, _msg((1, sid), *value)) for sid, value in stats])
        fields.append((4, _msg((1, eid), (2, meta))))
    return _msg(*fields)


def test_op_paths_decode_event_metadata():
    stat_names = {1: "tf_op", 2: "program_id", 3: "flops",
                  4: "jit(f)/while/body/attention/dot_general:"}
    big = 12894903536271268261                # over 2**63, as uint64
    events = {
        1: ("%fusion.1 = bf16[4]{0} fusion(), kind=kLoop",
            [(2, [(3, big)]), (3, [(2, 1.5)]),
             (1, [(5, "jit(f)/cast/convert_element_type:")])]),
        2: ("%copy.2 = f32[2]{0} copy(f32[2]{0} %p)",
            [(1, [(7, 4)]), (2, [(4, 42)])]),   # tf_op by reference
        3: ("%copy.3 = f32[2]{0} copy(f32[2]{0} %p)", [(2, [(4, 42)])]),
    }
    space = _msg((1, _plane("/host:CPU", events, stat_names)),
                 (1, _plane("/device:TPU:1", events, stat_names)),
                 (2, "an error"))
    assert layers.op_paths(space) == {1: {
        (big, "fusion.1 bf16[4]"): "jit(f)/cast/convert_element_type",
        (42, "copy.2 f32[2]"): "jit(f)/while/body/attention/dot_general"}}
