"""The reduction of the program's spans and scopes in a device trace, and
the three readers that take their metrics from it."""
import pytest

import harness
import program_trace as pt
import trace_reduce as tr

MS = 1e6   # nanoseconds
CHIP = "/device:TPU:0"


def _span(name, start, dur, thread=1, **args):
    return pt.HostEvent(name, start * MS, dur * MS, thread,
                        dict(args, cat="serve"))


def _event(name, start, dur, thread=1):
    return pt.HostEvent(name, start * MS, dur * MS, thread, None)


def _train_trace(program=True):
    """Two steps in a 20 ms window: device ops 0-3 and 10-13 ms."""
    device = {CHIP: [pt.Op("fusion.1", 0 * MS, 2 * MS, "layer0/aggregate"),
                     pt.Op("dot.2", 2 * MS, 1 * MS, "layer0/update"),
                     pt.Op("fusion.1", 10 * MS, 2 * MS, "layer0/aggregate"),
                     pt.Op("adam.3", 12 * MS, 1 * MS, "optimizer"),
                     pt.Op("copy.4", 25 * MS, 1 * MS, "")]}   # after window
    host = [_event(tr.WINDOW, 0, 20),
            _event("train.dispatch", 0, 0.5),
            _event("train.loss_sync", 0.5, 9),
            _event("train.dispatch", 9.5, 0.5),
            _event("train.loss_sync", 10, 10)]
    if program:
        host += [_span("host.gc", 4, 2, generation=2),            # gap 3-10
                 _event("TransferFromDevice", 6, 3, thread=7),
                 _event("ProfilerSession", 0, 30, thread=9),
                 _event("PjitFunction(step)", 9.6, 0.3)]
    return pt.Trace(device, host, (0, 20 * MS))


OLD_KEYS = ("busy_s", "window_s", "idle_share", "device_ops", "idle_gaps")


def test_the_old_keys_are_pinned_on_a_fixed_trace():
    out = pt.reduce(_train_trace(), ("train.dispatch", "train.loss_sync"))
    assert out["busy_s"] == pytest.approx(6e-3)
    assert out["window_s"] == pytest.approx(20e-3)
    assert out["idle_share"] == pytest.approx(0.7)
    assert out["device_ops"] == [["fusion.1", pytest.approx(4e-3)],
                                 ["dot.2", pytest.approx(1e-3)],
                                 ["adam.3", pytest.approx(1e-3)]]
    assert out["idle_gaps"] == [["train.loss_sync", pytest.approx(14e-3)]]


def test_program_events_leave_the_old_keys_as_they_were():
    names = ("train.dispatch", "train.loss_sync")
    with_program = pt.reduce(_train_trace(True), names)
    without = pt.reduce(_train_trace(False), names)
    t = _train_trace(False)
    direct = tr.reduce({c: [(o.name, o.start, o.dur) for o in ops]
                        for c, ops in t.device.items()},
                       [(e.name, e.start, e.dur) for e in t.host
                        if e.name != tr.WINDOW], t.window)
    for k in OLD_KEYS:
        assert with_program[k] == without[k] == direct[k]


def test_device_time_by_scope_and_steps_begun():
    out = pt.reduce(_train_trace(), ("train.dispatch", "train.loss_sync"))
    assert out["device_scopes"] == {
        "layer0/aggregate": pytest.approx(4e-3),
        "layer0/update": pytest.approx(1e-3),
        "optimizer": pytest.approx(1e-3)}
    assert out["harness_spans"] == {"train.dispatch": 2,
                                    "train.loss_sync": 2}


def test_idle_gaps_go_to_program_spans_then_runtime_events():
    out = pt.reduce(_train_trace(), ("train.dispatch", "train.loss_sync"))
    gaps = dict(out["program_idle_gaps"])
    # gap [3, 10): its middle, 6.5, is after host.gc's [4, 6) and inside
    # TransferFromDevice's [6, 9), the innermost runtime event; gap
    # [13, 20): at 16.5 only the profiler session's own event is open
    assert gaps == {"TransferFromDevice": pytest.approx(7e-3),
                    "ProfilerSession": pytest.approx(7e-3)}
    t = _train_trace()
    t.host.append(_span("host.gc", 15, 3, generation=0))
    gaps = dict(pt.reduce(t, ("train.dispatch",))["program_idle_gaps"])
    assert gaps["host.gc"] == pytest.approx(7e-3)
    assert sum(gaps.values()) == pytest.approx(14e-3)


def test_a_gap_with_nothing_open_is_idle_and_the_tail_is_other():
    device = {CHIP: [pt.Op(f"op{i}", i * 10 * MS, 1 * MS, "")
                     for i in range(14)]}
    host = [_event(tr.WINDOW, 0, 140)] + [
        _span(f"serve.s{i}", i * 10 + 2, 8 - i * 0.1) for i in range(12)]
    out = pt.reduce(pt.Trace(device, host, (0, 140 * MS)), ())
    labels = [n for n, _ in out["program_idle_gaps"]]
    assert len(labels) == pt.TOP + 1 and labels[-1] == "other"
    assert sum(t for _, t in out["program_idle_gaps"]) == pytest.approx(
        out["window_s"] - out["busy_s"])
    gaps = dict(pt.reduce(pt.Trace(device, host[:1], (0, 140 * MS)),
                          ())["program_idle_gaps"])
    assert gaps == {"idle": pytest.approx(126e-3)}


def _serve_trace():
    """Two batches on thread 1: the first 10 ms with 2 ms of CPU and 8 ms
    of read-back; the second 10 ms with 2 ms of CPU, 3 ms of read-back."""
    host = [_event(tr.WINDOW, 0, 40),
            _span("serve.batch", 0, 10, seq=0, first=0, last=7, cpu_ms=2.0),
            _span("serve.session.layer", 1, 9, layer=2),
            _span("serve.session.launch", 1, 1),
            _span("serve.session.readback", 2, 8),
            _span("serve.batch", 20, 10, seq=1, first=8, last=9, cpu_ms=2.0),
            _span("serve.session.layer", 21, 4, layer=2),
            _span("serve.session.readback", 22, 3),
            _span("serve.cache.lookup", 26, 1, thread=2)]   # another thread
    device = {CHIP: [pt.Op("fusion", 3 * MS, 1 * MS, "")]}
    return pt.Trace(device, host, (0, 40 * MS))


def test_program_spans_count_total_and_self_time():
    out = pt.reduce(_serve_trace(), ())
    spans = out["program_spans"]
    assert spans["serve.batch"]["count"] == 2
    assert spans["serve.batch"]["total_s"] == pytest.approx(20e-3)
    # self: 10 - 9 and 10 - 4
    assert spans["serve.batch"]["self_s"] == pytest.approx(7e-3)
    assert spans["serve.session.layer"]["self_s"] == pytest.approx(
        (9 - 1 - 8 + 4 - 3) * 1e-3)
    assert spans["serve.cache.lookup"]["self_s"] == pytest.approx(1e-3)
    first, second = out["span_instances"]["serve.batch"]
    assert first["args"] == {"seq": 0, "first": 0, "last": 7, "cpu_ms": 2.0}
    assert first["within"] == {"serve.session.layer": pytest.approx(9e-3),
                               "serve.session.launch": pytest.approx(1e-3),
                               "serve.session.readback": pytest.approx(8e-3)}
    assert second["within"]["serve.session.readback"] == pytest.approx(3e-3)
    assert "serve.session.launch" not in out["span_instances"]


# ------------------------------------------------------------------ readers
def _read(name, ctx):
    return harness.metric_reader(name)(ctx)


def test_aggregate_ms_is_aggregate_scoped_device_time_per_step():
    trace = pt.reduce(_train_trace(), ("train.dispatch", "train.loss_sync"))
    ctx = {"kind": "train", "trace": trace}
    assert _read("train.aggregate_ms", ctx) == pytest.approx(2.0)
    trace["device_scopes"]["layer1/aggregate"] = 2e-3
    assert _read("train.aggregate_ms", ctx) == pytest.approx(3.0)
    assert _read("train.aggregate_ms", {"kind": "serve",
                                        "trace": trace}) is None
    old = {k: trace[k] for k in OLD_KEYS}         # a reduction without it
    assert _read("train.aggregate_ms", {"kind": "train",
                                        "trace": old}) is None
    assert _read("train.aggregate_ms", {"kind": "train",
                                        "trace": None}) is None


def test_queue_ms_is_the_p95_of_the_queue_histogram():
    hist = {"count": 40, "p50": 0.001, "p95": 0.004, "p99": 0.005}
    ctx = {"kind": "serve", "registry": {"histograms": {
        "serve.queue_seconds": hist}}}
    assert _read("serve.queue_ms", ctx) == pytest.approx(4.0)
    assert _read("serve.queue_ms", {"kind": "serve",
                                    "registry": {"histograms": {}}}) is None
    assert _read("serve.queue_ms", {"kind": "serve"}) is None
    assert _read("serve.queue_ms", dict(ctx, kind="train")) is None


def test_stall_ms_is_wall_less_cpu_less_readback():
    trace = pt.reduce(_serve_trace(), ())
    ctx = {"kind": "serve", "trace": trace}
    # batch 0: 10 - 2 - 8 = 0 (a stall exactly offset by read-back);
    # batch 1: 10 - 2 - 3 = 5; the p99 of (0, 5) is 4.95
    assert _read("serve.stall_ms", ctx) == pytest.approx(4.95)
    trace["span_instances"]["serve.batch"].pop()
    assert _read("serve.stall_ms", ctx) == pytest.approx(0.0)
    # floored at 0 where CPU time and read-back overlap the wall time
    trace["span_instances"]["serve.batch"][0]["args"]["cpu_ms"] = 5.0
    assert _read("serve.stall_ms", ctx) == 0.0
    assert _read("serve.stall_ms", {"kind": "serve",
                                    "trace": {"busy_s": 1.0}}) is None
    assert _read("serve.stall_ms", dict(ctx, kind="train")) is None


@pytest.mark.parametrize("op_name,path", [
    ("jit(step)/jvp(layer0)/aggregate/scatter-add", "layer0/aggregate"),
    ("jit(step)/transpose(jvp(layer1))/aggregate/gather",
     "layer1/aggregate"),
    ("jit(step)/optimizer/sqrt", "optimizer"),
    ("jit(step)/jvp(layer0)/update/jit(relu)/max", "layer0/update"),
    ("jit(step)/jvp()/log", ""),
    ("", ""),
])
def test_scope_path_drops_wrappers_and_the_op(op_name, path):
    assert pt.scope_path(op_name) == path


HLO = """
ENTRY %main.7 (p0: f32[8,4]) -> f32[8,4] {
  %fusion.3 = f32[8,4]{1,0} fusion(f32[8,4]{1,0} %p0), kind=kLoop, calls=%fc.3, metadata={op_name="jit(step)/transpose(jvp(layer1))/aggregate/scatter-add" source_file="x.py" source_line=3}
  %copy.1 = f32[8,4]{1,0} copy(f32[8,4]{1,0} %fusion.3)
  ROOT %fusion.4 = f32[8,4]{1,0} fusion(%copy.1), kind=kLoop, metadata={op_name="jit(step)/optimizer/sqrt"}
}
"""


def test_hlo_scopes_map_instructions_to_their_scope_paths():
    assert pt.hlo_scopes(HLO) == {"fusion.3": "layer1/aggregate",
                                  "fusion.4": "optimizer"}
    assert pt._instruction("%fusion.3 = f32[8,4]{1,0} fusion(%p0)") == \
        "fusion.3"
    assert pt._instruction("spmm_blockell_compact.1") == \
        "spmm_blockell_compact.1"
