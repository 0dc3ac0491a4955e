"""The reduction from a device trace to busy time, idle share and gaps."""
import pytest

import trace_reduce as tr

MS = 1e6   # nanoseconds


def test_busy_is_the_union_of_op_intervals_clipped_to_the_window():
    device = {"/device:TPU:0": [("fusion.1", 0 * MS, 4 * MS),
                                ("fusion.2", 2 * MS, 4 * MS),   # overlaps
                                ("dot.3", 10 * MS, 2 * MS),
                                ("dot.3", 19 * MS, 4 * MS)]}    # crosses end
    out = tr.reduce(device, [], (1 * MS, 21 * MS))
    # [1, 6) + [10, 12) + [19, 21) = 5 + 2 + 2 ms of a 20 ms window
    assert out["busy_s"] == pytest.approx(9e-3)
    assert out["window_s"] == pytest.approx(20e-3)
    assert out["idle_share"] == pytest.approx(1 - 9 / 20)
    ops = dict(out["device_ops"])
    assert ops["dot.3"] == pytest.approx(4e-3)
    assert ops["fusion.1"] == pytest.approx(3e-3)
    assert ops["fusion.2"] == pytest.approx(4e-3)


def test_busy_is_averaged_over_chips():
    device = {"/device:TPU:0": [("a", 0, 10 * MS)],
              "/device:TPU:1": [("a", 0, 5 * MS)]}
    out = tr.reduce(device, [], (0, 10 * MS))
    assert out["busy_s"] == pytest.approx(7.5e-3)
    assert out["idle_share"] == pytest.approx(0.25)


def test_gaps_are_labelled_by_the_innermost_host_span():
    device = {"/device:TPU:0": [("op", 0, 2 * MS), ("op", 5 * MS, 1 * MS),
                                ("op", 9 * MS, 1 * MS)]}
    host = [("serve.process_batch", 1 * MS, 6 * MS),
            ("serve.expand", 2 * MS, 2 * MS),     # holds gap [2, 5)'s middle
            ("serve.wait", 7 * MS, 2 * MS)]       # holds gap [6, 9)'s middle
    out = tr.reduce(device, host, (0, 12 * MS))
    gaps = dict(out["idle_gaps"])
    assert gaps["serve.expand"] == pytest.approx(3e-3)
    assert gaps["serve.wait"] == pytest.approx(3e-3)
    assert gaps["idle"] == pytest.approx(2e-3)            # [10, 12)
    assert [g[0] for g in out["idle_gaps"]][-1] == "idle"
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"])


def test_lists_keep_the_ten_largest():
    device = {"/device:TPU:0": [(f"op{i}", i * 10 * MS, (i + 1) * MS)
                                for i in range(15)]}
    out = tr.reduce(device, [], (0, 200 * MS))
    assert len(out["device_ops"]) == 10
    assert out["device_ops"][0][0] == "op14"
    assert len(out["idle_gaps"]) <= 10


def test_a_trace_without_device_or_window_is_refused():
    with pytest.raises(ValueError):
        tr.reduce({}, [], (0, 1))
    with pytest.raises(ValueError):
        tr.reduce({"/device:TPU:0": [("a", 0, 1)]}, [], (5, 5))


def test_op_names_keep_the_name_and_output_type():
    text = ("%fusion.3 = f32[2708,16]{1,0:T(8,128)S(1)} fusion(f32[2708,16]"
            "{1,0:T(8,128)S(1)} %get-tuple-element.20), kind=kCustom")
    assert tr.op_name(text) == "fusion.3 f32[2708,16]"
    assert tr.op_name("%copy-done.1 = (f32[4]{0}, u32[]) copy-done(%a)") \
        == "copy-done.1 (f32[4]"
    assert tr.op_name("jit_step") == "jit_step"
