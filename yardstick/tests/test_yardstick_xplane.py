"""The reduction from a trace to numbers: interval arithmetic by hand, and a
small trace kept beside this file. data/synthetic_tpu.xplane.pb was written
with the xplane protocol buffers in the shape a TPU trace has (a
/device:TPU:0 plane with ``XLA Modules`` and ``XLA Ops`` lines, the two
window markers on /host:CPU), because no chip was to be had when this was
written; its intervals are listed in the test, in milliseconds."""

import os

import pytest

from yardstick import xplane

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "synthetic_tpu.xplane.pb")


def test_union_clip_subtract():
    assert xplane.union([[3, 4], [0, 1], [0.5, 2]]) == [[0, 2], [3, 4]]
    assert xplane.clip([[0, 2], [3, 4]], 1, 3.5) == [[1, 2], [3, 3.5]]
    assert xplane.subtract([0, 10], [[1, 2], [4, 6]]) == [
        [0, 1], [2, 4], [6, 10]]
    assert xplane.total([[0, 1], [2, 4]]) == 3


def test_a_gap_goes_to_the_innermost_span_that_holds_most_of_it():
    spans = [("cycle", 0.0, 10.0), ("encode", 1.0, 4.0),
             ("stage", 4.0, 9.5)]
    assert xplane.name_gap([0.0, 10.0], spans) == "stage"
    assert xplane.name_gap([0.5, 3.0], spans) == "encode"
    assert xplane.name_gap([20.0, 30.0], spans) == xplane.OUTSIDE
    assert xplane.name_gap([9.0, 30.0], spans) == xplane.OUTSIDE


def test_module_name_and_peaks():
    assert xplane.module_name("jit_drain_step(123)") == "jit_drain_step"
    assert xplane.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        xplane.peaks("TPU v9")


def test_a_small_trace_reduces_to_known_numbers():
    # window 1.0 .. 11.0 ms. XLA Ops: [0.5, 1.5] (half inside), a while
    # [2, 4] with two fusions nested in it, [6, 6.6], [6.7, 7], [9, 9.5],
    # [11.5, 12.5] (outside) -> busy 0.5 + 2 + 0.9 + 0.5 = 3.9 ms.
    # XLA Modules: jit_drain_step [2, 4] and [6, 7] (and one outside),
    # jit__stage [9, 9.5]. Host spans, on the wall clock the markers carry:
    # encode_pods 4.5 .. 5.8 ms, stage_batch 7.2 .. 8.8 ms.
    spans = [("scheduler/encode_pods", 1000.0045, 1000.0058),
             ("scheduler/stage_batch", 1000.0072, 1000.0088)]
    r = xplane.reduce(TRACE, spans)
    assert r["chips"] == 1 and r["notes"] == []
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.0039)
    assert r["idle_share"] == pytest.approx(0.61)
    assert r["modules"]["jit_drain_step"]["n"] == 2
    assert r["modules"]["jit_drain_step"]["s"] == pytest.approx(0.003)
    assert r["modules"]["jit__stage"]["s"] == pytest.approx(0.0005)
    assert r["device_ops"][0][0] == "fusion.1"
    gaps = [(name, round(s * 1000, 3)) for name, s in r["idle_gaps"]]
    assert gaps[:3] == [("scheduler/stage_batch", 2.0),
                        ("scheduler/encode_pods", 2.0),
                        ("outside-spans", 1.5)]
    from yardstick.readers import module_device_ms
    assert module_device_ms.read({"trace": r}, {"module": "drain_step"}) \
        == pytest.approx(1.5)
