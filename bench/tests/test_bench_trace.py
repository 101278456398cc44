"""The reduction from a device trace to busy and idle time, program time
and the breakdown, on a trace recorded on a TPU v5e and on a made-up one."""
import gzip
import json
import os

import pytest

import _paths  # noqa: F401
import roofline
import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_reduce_on_a_made_up_trace():
    ex = {"host": [["bench:window", 0, 100], ["bench:query:q1", 0, 50],
                   ["bench:query:q6", 60, 40]],
          "devices": [{"ops": [["fusion", 10, 20], ["copy", 25, 10],
                               ["fusion", 70, 10]],
                       "modules": [["jit_wrapped", 10, 25],
                                   ["jit_wrapped", 70, 10],
                                   ["jit_other", 90, 5]]}]}
    r = trace.reduce(ex)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(35e-9)  # [10,35) and [70,80)
    assert r["module_s"]["jit_wrapped"] == pytest.approx(35e-9)
    assert roofline.program_seconds(r["module_s"]) == pytest.approx(35e-9)
    assert r["breakdown"]["device_ops"][0] == ["fusion", pytest.approx(30e-9)]
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["client in q1", pytest.approx(35e-9)]  # [35, 70)
    assert gaps[1] == ["client in q6", pytest.approx(20e-9)]  # [80, 100)
    assert gaps[2] == ["client in q1", pytest.approx(10e-9)]  # [0, 10)
    ex["host"] = ex["host"][:1]
    out = trace.reduce(ex, outstanding=[(30, 75)])
    assert [g[0] for g in out["breakdown"]["idle_gaps"]] == [
        "1 requests outstanding", "no request outstanding",
        "no request outstanding"]


def test_reduce_on_a_recorded_tpu_trace():
    path = os.path.join(DATA, "tpu_v5e_stream.json.gz")
    with gzip.open(path, "rt") as f:
        ex = json.load(f)
    r = trace.reduce(ex)
    assert 0 < r["busy_s"] < r["window_s"]
    assert roofline.program_seconds(r["module_s"]) > 0
    assert len(r["breakdown"]["device_ops"]) == trace.TOP
    assert all(s > 0 for _, s in r["breakdown"]["idle_gaps"])


def test_peaks_refuse_an_unknown_device():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
