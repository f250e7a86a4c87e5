"""The reduction from a profiler trace to busy/idle time, time per
operation and program, and idle gaps named by what the host was doing."""

import os

import pytest

import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# Device: ops [0,2) and [3,4) and [8,9) ms inside a program [0,4) and one
# [8,9); host: the window [0,10), a step [0,5) holding an admit [2,3),
# a wait [5,8).  Times in ps from a line start of 1,000 ns.
XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 3000000000 duration_ps: 1000000000 }
    events { metadata_id: 2 offset_ps: 8000000000 duration_ps: 1000000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 4000000000 }
    events { metadata_id: 4 offset_ps: 8000000000 duration_ps: 1000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "flash_decode_kernel" } }
  event_metadata { key: 3 value { id: 3 name: "jit_serve_step(12)" } }
  event_metadata { key: 4 value { id: 4 name: "jit__prefill(7)" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 5000000000 }
    events { metadata_id: 3 offset_ps: 2000000000 duration_ps: 1000000000 }
    events { metadata_id: 4 offset_ps: 5000000000 duration_ps: 3000000000 }
    events { metadata_id: 5 offset_ps: 5000000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.admit" } }
  event_metadata { key: 4 value { id: 4 name: "bench.wait" } }
  event_metadata { key: 5 value { id: 5 name: "python_internal" } }
}
"""
MS = 1_000_000


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    import jax
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return tracing.Trace.load(str(path))


def test_merge_and_gaps():
    assert tracing.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tracing.covered([(0, 3), (5, 8)], 2, 6) == 2


def test_window_busy_and_idle(trace):
    lo, hi = trace.window()
    assert hi - lo == 10 * MS
    assert trace.busy_s(lo, hi) == pytest.approx(0.004)
    s = tracing.summary(trace)
    assert s["window_s"] == pytest.approx(0.010)
    assert s["busy_s"] == pytest.approx(0.004)


def test_time_per_op_and_program(trace):
    lo, hi = trace.window()
    ops = trace.op_seconds(lo, hi)
    assert ops == pytest.approx({"fusion.1": 0.002,
                                 "flash_decode_kernel": 0.002})
    kern = trace.op_seconds(lo, hi, match=lambda n: "flash_decode" in n)
    assert sum(kern.values()) == pytest.approx(0.002)
    assert trace.module_seconds(lo, hi) == pytest.approx(
        {"jit_serve_step": 0.004, "jit__prefill": 0.001})


def test_idle_gaps_named_by_the_host(trace):
    lo, hi = trace.window()
    # gaps [2,3) admit, [4,8) mostly in the wait (midpoint 6), [9,10) none
    idle = trace.idle_by_host(lo, hi)
    assert idle == pytest.approx({"bench.admit": 0.001, "bench.wait": 0.004,
                                  "untraced host": 0.001})
    b = tracing.breakdown(trace, lo, hi)
    assert b["idle_gaps"][0] == ["bench.wait", pytest.approx(0.004)]
    assert len(b["device_ops"]) <= 10
    # only the benchmark's and the program's spans name the host
    assert all(n.startswith(tracing.HOST_PREFIXES) for n, _, _ in trace.host)


def test_device_busy_inside_host_spans(trace):
    (s, e), = trace.spans("bench.step")
    assert trace.busy_s(s, e) == pytest.approx(0.003)


def test_recorded_trace():
    """A trace recorded by ``tracing.profile`` (on the CPU backend, which
    has no device plane): three ``bench.step`` spans inside the
    ``bench.window`` span."""
    tr = tracing.Trace.load(os.path.join(DATA, "cpu-small.xplane.pb"))
    steps = tr.spans("bench.step")
    assert len(steps) == 3
    lo, hi = tr.window()
    assert lo <= steps[0][0] and steps[-1][1] <= hi
    assert 0.005 < (hi - lo) / 1e9 < 1.0
    assert tr.devices == {} and tr.busy_s(lo, hi) == 0.0
    s = tracing.summary(tr)
    assert s["busy_s"] == 0.0 and s["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert sum(v for _, v in s["breakdown"]["idle_gaps"]) == \
        pytest.approx(s["window_s"])


def test_kernel_found_by_its_framework_path(tmp_path):
    """On the chip a Pallas kernel's operation is a custom call; its
    ``jax.named_scope`` path names it."""
    import jax
    xspace = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000
      stats { metadata_id: 9 str_value: "jit(serve_step)/while/body/obs.flash_decode/pallas_call" } }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "custom-call.7" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
}
"""
    path = tmp_path / "k.xplane.pb"
    path.write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(xspace))
    tr = tracing.Trace.load(str(path))
    kern = tr.op_seconds(0, 10**9, match=lambda n: "flash_decode" in n)
    assert sum(kern.values()) == pytest.approx(3e-6)
    assert list(kern)[0].startswith("custom-call.7 ")
    assert tracing.short_name(
        "%custom-call.7 = bf16[16] custom-call(x), "
        "custom_call_target=\"tpu_custom_call\"") == \
        "%custom-call.7 (tpu_custom_call)"
