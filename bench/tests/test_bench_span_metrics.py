"""The per-layer metric read from the engine's own admission spans on the
profiler's timeline, over a synthetic trace, and its silence on a trace of
a program without them."""

import pytest

import common
import tracing

MS = 1_000_000

# Device: operations [0,3.5) and [5,6).  Host: the window [1,10) and the
# engine's admissions [0.2,0.8) (before the window), [2,3) and [4,8); the
# harness's wrapper around the last.  Times in ps from a line start of 0.
XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3500000000 }
    events { metadata_id: 2 offset_ps: 5000000000 duration_ps: 1000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "sort.3" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 9000000000 }
    events { metadata_id: 2 offset_ps: 200000000 duration_ps: 600000000 }
    events { metadata_id: 2 offset_ps: 2000000000 duration_ps: 1000000000 }
    events { metadata_id: 3 offset_ps: 3900000000 duration_ps: 4100000000 }
    events { metadata_id: 2 offset_ps: 4000000000 duration_ps: 4000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "engine.admit" } }
  event_metadata { key: 3 value { id: 3 name: "bench.admit" } }
}
"""

# a program without the engine's admission spans: the harness's only
BARE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 2500000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.admit" } }
}
"""


def _run(tmp_path, xspace):
    import jax
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(xspace))
    tr = tracing.Trace.load(str(path))
    lo, hi = tr.window()
    return {"trace": tr, "lo": lo, "hi": hi}


def test_admission_idle_per_request(tmp_path):
    run = _run(tmp_path, XSPACE)
    assert run["hi"] - run["lo"] == 9 * MS
    # [2,3) is all busy; [4,8) holds 1 ms of work: 3 ms idle; the
    # admission before the window is not counted
    read = common.metric_reader("admit.idle_ms_per_req")
    assert read(run) == pytest.approx(1.5)
    # the innermost span names the idle gap [3.5,5): the engine's
    # admission, not the harness's wrapper around it
    idle = run["trace"].idle_by_host(run["lo"], run["hi"])
    assert idle == pytest.approx({"engine.admit": 0.0015,
                                  "untraced host": 0.004})


def test_admission_idle_silent_without_the_programs_spans(tmp_path):
    run = _run(tmp_path, BARE)
    assert common.metric_reader("admit.idle_ms_per_req")(run) is None
