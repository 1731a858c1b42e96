"""The trace reduction: busy time as the union of device operations,
idle share, the operations that took most time, and the longest idle
gaps named by the host spans that overlap them. Checked on a small
hand-written trace with known answers and on small traces recorded on
one v5e chip and on four (``data/tpu_small.xplane.pb`` and
``data/tpu_four.xplane.pb``, made by ``data/record_trace.py``)."""
import os

import pytest

from benchtest_util import spec  # noqa: F401  (puts bench/ on sys.path)
from lib import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# times in ns; offsets and durations in ps
TEXT = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 11000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 12000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "copy.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 4500000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 8000000 duration_ps: 1500000 }
  }
  lines { id: 2 name: "worker" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 4000000 duration_ps: 3000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.submit" } }
  event_metadata { key: 3 value { id: 3 name: "bench.search" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(step)" } }
}
"""


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData
    return xplane.reduce(ProfileData.from_text_proto(TEXT))


def test_busy_is_the_union_inside_the_window(synthetic):
    # window [0, 10] us; ops [1,3] [2,4] [7,8] [11,12]: union inside is
    # [1,4] + [7,8] = 4 us; the op after the window does not count
    assert synthetic.window_s == pytest.approx(10e-6)
    assert synthetic.busy_s == pytest.approx(4e-6)
    assert synthetic.idle_share == pytest.approx(0.6)
    assert synthetic.n_devices == 1


def test_top_ops_sum_time_by_name(synthetic):
    assert synthetic.top_ops == [["fusion.1", pytest.approx(3e-6)],
                                 ["copy.2", pytest.approx(2e-6)]]


def test_gaps_longest_first_named_by_host_spans(synthetic):
    # gaps [0,1] [4,7] [8,10]
    assert [g[1] for g in synthetic.idle_gaps] == [
        pytest.approx(3e-6), pytest.approx(2e-6), pytest.approx(1e-6)]
    assert synthetic.idle_gaps[0][0] == "bench.submit / PjitFunction(step)"
    assert synthetic.idle_gaps[1][0] == "bench.search / no host event"
    assert synthetic.idle_gaps[2][0] == "no bench span / no host event"


def test_union_length_merges_overlaps():
    total, merged = xplane.union_length([(5, 6), (0, 2), (1, 3), (3, 4)])
    assert total == 5 and merged == [[0, 4], [5, 6]]


def test_a_trace_without_a_device_plane_is_an_error():
    from jax.profiler import ProfileData
    txt = TEXT.split("planes { id: 2")[0].replace("/device:TPU:0",
                                                  "/device:CPU:0")
    with pytest.raises(ValueError):
        xplane.reduce(ProfileData.from_text_proto(txt))


def test_recorded_v5e_trace():
    """Three calls of one ~12 us program, 2 ms of sleep after each."""
    s = xplane.reduce(xplane.load(os.path.join(DATA, "tpu_small.xplane.pb")))
    assert s.n_devices == 1             # the Megascale plane is not a chip
    assert s.window_s == pytest.approx(10.545039e-3)
    # the device clock runs ~1-2 ms behind the host's in this trace, so
    # the first call's program may fall before the host window opens
    assert 2 * 11.7e-6 <= s.busy_s <= 3 * 11.9e-6
    assert s.top_ops[0][0].startswith("%fusion = f32[1024]")
    assert s.idle_share > 0.99
    assert len(s.idle_gaps) >= 3
    assert all(g[0].split(" / ")[0] in ("bench.call", "bench.sleep")
               for g in s.idle_gaps[:3])


def test_recorded_four_chip_v5e_trace():
    """The same program on each of four chips at once, three calls with
    2 ms of sleep after each (``record_trace.py --chips 4``): every chip
    is a device plane, busy time is the mean over them, and an
    operation's time is summed over them."""
    s = xplane.reduce(xplane.load(os.path.join(DATA, "tpu_four.xplane.pb")))
    # the chips' host-interface and misc planes and the Megascale plane
    # are not chips
    assert s.n_devices == 4
    assert s.window_s == pytest.approx(13.057864e-3)
    assert 2 * 11.7e-6 <= s.busy_s <= 3 * 11.9e-6
    name, seconds = s.top_ops[0]
    assert name.startswith("%fusion = f32[1024]")
    assert seconds == pytest.approx(4 * s.busy_s, rel=0.01)
    assert s.idle_share > 0.99
