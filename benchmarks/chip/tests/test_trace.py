"""The trace reduction, on a small synthetic trace."""

import pytest

import devtrace


def _trace():
    # device 0: busy [0, 20] and [30, 40]; a collective [30, 40] alone
    # device 1: busy [0, 50], a collective [10, 20] under compute [0, 50]
    return devtrace.Trace(
        devices={
            0: [(0, 20, "fusion.1"), (5, 15, "fusion.2"),
                (30, 40, "collective-permute-done.1")],
            1: [(0, 50, "fusion.1"), (10, 20, "all-reduce.4")],
        },
        host=[(0, 100, "bench.window"), (18, 32, "bench.dispatch"),
              (40, 100, "bench.wait")],
    )


def test_device_shares():
    s = devtrace.device_shares(_trace())
    assert s["window_s"] == pytest.approx(100e-9)
    d0, d1 = s["devices"][0], s["devices"][1]
    assert d0["busy_s"] == pytest.approx(30e-9)
    assert d0["idle_share"] == pytest.approx(0.7)
    assert d0["collective_exposed_share"] == pytest.approx(0.1)
    assert d1["idle_share"] == pytest.approx(0.5)
    assert d1["collective_exposed_share"] == pytest.approx(0.0)


def test_top_ops_and_idle_gaps():
    tr = _trace()
    top = dict(devtrace.top_ops(tr))
    # self time: fusion.2 and the all-reduce run inside fusion.1
    assert top["fusion.1"] == pytest.approx(10e-9 + 40e-9)
    assert top["fusion.2"] == pytest.approx(10e-9)
    assert top["all-reduce.4"] == pytest.approx(10e-9)
    gaps = dict(devtrace.idle_gaps(tr))
    # device 0 idles [20, 30] (host dispatching) and [40, 100] (waiting);
    # device 1 idles [50, 100] (waiting)
    assert gaps["bench.dispatch"] == pytest.approx(10e-9)
    assert gaps["bench.wait"] == pytest.approx(110e-9)


def test_window_clips_ops():
    tr = devtrace.Trace(devices={0: [(-50, 10, "fusion.1"), (90, 150, "fusion.2")]},
                        host=[(0, 100, "bench.window")])
    assert devtrace.device_shares(tr)["devices"][0]["idle_share"] == pytest.approx(0.8)


def test_load_reads_device_ops_and_bench_spans(tmp_path):
    from jax.profiler import ProfileData

    text = """
    planes {
      id: 1 name: "/device:TPU:0"
      lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
        events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 } }
      lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
        events { metadata_id: 3 offset_ps: 0 duration_ps: 4000000 } }
      event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
      event_metadata { key: 2 value { id: 2 name: "collective-permute-done.3" } }
      event_metadata { key: 3 value { id: 3 name: "jit_step" } }
    }
    planes {
      id: 2 name: "/host:CPU"
      lines { id: 1 name: "python" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
        events { metadata_id: 2 offset_ps: 0 duration_ps: 1000000 } }
      event_metadata { key: 1 value { id: 1 name: "bench.window" } }
      event_metadata { key: 2 value { id: 2 name: "$profiler.py:1 start_trace" } }
    }
    """
    (tmp_path / "plugins").mkdir()
    (tmp_path / "plugins" / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    tr = devtrace.load(str(tmp_path))
    assert tr.devices == {0: [(1000.0, 3000.0, "fusion.1"),
                              (4000.0, 5000.0, "collective-permute-done.3")]}
    assert tr.host == [(1000.0, 11000.0, "bench.window")]
    s = devtrace.device_shares(tr)["devices"][0]
    assert s["idle_share"] == pytest.approx(0.7)
    assert s["collective_exposed_share"] == pytest.approx(0.1)


def test_short_names_of_hlo_ops():
    op = ("%fusion.30 = f32[65536,256]{1,0:T(8,128)} fusion(s32[2162688]{0:T(1024)S(1)} "
          "%get-tuple-element.1388), kind=kLoop")
    assert devtrace.short_name(op) == "fusion.30 f32[65536,256]"
    assert devtrace.short_name("fusion.1") == "fusion.1"
