"""The trace reduction, on a small device trace whose busy share, kernel
time and idle gaps are known."""
import pytest

from bench import trace

# ns on one clock. Device: a while loop 0-100 holding a fusion (10-40) and
# the gram kernel (50-80); a copy 150-200. Host: window 0-250; the harness
# spans that cover the device's two idle gaps (100-150, 200-250).
DEVICE = [("%while.1 = (f32[]) while(..)", 0, 100),
          ("%fusion.2 = f32[8] fusion(..)", 10, 30),
          ("%batched_gram_apply_pallas.7 = f32[2] custom-call(..)", 50, 30),
          ("%copy.3 = f32[8] copy(..)", 150, 50)]
HOST = [("window", 0, 250), ("prepare", 95, 20), ("solve_call", 115, 40),
        ("block", 155, 95)]


def _text_proto():
    def plane(pid, name, line, events):
        names = sorted({n for n, _, _ in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        evs = "".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
            f"duration_ps: {d * 1000} }}\n" for n, s, d in events)
        meta = "".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
            for n, i in ids.items())
        return (f'planes {{ id: {pid} name: "{name}"\n'
                f'lines {{ id: 1 name: "{line}" timestamp_ns: 5000\n{evs}}}\n'
                f'{meta}}}\n')
    return (plane(1, "/device:TPU:0", "XLA Ops", DEVICE)
            + plane(2, "/host:CPU", "python", HOST))


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    from jax.profiler import ProfileData

    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        _text_proto()))
    return trace.reduce(trace.load(path))


def test_busy_share_and_window(reduced):
    assert reduced.window_ns == 250
    assert len(reduced.devices) == 1
    assert reduced.devices[0].busy_ns == 150          # 0-100 and 150-200
    assert reduced.solves == 1


def test_op_self_times_and_kernel_time(reduced):
    dv = reduced.devices[0]
    assert dv.op_ns["while.1"] == 40                  # 100 less 30 and 30
    assert dv.op_ns["fusion.2"] == 30
    assert trace.op_time(dv, "batched_gram_apply_pallas") == (30, 1)
    assert trace.op_time(dv, "batched_gram") == (0, 0)


def test_idle_gaps_labelled_by_host_span(reduced):
    assert dict(reduced.devices[0].idle_ns) == {"solve_call": 50,
                                                "block": 50}
    bd = trace.breakdown(reduced)
    assert bd["device_ops"][0] == ["copy.3", 50e-9]
    assert sorted(bd["idle_gaps"]) == [["block", 50e-9],
                                       ["solve_call", 50e-9]]


def test_idle_share_reader(reduced):
    from bench import harness, spec

    view = harness.TraceView(reduced, {}, {}, "TPU v5 lite")
    read = spec.metric_reader("device.idle_share")
    assert read(view) == pytest.approx(40.0)


def test_no_device_plane_gives_no_metric():
    red = trace.reduce([trace.Plane("/host:CPU", [trace.Line(
        "python", HOST)])])
    assert red.devices == []
    from bench import harness, spec

    view = harness.TraceView(red, {}, {}, "cpu")
    for name in ("device.idle_share", "gram_apply.ms_per_solve",
                 "gram_apply_roofline",
                 "gossip.collective_ms_per_solve"):
        assert spec.metric_reader(name)(view) is None


def test_collective_reader_takes_the_busiest_chip():
    from bench import harness, spec

    def dev(i, ns):
        return trace.Device(f"/device:TPU:{i}", 0,
                            trace.collections.Counter(
                                {"collective-permute-done.3": ns,
                                 "fusion.1": 99}),
                            trace.collections.Counter(), None)
    red = trace.Reduced(1000, 2, [dev(0, 4e6), dev(1, 6e6)])
    view = harness.TraceView(red, {}, {}, "TPU v5 lite")
    read = spec.metric_reader("gossip.collective_ms_per_solve")
    assert read(view) == pytest.approx(3.0)
