"""The trace reduction on a small synthetic trace whose answer is known."""

import pytest

from benchmark import trace_reduce


def _event(meta, start_ps, dur_ps):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ps} "
            f"duration_ps: {dur_ps} }}")


def _xspace() -> str:
    ms = 1_000_000_000  # ps per ms
    kernel = ('%_tick.1 = (s32[8]) custom-call(s32[8] %p), '
              'custom_call_target=\\"tpu_custom_call\\"')
    dev = lambda n, evs: (
        f'planes {{ id: {n + 1} name: "/device:TPU:{n}" '
        f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000000 '
        f'{" ".join(evs)} }} '
        'lines { id: 2 name: "XLA Modules" timestamp_ns: 1000000 '
        + _event(3, 0, 6 * ms) + ' } '
        f'event_metadata {{ key: 1 value {{ id: 1 name: "{kernel}" }} }} '
        'event_metadata { key: 2 value { id: 2 name: '
        '"%fusion.7 = s32[8] fusion(s32[8] %p)" } } '
        'event_metadata { key: 3 value { id: 3 name: '
        '"jit__storm_tick(1234)" } } }')
    # TPU:0 busy 0-2 ms (kernel), 1-3 ms (fusion, overlaps), 6-7.5 ms;
    # TPU:1 busy 4-5 ms (kernel). The program jit__storm_tick runs 0-6 ms
    # on each chip. Host span 0-8 ms; host work 3-6 ms.
    tpu0 = [_event(1, 0, 2 * ms), _event(2, 1 * ms, 2 * ms),
            _event(2, 6 * ms, 3 * ms // 2), _event(2, 9 * ms, 1 * ms)]
    tpu1 = [_event(1, 4 * ms, 1 * ms)]
    host = ('planes { id: 9 name: "/host:CPU" lines { id: 1 name: "main" '
            'timestamp_ns: 1000000 '
            + _event(1, 0, 8 * ms) + _event(2, 3 * ms, 3 * ms) + ' } '
            'event_metadata { key: 1 value { id: 1 name: "bench.trace" } } '
            'event_metadata { key: 2 value { id: 2 name: "WalFsync" } } }')
    return dev(0, tpu0) + dev(1, tpu1) + host


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    profile = ProfileData.from_text_proto(_xspace())
    return trace_reduce.reduce(profile, {"map_fold": "_storm_tick",
                                         "absent": "_nothing_"})


def test_window_is_the_host_span(summary):
    assert summary["window_s"] == pytest.approx(0.008)


def test_busy_is_the_union_clipped_to_the_window(summary):
    # TPU:0: [0, 3] + [6, 7.5] ms (the 9-10 ms op lies outside the
    # window); TPU:1: [4, 5] ms. Mean over the two chips: (4.5 + 1) / 2.
    devs = summary["devices"]
    assert devs["/device:TPU:0"]["busy_s"] == pytest.approx(0.0045)
    assert devs["/device:TPU:1"]["busy_s"] == pytest.approx(0.001)
    assert summary["busy_s"] == pytest.approx(0.00275)


def test_kernel_is_a_custom_call_in_its_program(summary):
    assert summary["kernels"]["map_fold"]["count"] == 2
    assert summary["kernels"]["map_fold"]["time_s"] == pytest.approx(0.003)
    assert "absent" not in summary["kernels"]


def test_top_ops_and_labelled_gaps(summary):
    names = [name for name, _ in summary["device_ops"]]
    assert names[0] == "fusion.7"
    label, secs = summary["idle_gaps"][0]
    # The longest gap is TPU:1's 0-4 ms; of the host's events the
    # WalFsync (3-6 ms) overlaps it most.
    assert secs == pytest.approx(0.004)
    assert label == "WalFsync"


def test_short_name():
    assert trace_reduce.short_name("%fusion.3 = s32[8] fusion()") \
        == "fusion.3"
    assert trace_reduce.short_name("WalFsync") == "WalFsync"


def test_union():
    assert trace_reduce.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert trace_reduce.union([(0, 1), (2, 3)]) == [(0, 1), (2, 3)]
