"""Device idle charged to the program's ``ff.`` spans, on a small
synthetic trace whose answer is known."""

import pytest

from benchmark import span_report


def _event(meta, start_ps, dur_ps):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ps} "
            f"duration_ps: {dur_ps} }}")


def _xspace() -> str:
    ms = 1_000_000_000  # ps per ms
    dev = lambda n, evs: (
        f'planes {{ id: {n + 1} name: "/device:TPU:{n}" '
        f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000000 '
        f'{" ".join(evs)} }} '
        'event_metadata { key: 1 value { id: 1 name: '
        '"%fusion.7 = s32[8] fusion(s32[8] %p)" } } }')
    # Window 0-10 ms. TPU:0 busy 0-2 and 6-7 ms (idle 2-6, 7-10: 7 ms);
    # TPU:1 busy 4-5 ms (idle 0-4, 5-10: 9 ms); TPU:2 runs nothing.
    tpu0 = [_event(1, 0, 2 * ms), _event(1, 6 * ms, 1 * ms)]
    tpu1 = [_event(1, 4 * ms, 1 * ms)]
    # Host: ff.storm.stage.scatter 1-3 ms and 8-9 ms (two events);
    # ff.wal.fsync 2.5-4.5 ms on another line; a runtime event 5-10 ms
    # that is not a program span; one scatter event past the window.
    host = ('planes { id: 9 name: "/host:CPU" '
            'lines { id: 1 name: "main" timestamp_ns: 1000000 '
            + _event(1, 0, 10 * ms) + _event(2, 1 * ms, 2 * ms)
            + _event(2, 8 * ms, 1 * ms) + _event(2, 12 * ms, 1 * ms)
            + ' } '
            'lines { id: 2 name: "group-commit-wal" timestamp_ns: 1000000 '
            + _event(3, 5 * ms // 2, 2 * ms) + _event(4, 5 * ms, 5 * ms)
            + ' } '
            'event_metadata { key: 1 value { id: 1 name: "bench.trace" } } '
            'event_metadata { key: 2 value { id: 2 name: '
            '"ff.storm.stage.scatter" } } '
            'event_metadata { key: 3 value { id: 3 name: "ff.wal.fsync" } } '
            'event_metadata { key: 4 value { id: 4 name: "XlaLinearize" } } '
            '}')
    return dev(0, tpu0) + dev(1, tpu1) + dev(2, []) + host


@pytest.fixture(scope="module")
def spans():
    from jax.profiler import ProfileData

    return span_report.spans(ProfileData.from_text_proto(_xspace()))


def test_span_host_time_and_count_clipped_to_the_window(spans):
    scatter = spans["by_name"]["ff.storm.stage.scatter"]
    assert scatter["count"] == 2
    assert scatter["host_s"] == pytest.approx(0.003)
    assert spans["by_name"]["ff.wal.fsync"]["host_s"] == pytest.approx(0.002)
    assert set(spans["by_name"]) == {"ff.storm.stage.scatter",
                                     "ff.wal.fsync"}


def test_span_idle_is_the_mean_over_chips_that_ran(spans):
    # scatter [1,3]+[8,9]: TPU:0 idle overlap [2,3]+[8,9] = 2 ms; TPU:1
    # [1,3]+[8,9] = 3 ms; mean 2.5 ms. fsync [2.5,4.5]: TPU:0 2 ms,
    # TPU:1 [2.5,4] = 1.5 ms; mean 1.75 ms.
    by = spans["by_name"]
    assert by["ff.storm.stage.scatter"]["idle_s"] == pytest.approx(0.0025)
    assert by["ff.wal.fsync"]["idle_s"] == pytest.approx(0.00175)


def test_idle_no_span_covers(spans):
    # Spanned [1,4.5]+[8,9]. TPU:0 idle 7 ms, covered [2,4.5]+[8,9] =
    # 3.5 ms -> 3.5 unspanned; TPU:1 idle 9 ms, covered [1,4]+[8,9] = 4
    # -> 5 unspanned; mean 4.25 ms.
    assert spans["idle_unspanned_s"] == pytest.approx(0.00425)


def test_overlap_of_interval_lists():
    assert span_report._overlap([(0, 4), (6, 8)], [(3, 7)]) == 2
    assert span_report._overlap([(0, 1)], [(2, 3)]) == 0


def test_span_window_leaves_out_the_edges(spans):
    # Stage spans run from 1 ms to 9 ms. Idle inside: TPU:0 [2,6]+[7,9]
    # = 6 ms, TPU:1 [1,4]+[5,9] = 7 ms; uncovered: TPU:0 2.5 ms, TPU:1
    # 3 ms.
    assert spans["span_window_s"] == pytest.approx(0.008)
    assert spans["idle_in_span_window_s"] == pytest.approx(0.0065)
    assert spans["idle_unspanned_in_span_window_s"] == pytest.approx(0.00275)
