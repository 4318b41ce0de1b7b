"""`reduce_trace` on a synthetic ProfileData-shaped trace gives the busy
union, idle share, kernel time by name and gap attribution worked by hand."""
import pytest
from jax.profiler import ProfileData

from benchmark import reduce_trace

US = 1_000_000  # picoseconds in a microsecond


def ev(mid, start_us, dur_us):
    return f"events {{ metadata_id: {mid} offset_ps: {int(start_us * US)} duration_ps: {int(dur_us * US)} }}"


def space(dev_events, host_events, second_device=None):
    dev_meta = 'event_metadata { key: 1 value { id: 1 name: "fusion.7" } } ' \
               'event_metadata { key: 2 value { id: 2 name: "paged_attention.3" } } ' \
               'event_metadata { key: 3 value { id: 3 name: "while.1" } }'
    host_meta = " ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for i, n in enumerate(("bench_trace_window", "llm_decode_tick",
                               "llm_prefill_chunk", "other"), 1))
    planes = [f'planes {{ id: 1 name: "/device:TPU:0" {dev_meta} lines {{ id: 1 '
              f'name: "XLA Ops" timestamp_ns: 0 {" ".join(dev_events)} }} '
              f'lines {{ id: 2 name: "Steps" timestamp_ns: 0 {ev(1, 0, 1000)} }} }}']
    if second_device is not None:
        planes.append(f'planes {{ id: 3 name: "/device:TPU:1" {dev_meta} lines {{ id: 1 '
                      f'name: "XLA Ops" timestamp_ns: 0 {" ".join(second_device)} }} }}')
    planes.append(f'planes {{ id: 2 name: "/host:CPU" {host_meta} lines {{ id: 9 '
                  f'name: "main" timestamp_ns: 0 {" ".join(host_events)} }} }}')
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace("\n".join(planes)))


# window 100..200 us.  device: while 110-150 holding fusion 110-130 and
# paged 130-150 (union 40), paged 160-170 (10), fusion 195-230 clipped to 5.
# idle: 100-110, 150-160, 170-195 = 45 of 100.
DEV = [ev(3, 110, 40), ev(1, 110, 20), ev(2, 130, 20), ev(2, 160, 10), ev(1, 195, 35),
       ev(1, 10, 20)]  # the last lies before the window
HOST = [ev(1, 100, 100), ev(2, 105, 50), ev(3, 108, 4), ev(4, 168, 30)]


def test_busy_union_idle_share_and_kernel_time_by_hand():
    red = reduce_trace.reduce(space(DEV, HOST))
    assert red["window_s"] == pytest.approx(100e-6)
    assert red["busy_s"] == pytest.approx(55e-6)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(0.45)
    assert red["ops"]["paged_attention.3"] == pytest.approx(30e-6)
    assert red["ops"]["fusion.7"] == pytest.approx(25e-6)
    assert reduce_trace.seconds_matching(red["ops"], ("paged_attention",)) == pytest.approx(30e-6)
    assert reduce_trace.seconds_matching(red["ops"], ("flash_fwd",)) == 0


def test_gaps_go_to_the_innermost_host_span_open_at_their_middle():
    red = reduce_trace.reduce(space(DEV, HOST))
    # 100-110: middle 105 lies in the tick (105-155) only;  wait: the chunk
    # span 108-112 does not hold 105.  150-160: middle 155 is past the tick.
    # 170-195: nothing but `other`, which is not a mark.
    assert red["gaps"] == pytest.approx({"llm_decode_tick": 10e-6, "unattributed": 35e-6})
    b = reduce_trace.breakdown(red)
    assert b["device_ops"][0] == ["while", pytest.approx(40e-6)]
    assert ["paged_attention", pytest.approx(30e-6)] in b["device_ops"]
    assert b["idle_gaps"][0][0] == "unattributed" and len(b["idle_gaps"]) == 2


def test_innermost_mark_wins_and_devices_are_averaged():
    host = [ev(1, 100, 100), ev(2, 100, 60), ev(3, 150, 10)]
    red = reduce_trace.reduce(space([ev(1, 100, 50), ev(1, 160, 40)], host,
                                    second_device=[ev(1, 100, 30)]))
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx((90e-6 + 30e-6) / 2)
    assert red["ops"]["fusion.7"] == pytest.approx(60e-6)
    assert red["gaps"] == pytest.approx({"llm_prefill_chunk": 10e-6})


def test_without_a_window_mark_the_device_events_span_the_window():
    red = reduce_trace.reduce(space([ev(1, 10, 10), ev(2, 40, 10)], [ev(4, 0, 5)]))
    assert red["window_s"] == pytest.approx(40e-6)
    assert red["busy_s"] == pytest.approx(20e-6)


def test_a_trace_with_no_device_operation_is_an_error():
    with pytest.raises(ValueError, match="no device operation"):
        reduce_trace.reduce(space([], HOST))


@pytest.mark.parametrize("ivs,want", [
    ([(0, 1), (2, 3)], 2), ([(0, 5), (1, 2), (4, 7)], 7), ([], 0), ([(3, 4), (0, 4)], 4)])
def test_union(ivs, want):
    assert reduce_trace.union_seconds(ivs) == want
