"""The reduction from a trace to numbers, on made-up intervals and on a
small trace recorded on the TPU v5e (``data/tiny_train.xplane.pb``)."""

import os

import pytest

from chipbench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_busy_is_the_union_of_nested_and_overlapping_ops():
    ev = [("while", 0.0, 10.0), ("fusion.1", 1.0, 4.0), ("fusion.2", 3.0, 6.0),
          ("copy", 12.0, 13.0)]
    assert trace.union((s, e) for _, s, e in ev) == [(0.0, 10.0),
                                                     (12.0, 13.0)]
    assert trace.busy_seconds(ev) == pytest.approx(11.0)
    assert trace.busy_seconds(trace.clip(ev, 5.0, 12.5)) == pytest.approx(5.5)


def test_self_time_leaves_a_container_its_overhead_only():
    ev = [("while.3", 0.0, 10.0), ("flash_attention_fwd", 1.0, 4.0),
          ("fusion.7", 4.0, 9.0), ("flash_attention_fwd", 20.0, 21.0)]
    own = trace.self_times(ev)
    assert own["while.3"] == pytest.approx(2.0)
    assert own["flash_attention_fwd"] == pytest.approx(4.0)
    assert trace.kernel_seconds(ev, "flash_attention") == pytest.approx(4.0)
    assert trace.top(own, 2)[0] == ["fusion.7", pytest.approx(5.0)]


def test_idle_gaps_are_charged_to_the_span_the_host_was_in():
    ev = [("a", 1.0, 2.0), ("b", 3.0, 4.0), ("c", 4.5, 9.0)]
    spans = [("chipbench::window", 0.0, 10.0),
             ("chipbench::feed", 2.0, 3.2), ("chipbench::step", 3.9, 9.5)]
    gaps = trace.idle_gaps(ev, spans, 0.0, 10.0)
    assert gaps["chipbench::feed"] == pytest.approx(1.0)      # 2..3
    assert gaps["chipbench::step"] == pytest.approx(0.5)      # 4..4.5
    assert gaps["chipbench::window"] == pytest.approx(2.0)    # 0..1, 9..10
    assert sum(gaps.values()) == pytest.approx(
        10.0 - trace.busy_seconds(ev))
    assert trace.idle_gaps(ev, [], 0.0, 10.0) == {
        "unattributed": pytest.approx(3.5)}


def test_exposed_collective_time_is_what_no_compute_hides():
    ev = [("while", 0.0, 20.0),
          ("fusion.1", 0.0, 4.0),
          ("all-reduce-start.2", 3.0, 3.1), ("all-reduce-done.2", 3.1, 6.0),
          ("fusion.2", 5.0, 8.0),
          ("all-gather.5", 10.0, 12.0),
          ("reduce-scatter.1", 13.0, 14.0), ("fusion.3", 12.5, 15.0)]
    # all-reduce 3..6 is hidden but for 4..5; all-gather wholly exposed;
    # reduce-scatter wholly hidden
    assert trace.exposed_collective_seconds(ev) == pytest.approx(3.0)
    assert trace.exposed_collective_seconds(
        [("fusion", 0.0, 1.0)]) == 0.0
    # an asynchronous collective lives on the async line as one span
    sync = [("fusion.1 f32[8]", 0.0, 4.0), ("fusion.2 f32[8]", 5.0, 8.0)]
    asyn = [("all-reduce-start.2 f32[8]", 3.0, 6.0), ("copy-start.1", 0.0, 9.0)]
    assert trace.exposed_collective_seconds(sync, asyn) == pytest.approx(1.0)


def test_window_is_found_by_its_span():
    spans = [("chipbench::feed", 1.0, 2.0), ("chipbench::window", 0.5, 9.0)]
    assert trace.window_of(spans) == (0.5, 9.0)
    with pytest.raises(ValueError):
        trace.window_of([("chipbench::feed", 1.0, 2.0)])


def test_recorded_tpu_trace():
    path = os.path.join(DATA, "tiny_train.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace kept yet")
    raw = trace.load(path)
    assert 0 in raw["devices"] and raw["devices"][0]
    lo, hi = trace.window_of(raw["spans"])
    ev = trace.clip(raw["devices"][0], lo, hi)
    busy = trace.busy_seconds(ev)
    assert 0 < busy <= hi - lo
    gaps = trace.idle_gaps(ev, raw["spans"], lo, hi)
    assert sum(gaps.values()) == pytest.approx(hi - lo - busy, rel=1e-6)
    own = trace.self_times(ev)
    assert sum(own.values()) == pytest.approx(busy, rel=0.05)
    assert any(n.startswith("chipbench::step") for n, _, _ in raw["spans"])


def test_peak_memory_is_read_only_where_the_steps_set_it():
    from types import SimpleNamespace

    from chipbench.readers import peak_hbm

    env = lambda **res: SimpleNamespace(res=res)      # noqa: E731
    assert peak_hbm.read(env(memory_peak_bytes=10_300_000_000,
                             memory_peak_built_bytes=6_000_000_000)) \
        == pytest.approx(10.3)
    # set-up had already stood as high: nothing to read of the step
    assert peak_hbm.read(env(memory_peak_bytes=16_000_000_000,
                             memory_peak_built_bytes=16_000_000_000)) is None
    assert peak_hbm.read(env()) is None
