import pytest

import stats


def test_nearest_rank_on_known_samples():
    samples = list(range(1, 101))  # 1..100
    assert stats.pctl(samples, 0.50) == 50
    assert stats.pctl(samples, 0.95) == 95
    assert stats.pctl(samples, 1.0) == 100
    assert stats.pctl([7.0], 0.95) == 7.0
    assert stats.median([3, 1, 2]) == 2
    assert stats.median([4, 1, 3, 2]) == 2.5


def test_samples_beyond():
    assert stats.samples_beyond(100, 0.95) == 5
    assert stats.samples_beyond(200, 0.95) == 10
    assert stats.samples_beyond(260, 0.95) == 13


def test_p95_refused_with_fewer_than_ten_beyond():
    with pytest.raises(stats.TooFewSamples, match="5 beyond"):
        stats.tail(list(range(100)), 0.95)
    assert stats.tail(list(range(200)), 0.95) == 189


def test_empty_is_refused():
    with pytest.raises(stats.TooFewSamples):
        stats.pctl([], 0.5)
    with pytest.raises(stats.TooFewSamples):
        stats.median([])


def test_rule_is_held_to_the_window_as_designed():
    # A 40 s window of 167 ms steps that lost 8 s to one stall of the host:
    # 191 samples realised, 239 by design, so the tail is printed.
    stalled = [167.0] * 190 + [8270.0]
    with pytest.raises(stats.TooFewSamples):
        stats.tail(stalled, 0.95)
    assert stats.tail(stalled, 0.95, window=40_000.0) == 167.0
    # A 10 s trial window holds 59 such steps, and stays refused.
    with pytest.raises(stats.TooFewSamples, match="59 samples"):
        stats.tail([167.0] * 59, 0.95, window=10_000.0)


def test_capture_p95_reader_survives_a_stalled_window():
    import cells

    reader = cells.load_readers()["step_ms_p95.capture"]
    run = {"step_ms": [167.0] * 190 + [8270.0], "window_s": 40.05}
    assert reader.read(run) == 167.0
    assert reader.read({"step_ms": [167.0] * 59, "window_s": 10.0}) is None
