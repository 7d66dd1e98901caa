"""The readers of the store's compaction-burst counters."""
import pytest

from perfbench import bench, harness


def ctx(before: dict, after: dict) -> harness.Run:
    return harness.Run(counters0=before, counters1=after)


@pytest.mark.parametrize("name", ["step_compactions_p99",
                                  "watermark_share"])
def test_reader_is_none_without_its_counters(name):
    """A store that does not count bursts (the parent of the change that
    added the counters) yields no reading, and no error."""
    plain = {"compactions": 7}
    assert bench.metric_reader(name)(ctx(plain, plain)) is None


@pytest.mark.parametrize("name", ["step_compactions_p99",
                                  "watermark_share"])
def test_reader_is_none_when_the_window_counted_nothing(name):
    c = {"jobs_by_trigger": [3, 4, 5], "steps_by_compactions": [9] * 10}
    assert bench.metric_reader(name)(ctx(c, c)) is None


@pytest.mark.parametrize("steps,want", [
    ([100] + [0] * 9, 0),                  # no step compacted
    ([98, 0, 1, 0, 0, 0, 0, 1, 0, 0], 3),  # rank 99 of 100: bucket 2-3
    ([98, 0, 0, 0, 0, 0, 0, 1, 1, 0], 127),
    ([0] * 9 + [5], 256),                  # the last bucket: 256 up
])
def test_step_compactions_p99_is_the_upper_edge_of_its_bucket(steps, want):
    before = {"steps_by_compactions": [7] * 10}
    after = {"steps_by_compactions": [7 + s for s in steps]}
    assert bench.metric_reader("step_compactions_p99")(
        ctx(before, after)) == want


def test_watermark_share_reads_window_deltas():
    before = {"jobs_by_trigger": [10, 20, 30]}
    after = {"jobs_by_trigger": [13, 29, 30]}       # 3 + 9 + 0 jobs
    assert bench.metric_reader("watermark_share")(
        ctx(before, after)) == pytest.approx(75.0)
