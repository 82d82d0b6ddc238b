"""The host part of `fluid.profiler`'s report (PR 38) on hand-made lists
of spans and device ops: parents by containment, self time, the idle time
under the innermost span open, every `sorted_key`.
"""
import pytest

from paddle_tpu import profiler


# ------------------------------------------------- the spans' arithmetic
def _span(name, start, end, thread="main", **stats):
    return (name, start, end - start, thread, stats)


# two steps of 10 ms on the device, 2 ms apart, in a session of 30 ms
OPS = [("fusion.1", 0.003, 0.006), ("tpu_custom_call/layer_norm_fwd.1",
                                    0.009, 0.001),
       ("tpu_custom_call/layer_norm_bwd.1", 0.010, 0.003),
       ("fusion.1", 0.015, 0.006), ("tpu_custom_call/layer_norm_fwd.1",
                                    0.021, 0.001),
       ("copy.7", 0.022, 0.003)]
SPANS = [
    _span("profiler.session", 0.0, 0.030),
    _span("executor.run", 0.0005, 0.0140, program=7, step=1),
    _span("executor.feed_put", 0.0010, 0.0020),
    _span("executor.prepare", 0.0020, 0.0025),
    _span("executor.step", 0.0025, 0.0040, step=1),
    _span("executor.fetch_readback", 0.0042, 0.0135, step=1),
    _span("executor.release", 0.0135, 0.0139),
    _span("executor.run", 0.0145, 0.0300, program=7, step=2),
    _span("executor.feed_put", 0.0146, 0.0148),
    _span("executor.step", 0.0148, 0.0160, step=2),
    _span("executor.fetch_readback", 0.0160, 0.0290, step=2),
]


def _gaps(ops=OPS, lo=0.0, hi=0.030):
    return profiler.idle_gaps([(s, d) for _, s, d in ops], lo, hi)


def test_parents_by_containment_on_one_thread_and_self_time():
    spans = SPANS + [_span("reader.fill", 0.0, 0.02, thread="other")]
    parent = profiler.parents_of(spans)
    assert parent == [None, 0, 1, 1, 1, 1, 1, 0, 7, 7, 7, None]
    own = profiler.self_seconds(spans, parent)
    # the session less its two runs; a run less its five children
    assert own[0] == pytest.approx(0.030 - 0.0135 - 0.0155)
    assert own[1] == pytest.approx(0.0135 - (0.0010 + 0.0005 + 0.0015
                                             + 0.0093 + 0.0004))
    assert own[11] == pytest.approx(0.02)
    # self times add up to the time the threads spent in any span
    assert sum(own) == pytest.approx(0.030 + 0.02)


def test_idle_time_goes_to_the_innermost_span_open_at_each_instant():
    idle = profiler.idle_by_span(_gaps(), SPANS)
    want = {"profiler.session": 0.0005 + 0.0005,   # the caller's own
            "executor.run": 0.0005 + 0.0001 + 0.0001 + 0.0010,
            "executor.feed_put": 0.0010 + 0.0002,
            "executor.prepare": 0.0005,
            "executor.step": 0.0005 + 0.0002,
            "executor.fetch_readback": 0.0005 + 0.0040,
            "executor.release": 0.0004}
    assert idle == pytest.approx(want)
    assert sum(idle.values()) == pytest.approx(0.030 - 0.020)
    # without a session span (another tool's file) the rest has no owner
    assert profiler.idle_by_span(_gaps(), SPANS[1:])[profiler.NO_SPAN] \
        == pytest.approx(0.0010)
    assert profiler.idle_by_span(_gaps(), []) \
        == pytest.approx({profiler.NO_SPAN: 0.010})


def test_one_gap_that_runs_through_three_spans_is_split_among_them():
    ops = [("fusion.1", 0.000, 0.001), ("fusion.1", 0.010, 0.001)]
    spans = [_span("executor.run", 0.000, 0.011),
             _span("executor.feed_put", 0.001, 0.002),
             _span("executor.prepare", 0.002, 0.009),
             _span("executor.step", 0.009, 0.011)]
    idle = profiler.idle_by_span(_gaps(ops, 0.0, 0.011), spans)
    assert idle == pytest.approx({"executor.feed_put": 0.001,
                                  "executor.prepare": 0.007,
                                  "executor.step": 0.001})
    # a span of another thread that opened later is the innermost one
    spans.append(_span("reader.fill", 0.004, 0.006, thread="other"))
    idle = profiler.idle_by_span(_gaps(ops, 0.0, 0.011), spans)
    assert idle["reader.fill"] == pytest.approx(0.002)
    assert idle["executor.prepare"] == pytest.approx(0.005)


def test_the_host_rows_add_up_to_the_sessions_host_and_idle_time():
    rows = profiler.host_rows(SPANS, _gaps())
    by = {r["span"]: r for r in rows}
    assert rows[0]["span"] == "profiler.session"         # by total
    assert by["executor.run"]["calls"] == 2
    assert by["executor.fetch_readback"]["total_ms"] == pytest.approx(22.3)
    assert by["executor.fetch_readback"]["idle_ms"] == pytest.approx(4.5)
    assert sum(r["self_ms"] for r in rows) == pytest.approx(30.0)
    assert sum(r["idle_ms"] for r in rows) == pytest.approx(10.0)
    # what lies under no span has a row of its own, last
    rows = profiler.host_rows(SPANS[1:], _gaps())
    assert rows[-1]["span"] == profiler.NO_SPAN
    assert sum(r["idle_ms"] for r in rows) == pytest.approx(10.0)


@pytest.mark.parametrize("key,first", [
    ("calls", "executor.feed_put"), ("total", "executor.run"),
    ("max", "executor.run"), ("min", "executor.run"),
    ("ave", "executor.run")])
def test_every_sorted_key_of_the_reference_orders_the_rows(key, first):
    spans = SPANS[1:] + [_span("executor.feed_put", 0.0291, 0.0292)]
    rows = profiler.host_rows(spans, [], key)
    assert rows[0]["span"] == first
    col = [r[profiler.SORT_KEYS[key]] for r in rows]
    assert col == sorted(col, reverse=True)

