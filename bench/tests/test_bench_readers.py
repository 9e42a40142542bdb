"""The end-to-end readers take all the work and all the time of the
window; the counter readers read the window's change."""
import os

import pytest

import bench_cpu
from bench import loops
from bench.harness import Window, load_module


def _reader(name):
    return load_module(os.path.join(bench_cpu.ROOT, "bench", "metrics",
                                    name + ".py"), "t_" + name).read


class _Server:
    """Answers one request per step after `service_s`."""

    def __init__(self, service_s):
        self.q, self.rid, self.service_s = [], 0, service_s

    def submit(self, text):
        self.rid += 1
        self.q.append(self.rid)
        return self.rid

    def step(self):
        import time
        time.sleep(self.service_s)
        return [type("R", (), {"request_id": self.q.pop(0)})()]


def test_closed_loop_serves_in_flight_answers_after_the_close():
    s = loops.closed_loop(_Server(0.02), lambda k: "q", 3, 0.2,
                          loops.Hooks())
    assert all(d is not None for d in s.delivered)
    assert s.t_end > s.t1 and len(s.submitted) >= 9
    # a request sent before the close and answered after it counts in
    # no window rate
    w = Window(0.2, 1.0, s, [], {}, None)
    late = sum(1 for d in s.delivered if d > s.t1)
    assert late >= 1
    assert _reader("qps")(w) == pytest.approx(
        (len(s.submitted) - late) / 0.2)


def test_qps_follows_the_server():
    fast = loops.closed_loop(_Server(0.005), lambda k: "q", 1, 0.3,
                             loops.Hooks())
    slow = loops.closed_loop(_Server(0.02), lambda k: "q", 1, 0.3,
                             loops.Hooks())
    q = [_reader("qps")(Window(0.3, 1.0, s, [], {}, None))
         for s in (fast, slow)]
    assert q[0] > 2 * q[1] > 0


def test_counter_and_span_readers():
    s = loops.Served([0.0] * 8, [0.1] * 8, {}, [], t0=0.0, t1=1.0,
                     t_end=1.0)
    w = Window(1.0, 12.5, s, [], {"escalations": 6}, None)
    assert _reader("escalations_per_query")(w) == pytest.approx(0.75)
    assert _reader("setup_s")(w) == 12.5
    assert _reader("device_idle_pct")(w) is None
    assert _reader("dispatch_ms")(w) is None
    span = type("S", (), {"name": "dispatch", "t0": 0.0, "t1": 0.004})
    w.spans = [span, span]
    assert _reader("dispatch_ms")(w) == pytest.approx(4.0)
    w.device_trace = {"busy_s": 0.25, "window_s": 1.0}
    assert _reader("device_idle_pct")(w) == pytest.approx(75.0)
    empty = Window(1.0, 1.0, loops.Served([], [], {}, []), [],
                   {"escalations": 0}, None)
    assert _reader("escalations_per_query")(empty) is None
