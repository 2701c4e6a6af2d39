"""The diagnostic that tells a run in which the machine stood still from a
quiet one: the clock thread notes late wake-ups, and `stalls` keeps what
fell into the window."""
from perfbench import metrics_lib as ml, runtime


def test_watch_clock_notes_a_late_wake_up_and_nothing_else(monkeypatch):
    waits = iter([False, False, False, True])
    clock = iter([0.0, 0.01, 0.51, 0.52])     # the second sleep took 0.5 s

    class Stop:
        def wait(self, _):
            return next(waits)

    class Clock:
        @staticmethod
        def monotonic():
            return next(clock)
    monkeypatch.setattr(runtime, "time", Clock)
    gaps = []
    runtime.watch_clock(Stop(), gaps)
    assert gaps == [(0.01, 0.5)]


def test_stalls_keeps_the_windows_gaps_and_the_longest_silence():
    run = {"t_win0": 100.0, "t_win1": 110.0,
           "records": [{"arrivals": [99.0, 101.0, 102.0]},
                       {"arrivals": [102.5, 106.5, 111.0]}],
           "client_clock_gaps": [(90.0, 0.2), (103.0, 1.5), (109.9, 0.3)],
           "replica_clock_gaps": [(103.0, 1.5), (120.0, 0.4)]}
    s = ml.stalls(run)
    assert s["longest_silence_s"] == 4.0          # 102.5 -> 106.5
    assert s["client_clock_gaps"] == [[3.0, 1.5], [9.9, 0.3]]
    assert s["replica_clock_gaps"] == [[3.0, 1.5]]
