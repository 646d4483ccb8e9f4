"""``EpochClock`` on a fake clock: when the stop is asked for, which epochs
the window holds and which one the profiler runs over, with and without a
traffic's ``warm_epochs`` / ``window_epochs``."""

import pytest

from benchmark.harness import train_window
from benchmark.harness.train_window import WARM_EPOCHS, EpochClock


class Compiles:
    armed = False


class FakeTime:
    """``perf_counter`` that advances by one epoch's length a reading."""

    def __init__(self, epoch_s):
        self.now, self.epoch_s = 100.0, epoch_s

    def perf_counter(self):
        self.now += self.epoch_s
        return self.now

    def time(self):
        return self.now


def drive(monkeypatch, seconds, epoch_s=1.0, trace=False, **keys):
    """Report epochs until the clock asks for the stop (at most 200); returns
    the clock and the index of the epoch whose record asked."""
    fake = FakeTime(epoch_s)
    monkeypatch.setattr(train_window, "time", fake)
    stops, traced = [], []
    clock = EpochClock(seconds, Compiles(), lambda: stops.append(1),
                       trace_dir="x" if trace else "", **keys)
    monkeypatch.setattr(clock, "_start_trace", lambda: (
        traced.append(("start", len(clock.ends) - 1)),
        setattr(clock, "_tracing", True)))
    monkeypatch.setattr(clock, "_stop_trace", lambda: (
        traced.append(("stop", len(clock.ends) - 1)),
        setattr(clock, "_tracing", False), setattr(clock, "traced", {})))
    for epoch in range(200):
        clock.log_metrics({"loss": 1.0, "val_loss": 1.0})
        if stops:
            return clock, epoch, traced
    raise AssertionError("the clock never asked for the stop")


def test_a_traffic_without_the_keys_gives_todays_window(monkeypatch):
    clock, asked_at, _ = drive(monkeypatch, seconds=3.5)
    assert WARM_EPOCHS == 2 and clock.warm == 2
    # opens at epoch 1's record; 3.5 s of 1 s epochs are in at epoch 5's
    assert asked_at == 5
    window = clock.window(steps_per_epoch=8, items_per_step=10)
    assert window["epochs"] == 4 and window["steps"] == 32
    assert window["span_s"] == pytest.approx(4.0)
    assert window["items_per_s"] == pytest.approx(80.0)
    assert len(window["epoch_s"]) == 4


@pytest.mark.parametrize("seconds,window_epochs,epochs", [
    (3.5, 9, 9),      # the seconds are in long before the epochs: epochs hold
    (20.0, 9, 20),    # the epochs are in long before the seconds: seconds hold
    (9.0, 9, 9),      # both at the same record
    (3.5, 0, 4),      # no least count: the seconds alone
])
def test_the_stop_is_asked_only_when_seconds_and_epochs_are_both_in(
        monkeypatch, seconds, window_epochs, epochs):
    clock, asked_at, _ = drive(monkeypatch, seconds, warm_epochs=5,
                               window_epochs=window_epochs)
    window = clock.window(8, 10)
    assert window["epochs"] == epochs
    assert asked_at == 5 - 1 + epochs
    # the window opens at the LAST warm epoch's record
    assert window["span_s"] == pytest.approx(epochs * 1.0)


def test_every_seed_holds_the_same_epochs_whatever_its_epochs_take(
        monkeypatch):
    """A seed whose routing makes its epochs longer holds as many."""
    held = {drive(monkeypatch, 20.0, epoch_s=s, warm_epochs=4,
                  window_epochs=6)[0].window(8, 10)["epochs"]
            for s in (3.4, 3.9, 4.4)}
    assert held == {6}


@pytest.mark.parametrize("keys,second", [({}, 3),
                                         ({"warm_epochs": 5,
                                           "window_epochs": 6}, 6)])
def test_the_traced_epoch_is_the_windows_second(monkeypatch, keys, second):
    clock, asked_at, traced = drive(monkeypatch, 0.5, trace=True, **keys)
    # started at the record that ends the window's first epoch, stopped at
    # the record that ends its second
    assert traced == [("start", second - 1), ("stop", second)]
    assert clock.trace_epoch == second == clock.warm + 1
    # a traced run never stops before its trace is in
    assert asked_at >= second


def test_the_collector_is_frozen_and_compiles_armed_at_the_windows_opening(
        monkeypatch):
    frozen = []
    monkeypatch.setattr(train_window.gc, "freeze",
                        lambda: frozen.append(len(clock.ends)))
    fake = FakeTime(1.0)
    monkeypatch.setattr(train_window, "time", fake)
    compiles = Compiles()
    clock = EpochClock(2.0, compiles, lambda: None, warm_epochs=4)
    armed_at = []
    for epoch in range(8):
        clock.log_metrics({})
        armed_at.append(compiles.armed)
    assert frozen == [3]                  # before the 4th record is stamped
    assert armed_at[:3] == [False] * 3 and armed_at[3] is True
    assert armed_at[-1] is False          # disarmed with the stop


def test_fewer_than_two_warm_epochs_are_refused():
    with pytest.raises(ValueError):
        EpochClock(1.0, Compiles(), lambda: None, warm_epochs=1)
