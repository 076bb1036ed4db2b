"""Whole-step windows: the rate is whole steps over their span."""
import pytest

from bench.train_cell import whole_step_window


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("step_s,seconds,steps", [
    (0.58, 20.0, 35), (0.5, 20.0, 40), (1.3, 1.0, 1), (0.25, 30.0, 120)])
def test_window_ends_on_first_boundary_past_seconds(step_s, seconds, steps):
    clock = Clock()
    seen = []

    def step(k):
        seen.append(k)
        clock.t += step_s
    n, span = whole_step_window(step, seconds, clock)
    assert n == steps and seen == list(range(steps))
    assert span == pytest.approx(steps * step_s)
    assert span >= seconds > span - step_s


def test_rate_does_not_jump_with_a_step_at_the_edge():
    """Counting steps that fit a fixed time would read 34 or 35 steps in
    20 s for a 0.58 s step; whole steps over their span read the step."""
    for step_s in (0.579, 0.581):
        clock = Clock()

        def step(k):
            clock.t += step_s
        n, span = whole_step_window(step, 20.0, clock)
        assert n / span == pytest.approx(1 / step_s)
