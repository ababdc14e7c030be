"""The window's arithmetic on a fake clock."""
from chipbench import window


class FakeLoop:
    """A device that takes ``step_s`` a step, a loader that takes
    ``fetch_s[i]`` for batch i; time only moves when someone waits."""

    def __init__(self, step_s, fetch_s):
        self.now, self.step_s, self.fetch_s = 0.0, step_s, fetch_s
        self.device_free_at, self.n = 0.0, 0

    def clock(self):
        return self.now

    def next_batch(self):
        self.now += self.fetch_s(self.n)
        self.n += 1
        return self.n

    def dispatch(self, batch):
        start = max(self.now, self.device_free_at)
        self.device_free_at = start + self.step_s
        return self.device_free_at

    def readback(self, done_at):
        self.now = max(self.now, done_at)
        return 1.0


def run(fetch_s, seconds=10.0, step_s=0.1):
    loop = FakeLoop(step_s, fetch_s)
    return window.closed_loop(loop.next_batch, loop.dispatch, loop.readback,
                              seconds, clock=loop.clock)


def test_fed_loop_runs_at_the_device_rate():
    log = run(lambda n: 0.01)
    assert log.steps == len(log.wait_s) == len(log.losses)
    assert abs(window.rate_per_chip(log, 256, 1) - 2560) < 30
    assert abs(window.percentile(window.step_intervals_ms(log), 95) - 100) < 1
    assert window.input_stall_pct(log) < 11
    assert log.t_close >= 10.0          # closes on the last step's readback


def test_a_stall_lowers_the_rate_and_raises_the_tail():
    fed = run(lambda n: 0.01)
    stalled = run(lambda n: 1.0 if n % 10 == 5 else 0.01)
    assert (window.rate_per_chip(stalled, 256, 1)
            < 0.6 * window.rate_per_chip(fed, 256, 1))
    assert window.percentile(window.step_intervals_ms(stalled), 95) > 800
    assert window.percentile(window.step_intervals_ms(fed), 95) < 110
    assert window.input_stall_pct(stalled) > 40


def test_at_most_two_steps_in_flight():
    in_flight, worst = [0], [0]
    t = [0.0]

    def dispatch(batch):
        in_flight[0] += 1
        worst[0] = max(worst[0], in_flight[0])
        return batch

    def readback(handle):
        in_flight[0] -= 1
        t[0] += 0.1
        return 0.0

    log = window.closed_loop(lambda: 0, dispatch, readback, 1.0,
                             clock=lambda: t[0])
    assert worst[0] == window.IN_FLIGHT == 2 and in_flight[0] == 0
    assert log.steps >= 10


def test_percentile_is_nearest_rank_over_every_value():
    assert window.percentile(list(range(1, 101)), 95) == 95
    assert window.percentile([5.0], 95) == 5.0
    assert window.percentile([1, 1, 1, 1, 1, 1, 1, 1, 1, 50], 95) == 50
