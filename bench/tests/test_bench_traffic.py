"""The traffic generator, against a stand-in server and clock."""
import numpy as np
import pytest

from bench import traffic


class FakeServer:
    """Answers every request at the next ``run()``, which takes
    ``run_s`` on the fake clock."""

    def __init__(self, clock, run_s):
        self.clock, self.run_s = clock, run_s
        self.queue, self.done, self.next_id = [], {}, 0

    def submit(self, images):
        self.next_id += 1
        self.queue.append((self.next_id, images))
        return self.next_id

    def run(self):
        self.clock.t += self.run_s
        n = len(self.queue)
        for rid, images in self.queue:
            self.done[rid] = images.reshape(len(images), -1)[:, :2]
        self.queue = []
        return {"ticks": n + 3, "injected_microbatches": n}

    def results(self, rid):
        return self.done.pop(rid)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


POISSON = {"loop": "open", "rate_per_s": 50, "arrival": "poisson",
           "images_per_request": 1, "pool_images": 8, "sample_requests": 4}


def test_every_seed_gets_the_same_gaps_in_another_order():
    a = traffic.arrivals(POISSON, 2**31 + 3, 2.0)
    b = traffic.arrivals(POISSON, 2**33 + 5, 2.0)
    assert len(a) == len(b) == 100
    ga, gb = np.diff(a, prepend=0), np.diff(b, prepend=0)
    assert not np.allclose(ga, gb)
    np.testing.assert_allclose(np.sort(ga), np.sort(gb))
    assert a[-1] == pytest.approx(2.0, rel=0.05)     # mean rate kept
    np.testing.assert_array_equal(a, traffic.arrivals(POISSON, 2**31 + 3,
                                                      2.0))


def test_bursts_share_their_due_time():
    due = traffic.arrivals(dict(POISSON, arrival="uniform", burst=4), 1, 1.0)
    assert len(due) == 52 and np.all(due[:4] == due[0])
    assert due[4] - due[0] == pytest.approx(4 / 50)


def test_open_loop_times_requests_from_when_they_came_due():
    clock = FakeClock()
    srv = FakeServer(clock, run_s=0.05)
    pool = np.arange(8 * 2, dtype=np.float32).reshape(8, 2, 1, 1)
    gen = traffic.Traffic(srv, pool, POISSON, seed=7, clock=clock,
                          sleep=clock.sleep)
    gen.warm_up()
    assert (gen.ticks, gen.injected, gen.next_image) == (0, 0, 0)
    t0 = clock()
    gen.start(t0, 1.0)
    reqs = []
    while clock() < t0 + 1.0 or gen.owed(t0 + 1.0):
        reqs += gen.round(t0 + 1.0)
    due = t0 + traffic.arrivals(POISSON, 7, 1.0)
    # every request that came due in the window is sent, none after it
    assert [r.submitted for r in reqs] == list(due)
    assert due[-1] <= t0 + 1.0 < clock()
    assert all(r.done >= r.submitted + 0.05 for r in reqs)
    # a request that came due while run() was busy waited for the next
    assert max(r.done - r.submitted for r in reqs) > 0.05
    assert gen.injected == len(reqs) == 50
    assert [r.first_image for r in reqs[:3]] == [0, 1, 2]


def test_closed_loop_rounds():
    clock = FakeClock()
    srv = FakeServer(clock, run_s=0.5)
    mix = {"loop": "closed", "requests_per_round": 3,
           "images_per_request": 2, "pool_images": 5, "sample_requests": 2}
    pool = np.zeros((5, 2, 1, 1), np.float32)
    gen = traffic.Traffic(srv, pool, mix, seed=1, clock=clock)
    gen.start(clock(), 10.0)
    reqs = gen.round(clock() + 10.0)
    assert len(reqs) == 3 and gen.ticks == 6
    assert [r.first_image for r in reqs] == [0, 2, 4]
    assert all(r.done - r.submitted == pytest.approx(0.5) for r in reqs)


@pytest.mark.parametrize("mix,missing", [
    ({"loop": "open", "rate_per_s": 1, "images_per_request": 1,
      "pool_images": 1, "sample_requests": 1}, ["arrival"]),
    ({"images_per_request": 1, "pool_images": 1, "sample_requests": 1},
     ["loop", "a loop of closed/open"]),
])
def test_missing_parameters_are_named(mix, missing):
    assert traffic.missing_keys(mix) == missing
