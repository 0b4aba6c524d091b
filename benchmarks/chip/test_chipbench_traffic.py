"""The open-loop generator's schedule and the frame pools, from a seed."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")]

import frames  # noqa: E402
import traffic  # noqa: E402

BIG_SEED = 2**31 + 12345  # wider than a signed 32-bit integer
MIX = {"loop": "open", "streams": 5, "fps": 30, "phases": [0.1, 0.35, 0.4, 0.8, 0.95]}


def test_phases_lie_in_one_frame_period():
    for seed in (0, 1, BIG_SEED):
        ph = traffic.phases(MIX, seed)
        assert len(ph) == 5 and all(0.0 <= p < 1 / 30 for p in ph)


def test_every_seed_offers_the_same_arrivals_in_another_order():
    """The run's seed permutes the phase set and shifts it as a whole, so
    the gaps between consecutive cameras are the same set for every seed."""

    def gaps(seed):
        ph = sorted(p * 30 for p in traffic.phases(MIX, seed))
        return sorted(np.round(np.diff(ph + [ph[0] + 1.0]), 9))

    assert gaps(1) == gaps(2) == gaps(BIG_SEED)
    assert traffic.phases(MIX, 1) != traffic.phases(MIX, 2)
    np.testing.assert_allclose(gaps(1), sorted(np.diff(MIX["phases"] + [1.1])))

    with pytest.raises(ValueError):
        traffic.phases(dict(MIX, streams=4), 1)


@pytest.mark.parametrize("fps", [30, [30, 60, 25]])
def test_schedule_rates_and_window(fps):
    mix = dict(MIX, fps=fps, streams=3, phases=MIX["phases"][:3])
    seconds = 2.0
    sched = traffic.open_schedule(mix, BIG_SEED, seconds)
    assert [a.due for a in sched] == sorted(a.due for a in sched)
    assert all(0.0 <= a.due < seconds for a in sched)
    rates = traffic.stream_rates(mix)
    for s, (rate, ph) in enumerate(zip(rates, traffic.phases(mix, BIG_SEED))):
        mine = [a for a in sched if a.stream == s]
        assert [a.index for a in mine] == list(range(len(mine)))
        np.testing.assert_allclose(np.diff([a.due for a in mine]), 1 / rate)
        assert mine[0].due == pytest.approx(ph)
        assert len(mine) == int(np.ceil((seconds - ph) * rate))


def test_same_seed_same_schedule_and_frames():
    a = traffic.open_schedule(MIX, BIG_SEED, 1.0)
    b = traffic.open_schedule(MIX, BIG_SEED, 1.0)
    assert [(x.due, x.stream, x.index) for x in a] == [(x.due, x.stream, x.index) for x in b]
    f1 = frames.make_streams(BIG_SEED, 2, 3, 24, 40, 1.5)
    f2 = frames.make_streams(BIG_SEED, 2, 3, 24, 40, 1.5)
    f3 = frames.make_streams(BIG_SEED + 1, 2, 3, 24, 40, 1.5)
    assert f1.dtype == np.uint8 and f1.shape == (2, 3, 24, 40)
    assert np.array_equal(f1, f2) and not np.array_equal(f1, f3)
    assert not np.array_equal(f1[0], f1[1])  # each stream has its own scene


def test_pool_is_played_forward_and_back():
    assert [frames.ping_pong(t, 4) for t in range(9)] == [0, 1, 2, 3, 2, 1, 0, 1, 2]
    assert all(frames.ping_pong(t, 1) == 0 for t in range(3))


class _Engine:
    """Answers each submission after ``delay_s`` from a thread of its own,
    and records which thread submitted."""

    def __init__(self, delay_s):
        import concurrent.futures as cf

        self.pool = cf.ThreadPoolExecutor(4)
        self.delay_s = delay_s
        self.submitters = set()

    def submit(self, frame, stream_id=None):
        import threading
        import time

        self.submitters.add(threading.current_thread().name)
        return self.pool.submit(lambda: (time.sleep(self.delay_s), frame + 1)[1])


@pytest.mark.parametrize("threads", [1, 3])
def test_closed_loop_submits_from_its_own_thread_and_fetches_in_a_pool(threads):
    eng = _Engine(0.002)
    pool = np.zeros((4, 2, 3), np.uint8)
    outs, _, drained, fetch = traffic.run_closed(eng, pool, 4, 0.3, lambda oc: True, threads)
    eng.pool.shutdown()
    assert drained and len(fetch.threads) == threads
    assert not any(name.startswith("client-fetch") for name in eng.submitters)
    assert len(outs) > 8 and all(np.isfinite(oc.done) and oc.error is None for oc in outs)
    assert all(np.array_equal(oc.output, pool[0] + 1) for oc in outs)
    assert [oc.index for oc in outs] == list(range(len(outs)))
    assert 0.0 <= fetch.busy_share(0.3) <= 1.0


def test_fetch_pool_busy_share():
    f = traffic.Fetcher(0.0, lambda oc: False, 2)
    f.spans = [(-0.5, 0.5), (0.25, 0.75), (0.9, 2.0)]
    f.close()
    assert f.busy_share(1.0) == pytest.approx((0.5 + 0.5 + 0.1) / 2)
