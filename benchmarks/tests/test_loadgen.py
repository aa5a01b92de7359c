"""The load generator's schedule: equal for equal seeds, different
otherwise, and the same set of sizes and gaps whatever the seed."""
import numpy as np
import pytest

from benchmarks import common, loadgen

DATA = common.HERE + "/tests/data/traffic/"
BIG = 3_000_000_019          # more than 32 signed bits hold


def _traffic(name):
    return common.load_json(DATA + name + ".json")


def _key(schedule):
    return [(r.index, r.prompt.tolist(), r.max_new, r.due)
            for r in schedule]


@pytest.mark.parametrize("name", ["tiny-open", "tiny-closed"])
def test_equal_seed_equal_schedule(name):
    a = loadgen.make_schedule(_traffic(name), seed=BIG, seconds=5,
                              vocab_size=256)
    b = loadgen.make_schedule(_traffic(name), seed=BIG, seconds=5,
                              vocab_size=256)
    c = loadgen.make_schedule(_traffic(name), seed=BIG + 1, seconds=5,
                              vocab_size=256)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("name", ["tiny-open", "tiny-closed"])
def test_every_seed_gets_the_same_work(name):
    a = loadgen.make_schedule(_traffic(name), seed=1, seconds=5,
                              vocab_size=256)
    c = loadgen.make_schedule(_traffic(name), seed=2, seconds=5,
                              vocab_size=256)
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in c)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]


def test_open_schedule_keeps_its_rate_and_limits():
    traffic = _traffic("tiny-open")
    sched = loadgen.make_schedule(traffic, seed=5, seconds=20,
                                  vocab_size=256)
    span = traffic["warmup_s"] + 20
    assert len(sched) == round(traffic["rate_per_s"] * span)
    due = np.array([r.due for r in sched])
    assert due[0] == pytest.approx(-traffic["warmup_s"])
    assert np.all(np.diff(due) > 0)
    assert due[-1] < 20
    lens = [len(r.prompt) for r in sched]
    assert min(lens) >= traffic["prompt_len"]["min"]
    assert max(lens) <= traffic["prompt_len"]["max"]
    assert abs(float(np.median(lens)) - traffic["prompt_len"]["median"]) <= 2
    assert all(1 <= t < 256 for r in sched for t in r.prompt)


def test_rate_override_is_read_from_the_environment(monkeypatch):
    traffic = _traffic("tiny-open")
    base = loadgen.make_schedule(traffic, seed=5, seconds=10, vocab_size=256)
    monkeypatch.setenv(loadgen.RATE_ENV, str(2 * traffic["rate_per_s"]))
    fast = loadgen.make_schedule(traffic, seed=5, seconds=10, vocab_size=256)
    assert len(fast) == 2 * len(base)


def test_closed_blocks_each_hold_the_whole_distribution():
    traffic = _traffic("tiny-closed")
    sched = loadgen.make_schedule(traffic, seed=9, seconds=5, vocab_size=256)
    n = traffic["clients"]
    assert len(sched) == n * traffic["blocks"]
    first = sorted(r.max_new for r in sched[:n])
    assert all(sorted(r.max_new for r in sched[i:i + n]) == first
               for i in range(0, len(sched), n))


def test_generator_times_streams_from_the_client_side():
    """A fake server: streams of three tokens, 10 ms apart."""
    import time

    def submit(prompt, max_new):
        def stream():
            for i in range(max_new):
                time.sleep(0.01)
                yield i
        return stream()

    traffic = dict(_traffic("tiny-open"), rate_per_s=50.0, warmup_s=0.1)
    traffic["output_len"] = {"dist": "fixed", "value": 3}
    sched = loadgen.make_schedule(traffic, seed=1, seconds=0.5,
                                  vocab_size=256)
    gen = loadgen.LoadGenerator(submit, sched, traffic)
    gen.start(time.perf_counter(), 0.5)
    assert gen.wait_completed(len(sched), timeout=10)
    gen.stop()
    assert gen.join(timeout=5) == 0
    assert all(r.finish == "complete" and len(r.token_times) == 3
               for r in sched)
    late = [r.sent - (gen.t0 + r.due) for r in sched]
    assert min(late) >= 0 and max(late) < 0.05
    gaps = [b - a for r in sched
            for a, b in zip(r.token_times, r.token_times[1:])]
    assert min(gaps) >= 0.009
