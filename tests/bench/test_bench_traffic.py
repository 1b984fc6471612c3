"""The traffic generators: bitwise deterministic per seed, the same work for
every seed, and a new mix (with a new arrival law) is files only."""
import json

import numpy as np

import tiny
from tiny import harness

import trafficlib

BURSTS = '''"""On-off bursts: ``size`` requests every ``every_s`` seconds."""
import numpy as np

import trafficlib


def serve_requests(mix, seed, seconds, vocab):
    arr = mix["arrivals"]
    due = np.repeat(np.arange(0.0, seconds, arr["every_s"]), arr["size"])
    return trafficlib.open_loop(mix, seed, due, vocab)
'''


def _serve(seed, seconds=50.0, rate=None):
    mix = harness.traffic("chat-steady")
    if rate is not None:
        mix["arrivals"]["rate_per_s"] = rate
    return harness.generator(mix).serve_requests(mix, seed, seconds, 151936)


def _train_batch(*args):
    mix = {"generator": "uniform_rows", "seq_len": 64}
    return harness.generator(mix).train_batch(mix, *args)


def test_serve_requests_are_bitwise_deterministic_per_seed():
    for seed in (0, 7, 2**31 + 11, 2**40 + 3):
        (w1, r1), (w2, r2) = _serve(seed), _serve(seed)
        for a, b in zip(w1 + r1, w2 + r2):
            assert a.due_s == b.due_s and a.max_new == b.max_new
            assert a.prompt.dtype == np.int32
            assert np.array_equal(a.prompt, b.prompt)


def test_every_seed_gets_the_same_sizes_and_gaps_in_another_order():
    (wa, ra), (wb, rb) = _serve(3), _serve(4)
    for key in (lambda r: len(r.prompt), lambda r: r.max_new):
        assert sorted(map(key, ra)) == sorted(map(key, rb))
        assert sorted(map(key, wa)) == sorted(map(key, wb))
        assert list(map(key, ra)) != list(map(key, rb))
    gaps_a = np.diff([0.0] + [r.due_s for r in ra])
    gaps_b = np.diff([0.0] + [r.due_s for r in rb])
    np.testing.assert_allclose(np.sort(gaps_a), np.sort(gaps_b), rtol=1e-9)
    assert not np.array_equal(ra[0].prompt[:16], rb[0].prompt[:16])


def test_blocked_runs_have_even_sums_and_the_same_members_for_every_seed():
    n, block = 96, 8
    values = np.exp(np.linspace(-3, 3, n))
    runs = {}
    for seed in (1, 2**33 + 5):
        order = trafficlib.blocked(trafficlib.rng(seed), values, block)
        np.testing.assert_array_equal(np.sort(order), values)
        sums = order.reshape(-1, block).sum(axis=1)
        assert np.ptp(sums) < 0.1 * sums.mean()
        runs[seed] = sorted(tuple(sorted(r)) for r in order.reshape(-1, block))
    assert runs[1] == runs[2**33 + 5]
    odd = trafficlib.blocked(trafficlib.rng(3), np.arange(101.0), block)
    assert sorted(odd) == list(range(101))


def test_blocked_arrivals_load_every_stretch_of_the_window_alike():
    """The request that closes each run of ``order_block`` is due at about
    the same time for every seed (within a fraction of a mean gap): the
    seed reorders the work within stretches, not across them."""
    for rate in (1.6, 2.0):
        block = harness.traffic("chat-steady")["order_block"]
        ends = [np.array([r.due_s for r in _serve(seed, rate=rate)[1]])
                [block - 1::block] for seed in range(6)]
        assert np.ptp(np.array(ends), axis=0).max() * rate < 0.5


def test_serve_sizes_follow_the_mix():
    mix = harness.traffic("chat-steady")
    warm, reqs = _serve(5)
    assert len(reqs) == round(mix["arrivals"]["rate_per_s"] * 50)
    assert len(warm) == mix["warm"]["requests"]
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    lens = np.array([len(r.prompt) for r in reqs])
    outs = np.array([r.max_new for r in reqs])
    assert lens.min() >= p["min"] and lens.max() <= p["max"]
    assert outs.min() >= o["min"] and outs.max() <= o["max"]
    assert abs(np.median(lens) - p["median"]) < 0.1 * p["median"]
    assert abs(np.median(outs) - o["median"]) < 0.1 * o["median"]
    # an in-flight request is caught part way: warm outputs are cut
    assert all(1 <= r.max_new <= o["max"] for r in warm)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and due[-1] < 50 * 1.1


def test_train_batches_are_deterministic_and_rows_differ():
    a = _train_batch(2**31 + 1, 0, 4, 1000)
    assert a.shape == (4, 65) and a.dtype == np.int32
    assert np.array_equal(a, _train_batch(2**31 + 1, 0, 4, 1000))
    b = _train_batch(2**31 + 1, 1, 4, 1000)
    rows = {r.tobytes() for r in np.concatenate([a, b])}
    assert len(rows) == 8


def test_a_new_mix_is_a_data_file_found_by_name(tmp_path, monkeypatch):
    """A later PR adds a cell's traffic as files only: a mix that names a
    new arrival law (on-off bursts) and the generator module beside it.
    The serving driver runs it from a copy of ``bench/`` that differs from
    the committed one by those two added files."""
    bench = tmp_path / "bench"
    bench.mkdir()
    for entry in harness.BENCH.iterdir():
        if entry.name != "traffic":
            (bench / entry.name).symlink_to(entry)
    (bench / "traffic").mkdir()
    for entry in (harness.BENCH / "traffic").iterdir():
        (bench / "traffic" / entry.name).symlink_to(entry)
    mix = tiny.serve_mix()
    mix.update(generator="bursts", arrivals={"every_s": 0.5, "size": 3})
    mix["warm"]["requests"] = 1
    (bench / "traffic" / "dummy-burst.json").write_text(json.dumps(mix))
    (bench / "traffic" / "bursts.py").write_text(BURSTS)
    monkeypatch.setattr(harness, "BENCH", bench)
    found = harness.traffic("dummy-burst")
    assert found["arrivals"] == {"every_s": 0.5, "size": 3}
    _, reqs = harness.generator(found).serve_requests(found, 9, 1.5, 512)
    assert [r.due_s for r in reqs] == [0.0] * 3 + [0.5] * 3 + [1.0] * 3
    driver = harness.plugin("drivers", "serve")
    out = driver.run(tiny.ctx(tiny.serve_cfg(), found, seconds=1.5))
    assert out["attempted"] == 9 and out["failed"] == 0
