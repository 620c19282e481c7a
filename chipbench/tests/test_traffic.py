"""The traffic generator: deterministic per seed, lengths inside the mix's
clips, the same multiset of sizes for every seed."""
import json
import pathlib

import pytest

from chipbench.lib import traffic

JOBS = sorted((pathlib.Path(__file__).parents[1] / "traffic").glob("*.json"))
MIXES = [p for p in JOBS if json.loads(p.read_text())["kind"] == "serve"]
TRAIN = [p for p in JOBS if json.loads(p.read_text())["kind"] == "train"]


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_mix_is_deterministic_and_clipped(path):
    mix = json.loads(path.read_text())
    a = traffic.generate(mix, 2 ** 33 + 17, 1000)
    b = traffic.generate(mix, 2 ** 33 + 17, 1000)
    c = traffic.generate(mix, 5, 1000)
    assert a == b
    assert a != c
    for r in a:
        assert mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
        assert all(0 <= t < 1000 for t in r.prompt)
    # another seed: the same sizes and gaps, in another order
    assert sorted(len(r.prompt) for r in a) == sorted(
        len(r.prompt) for r in c)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in c)
    sampled = sum(r.temperature > 0 for r in a)
    assert sampled == sum(r.temperature > 0 for r in c)
    block = mix.get("block", len(a))
    assert sampled == len(a) // block * -(-block // mix["sampled"]["every"])
    if mix["loop"] == "poisson":
        # the gaps of whole blocks (the last block's last gap is never used)
        m = len(a) - block + 1 if "block" in mix else len(a)
        ga = sorted(y.due - x.due for x, y in zip(a[:m], a[1:m]))
        gc = sorted(y.due - x.due for x, y in zip(c[:m], c[1:m]))
        assert ga == pytest.approx(gc)
        assert a[m - 1].due == pytest.approx(c[m - 1].due)


def test_each_block_carries_the_same_work():
    """With ``block`` every run of that many requests holds the same sizes,
    sampled share and gaps, each in an order of its own, so the work of a
    window does not change with the seed."""
    mix = {"kind": "serve", "loop": "poisson", "rate_per_s": 2.0,
           "requests": 120, "block": 40,
           "prompt": {"dist": "lognormal", "median": 64, "sigma": 1.0,
                      "min": 8, "max": 512},
           "output": {"dist": "uniform", "min": 8, "max": 64},
           "sampled": {"every": 3, "temperature": 0.8, "top_p": 0.95}}
    a = traffic.generate(mix, 2 ** 33 + 5, 100)
    blocks = [a[k:k + 40] for k in range(0, 120, 40)]
    for blk in blocks[1:]:
        for key in (lambda r: len(r.prompt), lambda r: r.max_new,
                    lambda r: r.temperature):
            assert sorted(map(key, blk)) == sorted(map(key, blocks[0]))
        assert [r.max_new for r in blk] != [r.max_new for r in blocks[0]]
    # each block spans the same time: a block's arrivals are its 40 gaps
    span = [a[k + 40].due - a[k].due for k in (0, 40)]
    assert span[0] == pytest.approx(span[1])
    assert span[0] == pytest.approx(40 / 2.0)
    with pytest.raises(ValueError, match="whole number of blocks"):
        traffic.generate(dict(mix, block=50), 1, 100)


def test_sizes_follow_the_distribution():
    spec = {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 64,
            "max": 1024}
    s = traffic.sizes(spec, 1001)
    assert s.min() >= 64 and s.max() <= 1024
    assert sorted(s)[500] == 256
    u = traffic.sizes({"dist": "uniform", "min": 512, "max": 1024}, 512)
    assert u.min() == 512 and u.max() == 1024
    g = traffic.gaps(3.0, 10000)
    assert g.mean() == pytest.approx(1 / 3.0, rel=1e-2)


@pytest.mark.parametrize("path", TRAIN, ids=lambda p: p.stem)
def test_train_feed_is_deterministic(path):
    """A training job's batches come from the seed alone, in its shape."""
    from repro.data import DataConfig, SyntheticCorpus
    job = json.loads(path.read_text())

    def first(seed, n=2):
        feed = SyntheticCorpus(DataConfig(
            vocab_size=1000, seq_len=job["seq"], global_batch=job["batch"],
            seed=seed)).prefetching(depth=2)
        out = [next(feed) for _ in range(n)]
        feed.close()
        return out

    a, b, c = first(2 ** 33 + 17), first(2 ** 33 + 17), first(5)
    for x, y, z in zip(a, b, c):
        assert x["tokens"].shape == (job["batch"], job["seq"])
        assert (x["tokens"] == y["tokens"]).all()
        assert not (x["tokens"] == z["tokens"]).all()
    assert not (a[0]["tokens"] == a[1]["tokens"]).all()


def test_mid_request_starts_part_way():
    """The first n requests have served the mid-quantile fractions of their
    outputs, in an order drawn from the seed; their served tokens join the
    prompt, their budgets shrink by as many; the rest are untouched."""
    mix = json.loads((JOBS[0].parent / "decode-long.json").read_text())
    base = traffic.generate(mix, 2 ** 33 + 9, 1000)
    a = traffic.mid_request(base, 6, 2 ** 33 + 9, 1000)
    assert a == traffic.mid_request(base, 6, 2 ** 33 + 9, 1000)
    assert a[6:] == base[6:]
    fracs = []
    for r, r0 in zip(a[:6], base[:6]):
        assert r.prompt[:len(r0.prompt)] == r0.prompt
        done = len(r.prompt) - len(r0.prompt)
        assert r.max_new == r0.max_new - done >= 1
        assert len(r.prompt) + r.max_new == len(r0.prompt) + r0.max_new
        fracs.append(done / r0.max_new)
    for f, q in zip(sorted(fracs), (0.5 / 6 + j / 6 for j in range(6))):
        assert q - 1 / 512 < f <= q
    c = traffic.mid_request(base, 6, 5, 1000)
    assert [len(r.prompt) for r in c[:6]] != [len(r.prompt) for r in a[:6]]


def test_bursts_leave_off_spells():
    """With ``bursts`` the same arrivals come in on-spells only, each
    followed by an off-spell; without it nothing changes."""
    mix = {"kind": "serve", "loop": "poisson", "rate_per_s": 4.0,
           "requests": 400,
           "prompt": {"dist": "uniform", "min": 8, "max": 16},
           "output": {"dist": "uniform", "min": 8, "max": 16},
           "sampled": {"every": 3, "temperature": 0.8, "top_p": 0.95}}
    steady = traffic.generate(mix, 11, 100)
    bursty = traffic.generate(dict(mix, bursts={"on_s": 5, "off_s": 10}),
                              11, 100)
    for r, s in zip(bursty, steady):
        spell = s.due // 5
        assert r.due == pytest.approx(s.due + 10 * spell)
        assert (r.due % 15) < 5 + 1e-9
