"""The traffic generator: seeded, deterministic, inside the mixes' ranges,
with the same work for every seed; the prefix mix shares its prefixes and
the decode-heavy mix shares nothing."""
import numpy as np
import pytest

from bench import traffic

VOCAB = 151936
SEEDS = (0, 7, 2**31 + 11, 2**40 + 3)


@pytest.mark.parametrize("mix", ["prefix-sessions", "decode-heavy"])
def test_bursts_are_deterministic_per_seed(mix):
    p = traffic.load(mix)
    a = traffic.ServeMix(p, 2**31 + 5, VOCAB).burst(3)
    b = traffic.ServeMix(p, 2**31 + 5, VOCAB).burst(3)
    c = traffic.ServeMix(p, 2**31 + 6, VOCAB).burst(3)
    assert [(r.prompt.tolist(), r.max_new, r.session) for r in a] == \
        [(r.prompt.tolist(), r.max_new, r.session) for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]


@pytest.mark.parametrize("mix", ["prefix-sessions", "decode-heavy"])
def test_lengths_stay_in_range_and_work_is_the_same_for_every_seed(mix):
    p = traffic.load(mix)
    pad = p["server"]["prompt_pad"]
    shapes = set()
    for seed in SEEDS:
        for k in range(3):
            reqs = traffic.ServeMix(p, seed, VOCAB).burst(k)
            assert len(reqs) == p["burst"]
            for r in reqs:
                suffix = len(r.prompt) - p["prefix_len"]
                assert p["suffix_len"][0] <= suffix <= p["suffix_len"][1]
                assert p["output_len"][0] <= r.max_new <= p["output_len"][1]
                assert len(r.prompt) <= pad
                assert len(r.prompt) + r.max_new <= p["server"]["max_len"]
                assert r.prompt.dtype == np.int32
                assert 0 <= r.prompt.min() and r.prompt.max() < VOCAB
            shapes.add((tuple(sorted(len(r.prompt) for r in reqs)),
                        tuple(sorted(r.max_new for r in reqs)),
                        tuple(sorted(reqs_per_session(reqs).values()))))
    assert len(shapes) == 1, "the seed changed the amount of work"


def reqs_per_session(reqs):
    out = {}
    for r in reqs:
        out[r.session] = out.get(r.session, 0) + 1
    return out


def test_prefix_mix_shares_its_prefixes():
    p = traffic.load("prefix-sessions")
    gen = traffic.ServeMix(p, 3, VOCAB)
    reqs = gen.burst(0) + gen.burst(1)
    by_session = {}
    for r in reqs:
        by_session.setdefault(r.session, []).append(r)
    assert p["prefix_len"] == 256
    for sess, rs in by_session.items():
        first = rs[0].prompt[:256]
        assert all(np.array_equal(r.prompt[:256], first) for r in rs)
    prefixes = {tuple(rs[0].prompt[:256]) for rs in by_session.values()}
    assert len(prefixes) == len(by_session)
    counts = sorted(reqs_per_session(gen.burst(0)).values(), reverse=True)
    assert counts[0] >= 4 * counts[-1], "sessions are not Zipf-skewed"
    assert len(by_session) > 16


def test_decode_heavy_mix_shares_nothing():
    p = traffic.load("decode-heavy")
    gen = traffic.ServeMix(p, 3, VOCAB)
    reqs = gen.burst(0) + gen.burst(1)
    assert len({r.session for r in reqs}) == len(reqs)
    assert p["prefix_len"] == 0
    # no two prompts share their first page of 4 tokens
    assert len({tuple(r.prompt[:4]) for r in reqs}) == len(reqs)


def test_warmup_bursts_do_not_repeat_the_window():
    p = traffic.load("prefix-sessions")
    gen = traffic.ServeMix(p, 1, VOCAB)
    wu = gen.burst(0, n=2, max_new=2, warmup=True)
    win = gen.burst(0)
    assert all(r.max_new <= 2 for r in wu)
    suffixes = {tuple(r.prompt[256:]) for r in win}
    assert not any(tuple(r.prompt[256:]) in suffixes for r in wu)


def test_seed_keys_keep_every_bit():
    import jax
    a = jax.random.key_data(traffic.jax_key(2**33, 0))
    b = jax.random.key_data(traffic.jax_key(2**33 + 2**32, 0))
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_sort_inputs_are_seeded_and_born_sharded():
    import jax
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    p = traffic.load("uniform")
    devs = jax.devices()[:4]
    mesh = jax.make_mesh((4,), ("data",), devices=devs,
                         axis_types=(AxisType.Auto,))
    sh = NamedSharding(mesh, P("data"))
    a = traffic.sort_inputs(p, 2**31 + 1, 1 << 12, sh)
    b = traffic.sort_inputs(p, 2**31 + 1, 1 << 12, sh)
    assert len(a) == p["inputs"]
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))
        assert {s.device for s in x.addressable_shards} == set(devs)
        assert x.dtype == np.int32
    assert not np.array_equal(np.asarray(a[0]), np.asarray(a[1]))
