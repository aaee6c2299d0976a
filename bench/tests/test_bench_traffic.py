"""The traffic generator: the same seed gives the same requests, and every
seed offers the same set of gaps and projections in another order."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import traffic as tr  # noqa: E402

MIX = {"kind": "open", "rows": 1, "rate_rps": 3.5,
       "projections": ["q", "k", "v", "o", "gate", "up", "down"]}


LAYERS = 40


def _key(schedule):
    return [(r.due_s, r.projection, r.operand, r.layer) for r in schedule]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_same_seed_same_schedule(seed):
    assert _key(tr.open_schedule(MIX, seed, 30, LAYERS)) == _key(
        tr.open_schedule(MIX, seed, 30, LAYERS))


def test_seeds_reorder_one_set_of_gaps_and_projections():
    a = tr.open_schedule(MIX, 1, 30, LAYERS)
    b = tr.open_schedule(MIX, 2**33 + 5, 30, LAYERS)
    assert _key(a) != _key(b)
    assert len(a) == len(b) == round(3.5 * 30)

    def gaps(s):
        return sorted(np.diff([r.due_s for r in s]))

    # every seed draws its n gaps from one set of n (it leaves one out as
    # the first arrival's), so the spans agree to within one gap
    assert np.isclose(np.median(gaps(a)), np.median(gaps(b)), rtol=0.1)
    assert sorted(r.projection for r in a) == sorted(r.projection for r in b)


def test_open_schedule_is_poisson_at_the_rate():
    s = tr.open_schedule({**MIX, "rate_rps": 50.0}, 3, 60, LAYERS)
    gaps = np.diff([r.due_s for r in s])
    assert gaps.mean() == pytest.approx(1 / 50, rel=0.05)
    assert np.std(gaps) == pytest.approx(1 / 50, rel=0.1)   # exponential
    assert s[0].due_s == 0.0 and all(np.diff([r.due_s for r in s]) >= 0)


def test_operands_count_per_projection():
    s = tr.open_schedule(MIX, 4, 30, 3)
    pools = tr.pool_sizes(MIX, s)
    for name in MIX["projections"]:
        ops = sorted(r.operand for r in s if r.projection == name)
        assert ops == list(range(pools[name]))
        assert all(r.layer == r.operand % 3 for r in s)


def test_closed_loop_alternates_and_cycles_the_pool():
    mix = {"kind": "closed", "projections": ["up", "down"], "operand_pool": 3}
    reqs = [tr.closed_request(mix, i, 4) for i in range(10)]
    assert [r.projection for r in reqs] == ["up", "down"] * 5
    assert [r.operand for r in reqs] == [0, 0, 1, 1, 2, 2, 0, 0, 1, 1]
    assert [r.layer for r in reqs] == [0, 0, 1, 1, 2, 2, 3, 3, 0, 0]
    assert tr.pool_sizes(mix, []) == {"up": 3, "down": 3}


@pytest.mark.parametrize("change", [{"clients": 4}, {"arrivals": "bursty"},
                                    {"kind": "replay"}, {"rate_rps": None}])
def test_a_key_the_generator_does_not_read_is_an_error(change):
    mix = {k: v for k, v in {**MIX, **change}.items() if v is not None}
    with pytest.raises(ValueError):
        tr.validate(mix)
    assert tr.validate(MIX) is MIX


def test_rate_must_be_positive():
    with pytest.raises(ValueError):
        tr.open_schedule({**MIX, "rate_rps": 0}, 1, 10, LAYERS)


def test_reservoir_keeps_a_seeded_sample_and_lets_the_rest_go():
    from bench.harness.runner import Reservoir

    def sample(seed):
        keep = Reservoir(3, seed)
        reqs = [tr.Request(index=i, projection="up", operand=0)
                for i in range(50)]
        for r in reqs:
            keep(r, f"y{r.index}")
        held = [r.index for r in reqs if r.result is not None]
        assert sorted(held) == sorted(r.index for r in keep.kept)
        return held

    assert len(sample(5)) == 3
    assert sample(5) == sample(5)
    assert any(sample(s) != sample(5) for s in (6, 7, 8))


def test_end_to_end_metrics_from_requests():
    from bench.harness.runner import end_to_end

    reqs = [tr.Request(index=i, projection="q", operand=0, due_s=float(i),
                       sent_s=i + 0.1, done_s=i + 0.1 + 0.01 * (i + 1))
            for i in range(10)]
    reqs.append(tr.Request(index=10, projection="q", operand=0, due_s=10.0,
                           sent_s=10.0, failure="refused"))
    assert end_to_end("setup_s", reqs, 12.5) == 12.5
    assert end_to_end("products_per_s", reqs, 0) == pytest.approx(10 / 9.1)
    assert end_to_end("products_per_s.sharded4", reqs, 0) == pytest.approx(10 / 9.1)
    lat = [1e3 * (0.1 + 0.01 * (i + 1)) for i in range(10)]
    for q in (50, 90):
        assert end_to_end(f"latency_p{q}_ms", reqs, 0) == pytest.approx(
            np.percentile(lat, q))
    with pytest.raises(ValueError):
        end_to_end("tokens_per_s", reqs, 0)


ROUTED = {"kind": "routed", "tokens": 2048,
          "projections": ["expert_gate", "expert_up", "expert_down"],
          "operand_pool": 4, "check_sample": 3}
#: the expert counts a routed mix takes from its configuration
MOE = {"model": {"n_routed_experts": 8, "num_experts_per_tok": 6},
       "published": {"n_routed_experts": 64}}


def _routing(seed, n=6):
    routings = tr.chunk_routings(ROUTED, MOE)
    return [tr.routed_request(ROUTED, i, 26, routings, seed) for i in range(n)]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_same_seed_same_routing(seed):
    a, b = _routing(seed), _routing(seed)
    for x, y in zip(a, b, strict=True):
        assert (x.projection, x.operand, x.layer) == (y.projection, y.operand,
                                                      y.layer)
        assert all(np.array_equal(p, q)
                   for p, q in zip(x.tokens, y.tokens, strict=True))


def test_every_seed_routes_the_same_held_loads():
    """Each chunk's loads are fixed by the mix and the configuration; the
    seed relabels the tokens, so the rows differ and the sizes do not."""
    want = [[len(rows) for rows in chunk]
            for chunk in tr.chunk_routings(ROUTED, MOE)]
    assert len({tuple(w) for w in want}) == len(want)   # chunks differ
    rows_of = {}
    for seed in (1, 2, 2**33 + 5, 2**45 + 9):
        for r in _routing(seed, n=12):
            assert [len(t) for t in r.tokens] == want[r.operand]
            for rows in r.tokens:     # distinct tokens of the chunk, in order
                assert np.all(np.diff(rows) > 0)
                assert 0 <= rows[0] and rows[-1] < ROUTED["tokens"]
            rows_of.setdefault(r.operand, set()).add(
                tuple(r.tokens[0].tolist()))
    assert all(len(seen) == 4 for seen in rows_of.values())


def test_routed_requests_alternate_and_cycle_like_the_closed_loop():
    reqs = _routing(3, n=9)
    assert [r.projection for r in reqs] == ROUTED["projections"] * 3
    assert [r.operand for r in reqs] == [0] * 3 + [1] * 3 + [2] * 3
    # the three projections of one chunk route the same rows
    for i in range(0, 9, 3):
        assert all(np.array_equal(p, q) for p, q in zip(
            reqs[i].tokens, reqs[i + 2].tokens, strict=True))
    assert tr.pool_sizes(ROUTED, []) == {p: 4 for p in ROUTED["projections"]}


def test_balanced_routing_gives_each_token_top_k_experts_and_the_chips_partition_them():
    picks = tr.balanced_routing(2048, 64, 6, np.random.default_rng(5))
    assert picks.shape == (2048, 6)
    assert all(len(set(row)) == 6 for row in picks.tolist())
    loads = np.bincount(picks.ravel(), minlength=64)
    assert loads.sum() == 2048 * 6
    assert abs(loads.mean() - 192) < 1e-9 and loads.max() < 256
    held = np.concatenate([tr.held_experts(64, 8, o) for o in range(8)])
    assert sorted(held) == list(range(64))
    assert tr.held_experts(64, 8).tolist() == [0, 8, 16, 24, 32, 40, 48, 56]
    with pytest.raises(ValueError):
        tr.held_experts(64, 7)
    # the chip's rows are the tokens that picked its experts
    chunk = tr.chunk_routings(ROUTED, MOE)[0]
    picks = tr.balanced_routing(2048, 64, 6, np.random.default_rng(
        [2048, 64, 6, 0]))
    for e, rows in zip(tr.held_experts(64, 8), chunk, strict=True):
        assert np.array_equal(rows, np.flatnonzero((picks == e).any(axis=1)))


def test_the_block_sides_follow_the_loads():
    """Every held expert's product tiles to m = 256 blocks, and the pad
    share differs from chunk to chunk."""
    from repro.mpc.tiling import choose_block, padded_volume

    shares = set()
    for chunk in tr.chunk_routings(ROUTED, MOE):
        work = volume = 0
        for rows in chunk:
            for k, f in ((2048, 1408), (1408, 2048)):
                m = choose_block(2, 2, rows.size, k, f)
                assert m == 256
                work += rows.size * k * f
                volume += padded_volume(m, rows.size, k, f)
        shares.add(round(1 - work / volume, 6))
    assert len(shares) == 4 and all(0.25 < s < 0.4 for s in shares)


@pytest.mark.parametrize("change", [{"rows": 2048}, {"rate_rps": 1.0},
                                    {"experts": 8}, {"load_skew": 0.25},
                                    {"tokens": None}])
def test_a_key_the_routed_generator_does_not_read_is_an_error(change):
    mix = {k: v for k, v in {**ROUTED, **change}.items() if v is not None}
    with pytest.raises(ValueError):
        tr.validate(mix)
    assert tr.validate(ROUTED) is ROUTED
