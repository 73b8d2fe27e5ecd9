"""The yardstick's arithmetic: traffic, rates, percentiles, work, peaks."""
import numpy as np
import pytest

from bench.lib import data, peaks, rescore, stats, traffic, work


def test_job_seeds_are_fixed_by_the_seed():
    spec = traffic.load("jobs-closed")
    a = traffic.job_seeds(spec, 2**31 + 11)
    b = traffic.job_seeds(spec, 2**31 + 11)
    c = traffic.job_seeds(spec, 2**31 + 12)
    first = [next(a) for _ in range(8)]
    assert first == [next(b) for _ in range(8)]
    assert first != [next(c) for _ in range(8)]
    assert len(set(first)) == 8
    assert all(1 <= s < 2**31 for s in first)


def test_open_requests_send_fixed_arrivals_with_seeded_parameters():
    spec = traffic.load("open-fresh")
    a = traffic.open_requests(spec, 2**31 + 11, 10_000, 51.0)
    b = traffic.open_requests(spec, 2**31 + 11, 10_000, 51.0)
    c = traffic.open_requests(spec, 2**31 + 12, 10_000, 51.0)
    assert a == b
    n = round(spec["rate_per_s"] * 51.0)
    assert len(a) == len(c) == n
    # every seed: the same due times and kinds, in due order in the window
    due = [r["due"] for r in a]
    assert due == [r["due"] for r in c] == sorted(due)
    assert 0.0 <= due[0] and due[-1] < 51.0
    kinds = [(r["k"], "budget" in r, "query_row" in r) for r in a]
    assert kinds == [(r["k"], "budget" in r, "query_row" in r) for r in c]
    assert sum(r["k"] == 50 for r in a) == (n + 1) // 2    # split exactly
    # the seed draws the parameters
    assert [r["seed"] for r in a] != [r["seed"] for r in c]
    assert all(5.0 <= r["budget"] < 10.0 for r in a if "budget" in r)
    assert all(0 <= r["query_row"] < 10_000 for r in a if "query_row" in r)
    assert len(traffic.open_requests(spec, 1, 10_000, 51.0, rate=1.0)) == 51


def test_pool_requests_repeat_the_seeded_pool_by_zipf_rank():
    spec = traffic.load("open-hot")
    pool = traffic.pool_specs(spec, 2**31 + 11, 10_000)
    assert pool == traffic.pool_specs(spec, 2**31 + 11, 10_000)
    assert pool != traffic.pool_specs(spec, 2**31 + 12, 10_000)
    assert len(pool) == spec["pool"]["size"] == 32
    assert sum(p["k"] == 50 and "query_row" in p for p in pool) == 16
    assert sum(p["k"] == 25 and "budget" in p for p in pool) == 16
    a = traffic.open_requests(spec, 2**31 + 11, 10_000, 51.0)
    c = traffic.open_requests(spec, 2**31 + 12, 10_000, 51.0)
    assert a == traffic.open_requests(spec, 2**31 + 11, 10_000, 51.0)
    strip = ("due", "seed")
    assert all({k: v for k, v in r.items() if k not in strip} in pool
               for r in a)
    assert [r["due"] for r in a] == [r["due"] for r in c]
    assert len({r["seed"] for r in a}) == len(a)       # fresh tail seeds
    # Zipf(1): rank 1 is asked for most, about 1 / H_32 of the requests
    first = sum(r == {**pool[0], "due": r["due"], "seed": r["seed"]}
                for r in a)
    assert first == max(sum(r == {**p, "due": r["due"], "seed": r["seed"]}
                            for r in a) for p in pool)
    # a pool handed in (the set-up's) is the one repeated
    b = traffic.open_requests(spec, 5, 10_000, 51.0, pool=pool)
    assert [r["due"] for r in b] == [r["due"] for r in a]
    assert all({k: v for k, v in r.items() if k not in strip} in pool
               for r in b)


def test_exact_counts_split_the_remainder_by_largest_fraction():
    assert list(traffic._exact_counts(np.array([1.0, 1.0]), 163)) == [82, 81]
    assert list(traffic._exact_counts(np.array([0.2, 0.3, 0.5]), 7)) == \
        [1, 2, 4]
    assert traffic._exact_counts(np.array([3.0, 1.0]), 0).sum() == 0


def test_unknown_traffic_kind_is_refused(tmp_path, monkeypatch):
    from bench.lib import manifest
    p = tmp_path / "odd.json"
    p.write_text('{"kind": "guess"}')
    monkeypatch.setattr(manifest, "traffic_path", lambda name: str(p))
    with pytest.raises(ValueError):
        traffic.load("odd")


def test_ground_set_is_fixed_by_the_seed():
    x = data.tiny(3_000, 64, 2**31 + 5)
    assert x.dtype == np.float32 and x.shape == (3_000, 64)
    np.testing.assert_allclose(np.linalg.norm(x, axis=1), 1.0, rtol=1e-5)
    assert np.array_equal(x, data.tiny(3_000, 64, 2**31 + 5))
    assert not np.array_equal(x, data.tiny(3_000, 64, 2**31 + 6))
    e = data.eval_set(x, 100, 7)
    assert e.shape == (100, 64)
    assert len({r.tobytes() for r in e}) == 100


def test_percentile_is_nearest_rank_over_all_samples():
    xs = list(range(1, 201))                  # 200 samples
    assert stats.percentile(xs, 50) == 100
    assert stats.percentile(xs, 95) == 190
    assert stats.percentile(xs, 100) == 200
    # a failed request (inf) ranks above every served one
    assert stats.percentile([float("inf")] + xs[1:], 95) == 191
    assert stats.percentile(xs[:-10] + [float("inf")] * 10, 96) == \
        float("inf")
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_whole_jobs_rate_counts_time_to_the_last_job_end():
    # window opens at 100 s; jobs of 600k rows end at 110, 121 and 133 s
    rate = stats.whole_jobs_rate([600_000] * 3, 100.0, [110.0, 121.0, 133.0])
    assert rate == pytest.approx(1_800_000 / 33.0)
    with pytest.raises(ValueError):
        stats.whole_jobs_rate([], 0.0, [])


def test_round0_work_count():
    flops, nbytes = work.greedy_round0(1, 1000, 512, 3072, 50)
    want = (2 * 1000 * 512 * 3072 + 2 * 1512 * 3072 + 3 * 1000 * 512
            + 50 * 3 * 1000 * 512 + 50 * 3 * 512 * 3072)
    assert flops == want
    assert nbytes == 1000 * 3072 * 4 + 1000 + 50 * 3073 * 4 + 512 * 3072 * 4
    f200, b200 = work.greedy_round0(200, 1000, 512, 3072, 50)
    assert f200 == 200 * flops
    # one 200-machine wave: ~0.64 TFLOP, dominated by the contraction
    assert 0.6e12 < f200 < 0.7e12


def test_roofline_share_names_its_bound():
    pk = peaks.peaks("TPU v5 lite")
    share, bound = work.roofline_share(197e12, 1.0, 2.0, pk["bf16_flop_s"],
                                       pk["hbm_bytes_s"])
    assert share == pytest.approx(50.0) and bound == "compute"
    share, bound = work.roofline_share(1.0, 819e9, 4.0, pk["bf16_flop_s"],
                                       pk["hbm_bytes_s"])
    assert share == pytest.approx(25.0) and bound == "memory"


def test_peaks_name_their_source_and_refuse_unknown_chips():
    pk = peaks.peaks("TPU v5 lite")
    assert pk["bf16_flop_s"] == 197e12 and pk["hbm_bytes_s"] == 819e9
    assert "TPU v5e" in pk["source"] and "cloud.google.com" in pk["source"]
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")


def test_fp64_rescore():
    rng = np.random.default_rng(0)
    E = rng.standard_normal((40, 8))
    S = E[:3].copy()
    v = rescore.exemplar_value(E, np.vstack([S, np.zeros((2, 8))]),
                               [True] * 3 + [False] * 2)
    e0 = (E ** 2).sum(1)
    d2 = ((E[:, None] - S[None]) ** 2).sum(-1)
    want = e0.mean() - np.minimum(e0, d2.min(1)).mean()
    assert v == pytest.approx(want, rel=1e-12)
    assert rescore.exemplar_value(E, S, [False] * 3) == 0.0


def test_fp64_rescore_takes_the_request_weights():
    rng = np.random.default_rng(1)
    E = rng.standard_normal((40, 8))
    S = rng.standard_normal((3, 8))
    w = rng.uniform(0.1, 2.0, 40)
    e0 = (E ** 2).sum(1)
    cur = np.minimum(e0, ((E[:, None] - S[None]) ** 2).sum(-1).min(1))
    assert rescore.exemplar_value(E, S, weights=w) == pytest.approx(
        np.mean(w * e0) - np.mean(w * cur), rel=1e-12)
    assert rescore.exemplar_value(E, S, weights=np.ones(40)) == \
        rescore.exemplar_value(E, S)


def test_answer_gap_reads_the_rows_not_the_reported_value():
    import jax.numpy as jnp

    from bench.lib import checks
    rng = np.random.default_rng(2)
    ground = rng.standard_normal((200, 16)).astype(np.float32)
    ground /= np.linalg.norm(ground, axis=1, keepdims=True)
    E = ground[:40]
    ids = np.array([7, 3, 150, 42])
    ref = {"ids": np.array([42, 7, 150, 3, -1]), "value": 0.5}

    def job(rows_ids):
        rows = np.zeros((5, 16), np.float32)
        rows[:4] = ground[rows_ids]
        return {"mask": np.arange(5) < 4, "rows": rows, "value": 0.5}
    dev = jnp.asarray(ground)
    same = checks.answer_readings(ground, dev, E, 5, job(ids), ref)
    assert same["answer_gap_ref"] == 0.0 and same["picks_off_ref"] == 0
    other = checks.answer_readings(ground, dev, E, 5,
                                   job(np.array([8, 9, 10, 11])), ref)
    assert other["rows_off_set"] == 0 and other["value_gap_ref"] == 0
    assert other["answer_gap_ref"] > 1e-3 and other["picks_off_ref"] == 8


def test_roofline_reads_the_round0_programs_by_name():
    from bench.lib import manifest

    class FakeTrace:
        modules = {0: []}

        def window(self):
            return (0.0, 10.0)

        def module_s(self, t0, t1):
            return {"jit__round_local(11)": 0.10, "jit_concatenate(12)": 5.0}
    r = manifest.metric_reader("round0_solve_roofline.batch")
    c = {"mu": 1000, "n_eval": 512, "d": 3072, "k": 50}
    rec = {"trace": FakeTrace(), "window_pc": (0.0, 10.0), "config": c,
           "device_kind": "TPU v5 lite",
           "jobs": [{"waves": [{"t_end": 2.0, "solve_s": 1.0,
                                "machines": 200}]}]}
    flops, nbytes = work.greedy_round0(200, 1000, 512, 3072, 50)
    want, _ = work.roofline_share(flops, nbytes, 0.10, 197e12, 819e9)
    # the upload join outlasts the solve and is not charged with its work
    assert r.read(rec) == pytest.approx(want)
    FakeTrace.module_s = lambda self, t0, t1: {"jit_concatenate(12)": 5.0}
    assert r.read(rec) is None


def test_reference_leaves_a_near_tie_open():
    """Two equal rows on one machine: greedy's first pick is a tie, and the
    answer that takes the other row is one TREE may give."""
    import jax.numpy as jnp

    from bench.lib import tree_ref
    rng = np.random.default_rng(3)
    ground = rng.standard_normal((60, 16)).astype(np.float32)
    ground /= np.linalg.norm(ground, axis=1, keepdims=True)
    E = ground[:20]
    a = int(tree_ref.tree(jnp.asarray(ground), E, 11, k=4, mu=64)["ids"][0])
    b = next(i for i in range(59, 0, -1) if i != a)
    ground[b] = ground[a]
    ref = tree_ref.tree(jnp.asarray(ground), E, 11, k=4, mu=64)
    own = set(ref["ids"].tolist())
    assert len(own & {a, b}) == 1
    other = own ^ {a, b}
    answers = [set(x["ids"].tolist()) for x in ref["answers"]]
    assert answers[0] == own and other in answers[1:]


def test_answer_readings_take_a_near_tie_answer_only_when_it_is_exact():
    import jax.numpy as jnp

    from bench.lib import checks
    rng = np.random.default_rng(4)
    ground = rng.standard_normal((100, 16)).astype(np.float32)
    ground /= np.linalg.norm(ground, axis=1, keepdims=True)
    E = ground[:30]
    dev = jnp.asarray(ground)

    def ref():
        own = {"ids": np.array([3, 8, 21]), "value": 0.4}
        return {**own, "answers": iter([own, {"ids": np.array([3, 8, 22]),
                                              "value": 0.41}])}

    def job(ids):
        return {"mask": np.ones(3, bool), "rows": ground[ids], "value": 0.41}
    tie = checks.answer_readings(ground, dev, E, 3, job([22, 3, 8]), ref())
    assert tie["ref_answer"] == 1 and tie["picks_off_ref"] == 0
    assert tie["answer_gap_ref"] == 0.0 and tie["value_gap_ref"] == 0.0
    wrong = checks.answer_readings(ground, dev, E, 3, job([3, 8, 23]), ref())
    assert wrong["ref_answer"] == 0 and wrong["picks_off_ref"] == 2
    assert wrong["answer_gap_ref"] > 0
