"""Implementation-neutral work counts (``bench.work``) and the row
order the reference follows (``bench.data.RowSchedule``)."""

import dataclasses
import itertools
import json
from pathlib import Path

import numpy as np
import pytest

from bench import work
from bench.data import Data, RowSchedule, make_data

ROOT = Path(__file__).resolve().parents[2]


def _tiny(seed=0, m=12, n=10, zbar=4):
    return make_data({"m": m, "n": n, "zbar": zbar, "skew_alpha": 0.8}, seed)


def _dense(d: Data, rows) -> np.ndarray:
    y = np.zeros((len(rows), d.n))
    for k, r in enumerate(rows):
        if r >= 0:
            lo, hi = d.indptr[r], d.indptr[r + 1]
            y[k, d.indices[lo:hi]] = d.ya[lo:hi]
    return y


def test_gram_and_v_counts_match_a_brute_force_count():
    d = _tiny()
    rows = np.array([0, 3, 5, -1, 7, 8, 2, 11])
    y = _dense(d, rows) != 0
    pairs = sum(int(np.sum(y[i] & y[j])) for i, j in itertools.combinations(range(len(rows)), 2))
    (call,) = work.bundle_calls(d, rows, sb=len(rows))
    assert call.gram_flops == 2 * pairs
    assert call.v_flops == 2 * int(y.sum()) == 2 * call.nnz
    sb = len(rows)
    assert call.least_bytes == 12 * call.nnz + (sb * (sb - 1) // 2 + sb) * 4


def test_column_shards_split_the_same_work():
    d = _tiny(seed=3, m=40, n=30, zbar=6)
    rows = np.arange(16)
    (whole,) = work.bundle_calls(d, rows, sb=16)
    shards = work.bundle_calls(d, rows, sb=16, p_c=2)
    assert sum(c.gram_flops for c in shards) == whole.gram_flops
    assert sum(c.nnz for c in shards) == whole.nnz


def test_counts_do_not_depend_on_the_gram_path():
    d = _tiny(seed=5, m=256, n=64, zbar=5)
    base = {"p_r": 2, "s": 4, "b": 4, "tau": 8, "eta": 0.5, "bk": 512}
    counts = {}
    for gram in ("pallas", "dense", "blocked"):
        sched = RowSchedule.of(d.m, {**base, "gram": gram})
        counts[gram] = ([work.round_calls(d, sched, r, p_c=2) for r in range(5)],
                        [work.round_flops(d, sched, r) for r in range(5)])
    assert counts["pallas"] == counts["dense"] == counts["blocked"]


def test_row_schedule_matches_the_program_row_teams():
    from repro.core.teams import stack_row_teams
    from repro.sparse.synthetic import dataset_stats, make_dataset

    seed = 2**31 + 3
    ds = make_dataset("rcv1-sm", seed)
    d = make_data(dataclasses.asdict(dataset_stats("rcv1-sm")), seed)
    sched = RowSchedule.of(d.m, {"p_r": 2, "s": 8, "b": 8, "tau": 32})
    tp = stack_row_teams(ds.A, ds.y, 2, row_multiple=64)
    assert tp.rows_local == sched.rows_local
    idx = np.asarray(tp.indices)
    for r, team, t in [(0, 0, 0), (0, 1, 3), (7, 1, 2), (40, 0, 1)]:
        rows = sched.bundle(r, team, t)
        start = ((r * sched.bundles + t) * sched.sb) % sched.rows_local
        for k, row in enumerate(rows):
            got = idx[team, start + k]
            if row < 0:
                assert not got.any()
            else:
                want = d.indices[d.indptr[row]:d.indptr[row + 1]]
                assert np.array_equal(got[:len(want)], want)


def test_bench_data_equals_the_program_generator():
    from repro.sparse.synthetic import dataset_stats, make_dataset

    seed = 2**31 + 11
    ds = make_dataset("news20-sm", seed)
    d = make_data(dataclasses.asdict(dataset_stats("news20-sm")), seed)
    row_ids = np.repeat(np.arange(ds.A.m), np.diff(ds.A.indptr))
    assert np.array_equal(d.indptr, ds.A.indptr)
    assert np.array_equal(d.indices, ds.A.indices)
    assert np.array_equal(d.ya, ds.A.data * ds.y[row_ids])


@pytest.mark.parametrize("config", ["news20-sstep", "rcv1-sstep", "rcv1-hybrid-2x2"])
def test_a_config_data_seed_makes_the_ell_width_it_states(config):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json").read_text())
    d = make_data(cfg["data"], cfg["data_seeds"][0])
    assert int(np.diff(d.indptr).max()) == cfg["ell_width"]["rows"]
    p_c = cfg["spec"]["mesh"]["p_c"]
    if p_c > 1:
        rows = d.row_ids()
        widest = max(int(np.bincount(rows[d.indices % p_c == j], minlength=d.m).max())
                     for j in range(p_c))
        assert widest == cfg["ell_width"]["shards"]
