"""The loss probe's margin kernel (``repro.kernels.ell_margins``) in the
Pallas interpreter, against ``ell_matvec``.

Tolerance: the kernel sums each row's products in another order than
``ell_matvec`` (a segmented scan per lane-row, then one add per
lane-row into the row), so a margin may move by a few float32 roundings
of the row's terms. Each check bounds the gap by ``RTOL`` times the sum
of the row's |a_ij x_j|: a few hundred roundings of float32 (6e-8)
fit, and one term lost, doubled or taken from the wrong column does
not (every term is 1e-3 of that sum or more at these widths).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExperimentSpec, MeshSpec, Session
from repro.core import ParallelSGDSchedule
from repro.core.engine import engine_loss
from repro.core.objective import get_objective
from repro.core.problem import make_problem
from repro.kernels.ell_margins import Geometry, build_margin_layout, ell_margins, margin_layout
from repro.obs import trace as obs_trace
from repro.sparse.ell import EllBlock, ell_matvec
from repro.sparse.synthetic import make_skewed_csr

RTOL = 1e-5

# (rows, n, width, zbar): news20-like (n ≫ rows·width/128: 16-row windows),
# an rcv1 shard (8-row windows), n inside one window, and rows whose
# runs fill and overflow whole lane-rows
SHAPES = {
    "news20-like": (300, 200_003, 80, 20),
    "rcv1-shard": (1_000, 2_357, 36, 9),
    "narrow": (700, 100, 20, 5),
    "long-runs": (130, 300, 300, 200),
}


def _ell(rows, n, width, zbar, seed=0):
    """An ELL block from the skewed generator with pad entries, rows
    with no entries, and signed values."""
    a = make_skewed_csr(rows, n, zbar, 0.8, seed=seed)
    y = np.where(np.random.default_rng(seed).random(rows) < 0.5, 1.0, -1.0)
    prob = make_problem(a, y, ell_width=width)
    idx, val = np.array(prob.ya.indices), np.array(prob.ya.values)
    val[::7] = 0.0  # rows with no entries
    idx[::7] = 0
    return idx, val


def _x(n, seed=1):
    return (0.3 * np.random.default_rng(seed).standard_normal(n)).astype(np.float32)


def _check(got, idx, val, x):
    terms = np.asarray(val, np.float64) * x.astype(np.float64)[idx]
    gap = np.abs(np.asarray(got, np.float64) - terms.sum(axis=1))
    assert (gap <= RTOL * np.abs(terms).sum(axis=1) + 1e-30).all(), gap.max()


def _margins(layout, x):
    return np.asarray(ell_margins(jax.tree.map(jnp.asarray, layout), jnp.asarray(x),
                                  interpret=True))


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_kernel_matches_ell_matvec(shape):
    idx, val = _ell(*shape)
    n = shape[1]
    x = _x(n)
    got = _margins(build_margin_layout(idx, val, n), x)
    _check(got, idx, val, x)
    ref = np.asarray(ell_matvec(EllBlock(jnp.asarray(idx), jnp.asarray(val), n), jnp.asarray(x)))
    _check(ref, idx, val, x)
    assert (got[::7] == 0).all()  # the empty rows


def test_window_follows_the_shapes():
    assert Geometry(*SHAPES["news20-like"][:3]).window_rows == 16
    assert Geometry(*SHAPES["rcv1-shard"][:3]).window_rows == 8
    # news20 and the rcv1 2x2 block at full size
    assert Geometry(20_032, 1_355_191, 481).window_rows == 16
    assert Geometry(10_176, 23_618, 64).window_rows == 8


def test_batch_of_blocks_builds_each_block_alone():
    """A (2, 2) batch, as the mesh builds it: each block's slice of the
    batch layout is that block's own layout."""
    rows, n, width, zbar = SHAPES["rcv1-shard"]
    blocks = [_ell(rows, n, width, zbar, seed=s) for s in range(4)]
    idx = np.stack([b[0] for b in blocks]).reshape(2, 2, rows, width)
    val = np.stack([b[1] for b in blocks]).reshape(2, 2, rows, width)
    batch = build_margin_layout(idx, val, n)
    assert batch.meta.shape[:2] == (2, 2) and batch.chunks.shape == (2, 2, 1)
    x = _x(n)
    for i in range(2):
        for j in range(2):
            own = build_margin_layout(idx[i, j], val[i, j], n)
            mine = jax.tree.map(lambda a: a[i, j], batch)
            for a, b in zip(jax.tree.leaves(own), jax.tree.leaves(mine)):
                np.testing.assert_array_equal(a, b)
            _check(_margins(mine, x), idx[i, j], val[i, j], x)


def _shift_places(lay):
    return dataclasses.replace(lay, places=np.roll(lay.places, 1, axis=-1))


def _shift_columns(lay):
    pad = (lay.meta >> 12 & 255) == 255
    return dataclasses.replace(lay, meta=np.where(pad, lay.meta, lay.meta ^ 1))


def _shift_rows(lay):
    meta = lay.meta + np.where((lay.meta >> 12 & 255) < 127, 1 << 12, 0).astype(np.int32)
    return dataclasses.replace(lay, meta=meta)


def _half_the_chunks(lay):
    return dataclasses.replace(lay, chunks=lay.chunks // 2)


@pytest.mark.parametrize("broken", [_shift_places, _shift_columns, _shift_rows,
                                    _half_the_chunks])
def test_a_broken_layout_fails_the_check(broken):
    """The check above tells a layout whose offsets are off by one."""
    rows, n, width, zbar = SHAPES["news20-like"]
    idx, val = _ell(rows, n, width, zbar)
    x = _x(n)
    got = _margins(broken(build_margin_layout(idx, val, n)), x)
    with pytest.raises(AssertionError):
        _check(got, idx, val, x)


OBJECTIVES = [("logistic", 0.0), ("logistic", 0.01), ("squared_hinge", 0.01),
              ("least_squares", 0.0)]


@pytest.mark.parametrize("objective", OBJECTIVES, ids=lambda o: f"{o[0]}-l2={o[1]}")
def test_engine_loss_with_the_kernel(objective):
    """``engine_loss`` with a layout against its gather path, rows
    padded past m as the engine pads them."""
    rows, n, width, zbar = SHAPES["news20-like"]
    a = make_skewed_csr(rows, n, zbar, 0.8, seed=4)
    y = np.where(np.random.default_rng(4).random(rows) < 0.5, 1.0, -1.0)
    gp = make_problem(a, y, row_multiple=64, objective=get_objective(*objective))
    lay = jax.tree.map(jnp.asarray, build_margin_layout(gp.ya.indices, gp.ya.values, n))
    x = jnp.asarray(_x(n))
    got = float(engine_loss(gp, x, lay))
    assert got == pytest.approx(float(engine_loss(gp, x)), rel=RTOL)


def _problem(seed, n, width=40):
    a = make_skewed_csr(400, n, 8, 0.8, seed=seed)
    y = np.where(np.random.default_rng(seed).random(400) < 0.5, 1.0, -1.0)
    return make_problem(a, y, row_multiple=64, ell_width=width)


def test_layout_and_probe_program_do_not_depend_on_the_data():
    """Two data seeds of one ELL width: the layouts' arrays have the same
    shapes and dtypes, and the probe lowers to the same program, so every
    seed of a cell shares one compiled loss. At 4x the columns (and the
    same window) the program has as many lines: nothing in it is
    unrolled over n."""
    n = 100_000
    gps = [_problem(seed, n) for seed in (11, 12)]
    lays = [build_margin_layout(gp.ya.indices, gp.ya.values, n) for gp in gps]
    assert not np.array_equal(lays[0].meta, lays[1].meta)
    for a, b in zip(jax.tree.leaves(lays[0]), jax.tree.leaves(lays[1])):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    x = jnp.zeros(n, jnp.float32)
    texts = [engine_loss.lower(gp, x, lay).as_text() for gp, lay in zip(gps, lays)]
    assert texts[0] == texts[1]

    wide = _problem(11, 4 * n)
    lay = build_margin_layout(wide.ya.indices, wide.ya.values, 4 * n)
    assert lay.meta.shape != lays[0].meta.shape
    assert lay.geometry.window_rows == lays[0].geometry.window_rows
    text = engine_loss.lower(wide, jnp.zeros(4 * n, jnp.float32), lay).as_text()
    assert len(text.splitlines()) == len(texts[0].splitlines())


def test_off_a_tpu_the_probe_keeps_the_gather():
    """No layout off a TPU: the Session probes through ``ell_matvec``,
    bitwise as before, and its probe span says so."""
    rows, n, width, zbar = SHAPES["rcv1-shard"]
    idx, val = _ell(rows, n, width, zbar)
    assert margin_layout(idx, val, n) is None
    spec = ExperimentSpec(
        dataset="rcv1-sm", name="margins",
        schedule=ParallelSGDSchedule.hybrid(1, 2, 4, 0.05, 8, rounds=2, loss_every=1),
        mesh=MeshSpec(p_r=1, p_c=1, backend="simulated"),
    )
    sess = Session(spec)
    assert sess._margins is None
    with obs_trace.install() as rec:
        sess.step_rounds(1)
    (probe,) = rec.by_category()["probe"]
    assert probe.args == {"path": "device", "margins": "gather"}
