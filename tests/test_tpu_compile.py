"""Compile-only checks of the main path's kernels for a described TPU v5e.

Nothing here runs: each test compiles with ``interpret=False`` for a
chip that is described, not attached, so what the Mosaic compiler
refuses shows up without a chip. Shapes are the real widths: rcv1
(ELL width 111, n = 47,236) and news20 (width 483, n = 1,355,191) at
bundle size s·b = 64.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels.ell_gram import ell_gram_and_v
from repro.kernels.sstep_inner import sstep_inner

RCV1 = dict(w=111, n=47_236)
NEWS20 = dict(w=483, n=1_355_191)
SB = 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _gram_text(sharding, w, n, precision):
    f = jax.jit(
        lambda i, v, x: ell_gram_and_v(
            i, v, x, n=n, bk=512, precision=precision, interpret=False
        )
    )
    return f.lower(
        _sds((SB, w), jnp.int32, sharding),
        _sds((SB, w), jnp.float32, sharding),
        _sds((n,), jnp.float32, sharding),
    ).compile().as_text()


def test_topology_is_a_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"
    assert len(topo.devices) == 4


@pytest.mark.parametrize("width", [RCV1, NEWS20], ids=["rcv1", "news20"])
def test_ell_gram_fp32_compiles(one_chip, width):
    assert "tpu_custom_call" in _gram_text(one_chip, precision="fp32", **width)


@pytest.mark.parametrize("width,panels", [(RCV1, 14), (NEWS20, 61)], ids=["rcv1", "news20"])
def test_ell_gram_walks_compacted_panels(one_chip, width, panels):
    """At both real widths a bundle touches fewer columns than n, so the
    compiled kernel's grid is ⌈64·w/512⌉ steps (news20: 61, not the
    2,647 of the whole n)."""
    n = width["n"]
    args = (
        _sds((SB, width["w"]), jnp.int32, one_chip),
        _sds((SB, width["w"]), jnp.float32, one_chip),
        _sds((n,), jnp.float32, one_chip),
    )
    f = jax.jit(lambda i, v, x: ell_gram_and_v(i, v, x, n=n, bk=512, interpret=False))
    eqns = jax.make_jaxpr(f)(*args).jaxpr.eqns[0].params["jaxpr"].eqns
    grids = [e.params["grid_mapping"].grid for e in eqns if e.primitive.name == "pallas_call"]
    assert grids == [(panels,)] and panels == -(-SB * width["w"] // 512) < -(-n // 512)
    assert "tpu_custom_call" in f.lower(*args).compile().as_text()


def test_ell_gram_bf16_compiles(one_chip):
    assert "tpu_custom_call" in _gram_text(one_chip, precision="bf16", **RCV1)


def test_sstep_inner_compiles(one_chip):
    f = jax.jit(lambda g, v: sstep_inner(g, v, 8, 8, 0.1, interpret=False))
    text = f.lower(
        _sds((SB, SB), jnp.float32, one_chip), _sds((SB,), jnp.float32, one_chip)
    ).compile().as_text()
    assert "tpu_custom_call" in text


def test_pallas_gram_inside_shard_map_psum(topo):
    """The shard_map backend's bundle step: the kernel per column shard,
    then the (G, v) psum over "cols" — both land in the compiled HLO."""
    from repro.compat import shard_map

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("rows", "cols"))
    n_loc = -(-RCV1["n"] // 2)

    def body(i, v, x):
        g, vv = ell_gram_and_v(i[0, 0], v[0, 0], x, n=n_loc, interpret=False)
        return jax.lax.psum(g, "cols")[None], jax.lax.psum(vv, "cols")[None]

    f = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P("rows", "cols"), P("rows", "cols"), P("cols")),
        out_specs=(P("rows"), P("rows")),
    ))
    data = NamedSharding(mesh, P("rows", "cols"))
    text = f.lower(
        _sds((2, 2, SB, RCV1["w"]), jnp.int32, data),
        _sds((2, 2, SB, RCV1["w"]), jnp.float32, data),
        _sds((2 * n_loc,), jnp.float32, NamedSharding(mesh, P("cols"))),
    ).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text


def test_engine_round_compiles_with_the_kernel(one_chip, monkeypatch):
    """One round of the full-size rcv1 HybridSGD spec (p_r = 2 teams of
    10,176 rows) as the simulated backend compiles it for the chip."""
    import repro.kernels.ell_gram as ell_gram
    from repro.core.engine import ParallelSGDSchedule, lower_engine_chunk
    from repro.core.teams import TeamProblem

    # the kernels ask the process's backend (CPU here) for their mode
    monkeypatch.setattr(ell_gram, "default_interpret", lambda: False)
    sched = ParallelSGDSchedule.hybrid(2, 8, 8, 0.5, 32, rounds=1)
    rows = 10_176
    tp = TeamProblem(
        indices=_sds((2, rows, RCV1["w"]), jnp.int32, one_chip),
        values=_sds((2, rows, RCV1["w"]), jnp.float32, one_chip),
        rows_valid=_sds((2, rows), jnp.bool_, one_chip),
        p=2, m=20_242, n=RCV1["n"],
    )
    x = _sds((RCV1["n"],), jnp.float32, one_chip)
    compiled = lower_engine_chunk(tp, x, 1, sched).compile()
    assert "tpu_custom_call" in compiled.as_text()
    dense = lower_engine_chunk(tp, x, 1, dataclasses.replace(sched, gram="dense"))
    assert "tpu_custom_call" not in dense.compile().as_text()


@pytest.fixture(scope="module")
def rcv1_2x2(topo):
    """The ``rcv1-hybrid-2x2`` layout on the described 2x2 mesh: 2 teams
    of 10,176 rows (10,121 valid, padded to s·b), 2 cyclic column shards
    of 23,618, ELL width 64 a shard."""
    from repro.core.distributed import Hybrid2DProblem

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("rows", "cols"))
    data = NamedSharding(mesh, P("rows", "cols"))
    shape = (2, 2, 10_176, 64)
    prob = Hybrid2DProblem(
        indices=_sds(shape, jnp.int32, data), values=_sds(shape, jnp.float32, data),
        col_sizes=_sds((2,), jnp.int32, NamedSharding(mesh, P())),
        p_r=2, p_c=2, m=20_242, n=RCV1["n"], n_loc=23_618,
    )
    x = _sds((2 * 23_618,), jnp.float32, NamedSharding(mesh, P("cols")))
    return mesh, prob, x


def test_mesh_round_compiles_with_the_kernel(rcv1_2x2, monkeypatch):
    """One full-size round of the shard_map backend: the kernel per
    shard, the (G, v) psum and the row-team pmean."""
    import repro.kernels.ell_gram as ell_gram
    from repro.core.distributed import make_hybrid_step
    from repro.core.engine import ParallelSGDSchedule

    monkeypatch.setattr(ell_gram, "default_interpret", lambda: False)
    mesh, prob, x = rcv1_2x2
    step = make_hybrid_step(mesh, prob, ParallelSGDSchedule.hybrid(2, 8, 8, 0.5, 32, rounds=1))
    text = step.lower(prob.indices, prob.values, x,
                      jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text
    assert text.count("all-reduce(") + text.count("all-reduce-start(") >= 2


def test_mesh_loss_compiles_to_a_replicated_scalar(rcv1_2x2):
    """The mesh loss probe: partial margins summed over "cols", team
    sums over "rows", one f32 scalar out; no gather of x."""
    from repro.core.distributed import make_hybrid_loss

    mesh, prob, x = rcv1_2x2
    compiled = make_hybrid_loss(mesh, prob).lower(prob.indices, prob.values, x).compile()
    text = compiled.as_text()
    assert "all-reduce" in text and "all-gather" not in text
    assert compiled.out_info.shape == () and compiled.out_info.dtype == jnp.float32


def _layout_sds(rows, n, width, lead, sharding):
    from repro.kernels.ell_margins import Geometry, MarginLayout

    geo = Geometry(rows, n, width)
    return MarginLayout(
        meta=_sds((*lead, geo.lane_rows, 128), jnp.int32, sharding),
        values=_sds((*lead, geo.lane_rows, 128), jnp.float32, sharding),
        places=_sds((*lead, geo.lane_rows), jnp.int32, sharding),
        chunks=_sds((*lead, 1), jnp.int32, sharding),
        rows=rows, n=n, width=width,
    )


def test_news20_probe_compiles_with_the_margin_kernel(one_chip, monkeypatch):
    """The news20 loss probe (19,996 rows of width 481 over n =
    1,355,191) with its margins from the kernel: no XLA gather is left."""
    import repro.kernels.ell_margins as ell_margins
    from repro.core.engine import engine_loss
    from repro.core.problem import Problem
    from repro.sparse.ell import EllBlock

    monkeypatch.setattr(ell_margins, "default_interpret", lambda: False)
    rows, n, width = 19_996, 1_355_191, 481
    gp = Problem(
        ya=EllBlock(_sds((rows, width), jnp.int32, one_chip),
                    _sds((rows, width), jnp.float32, one_chip), n),
        rows_valid=_sds((rows,), jnp.bool_, one_chip), m=rows, n=n,
    )
    margins = _layout_sds(rows, n, width, (), one_chip)
    text = engine_loss.lower(gp, _sds((n,), jnp.float32, one_chip), margins).compile().as_text()
    assert "tpu_custom_call" in text and "ell_margins" in text
    assert " gather(" not in text


def test_mesh_loss_compiles_with_the_margin_kernel(rcv1_2x2, monkeypatch):
    """The mesh probe over each 10,176 x 64 block's layout (n_loc =
    23,618), inside shard_map: one f32 scalar out, no gather."""
    import repro.kernels.ell_margins as ell_margins
    from repro.core.distributed import make_hybrid_loss

    monkeypatch.setattr(ell_margins, "default_interpret", lambda: False)
    mesh, prob, x = rcv1_2x2
    margins = _layout_sds(10_176, 23_618, 64, (2, 2), NamedSharding(mesh, P("rows", "cols")))
    compiled = make_hybrid_loss(mesh, prob).lower(prob.indices, prob.values, x, margins).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    assert " gather(" not in text and "all-gather" not in text
    assert compiled.out_info.shape == () and compiled.out_info.dtype == jnp.float32
