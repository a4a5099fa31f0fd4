"""Mixed precision + panel autotuner acceptance tests.

Three contracts from the precision/tuning PR:

* the default (fp32, untuned-fallback) path matches a plain float32
  ``jax.grad`` sequential-SGD reference, and its row tiling and the
  bk=None fallback are bitwise no-ops, on both backends;
* ``precision="bf16"`` really computes in bf16 (kernel outputs deviate
  from fp32 by a measurable-but-bounded amount), both backends agree,
  and the CommLedger prices the (G, v) wire at 2-byte words while the
  Table 2–3 *word* counts are untouched;
* the tuner cache is deterministic (same profile → same key → cache
  hit; kernel-version bump → miss) and the autotune opt-in (bk=None)
  resolves through it at build time.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.plan import plan
from repro.api.spec import ExperimentSpec, MeshSpec
from repro.core.comm import CommLedger
from repro.core.engine import (
    ParallelSGDSchedule,
    engine_comm_ledger,
    run_parallel_sgd,
)
from repro.core.teams import stack_row_teams
from repro.costmodel.hockney import schedule_comm_volume
from repro.kernels import tune
from repro.kernels.ell_gram import ell_gram_and_v, ell_gram_and_v_blocked
from repro.kernels.sstep_inner import sstep_inner
from repro.sparse.synthetic import make_skewed_csr

from tests.test_distributed_subprocess import run_in_subprocess


def _ref_problem():
    rng = np.random.default_rng(0)
    a = make_skewed_csr(256, 100, 12, 0.8, seed=3)
    y = np.where(rng.random(256) < 0.5, 1.0, -1.0)
    return a, y


def _sched(**kw):
    return ParallelSGDSchedule.hybrid(2, 2, 4, 0.05, 8, rounds=3, loss_every=1, **kw)


# ---- the fp32 default against a plain jax.grad reference ----

# s-step corrections reassociate the sequential SGD sums: over these 3
# rounds of 8 float32 steps (weights of size 1e-2) the gap is 2e-9;
# 1e-7 is float32 rounding headroom at that scale.
GRAD_REF_ATOL = 1e-7


def _grad_sgd_reference(tp, sched):
    """Plain float32 ``jax.numpy`` HybridSGD with the engine's sampling:
    each row team runs τ sequential mini-batch SGD steps on the mean
    logistic loss (step k reads rows (k·b) mod m_local .. +b of its own
    block, differentiated by ``jax.grad``), then the teams' weights are
    averaged; the full-data loss is sampled after every round."""
    rows, n = tp.rows_local, tp.n
    dense = []
    for i in range(tp.p):
        ya = np.zeros((rows, n), np.float32)
        idx, val = np.asarray(tp.indices[i]), np.asarray(tp.values[i])
        np.add.at(ya, (np.arange(rows)[:, None], idx), val)
        dense.append(jnp.asarray(ya))
    valid = np.asarray(tp.rows_valid).reshape(-1)
    ya_all = jnp.concatenate(dense)[valid]

    @jax.jit
    def grad(x, yb):
        return jax.grad(lambda z: jnp.mean(jax.nn.softplus(-(yb @ z))))(x)

    x = jnp.zeros(n, jnp.float32)
    losses = []
    for r in range(sched.rounds):
        team_x = []
        for ya in dense:
            xi = x
            for t in range(sched.tau):
                start = ((r * sched.tau + t) * sched.b) % rows
                xi = xi - sched.eta * grad(xi, ya[start : start + sched.b])
            team_x.append(xi)
        x = jnp.mean(jnp.stack(team_x), axis=0)
        losses.append(float(jnp.mean(jax.nn.softplus(-(ya_all @ x)))))
    return np.asarray(x), np.asarray(losses)


def test_fp32_engine_bitwise_vs_reference():
    """The default fp32 schedule computes HybridSGD: weights AND loss
    trace match the jax.grad sequential-SGD reference."""
    a, y = _ref_problem()
    sched = _sched()
    tp = stack_row_teams(a, y, 2, row_multiple=sched.s * sched.b)
    x, losses = run_parallel_sgd(tp, jnp.zeros(100), sched)
    x_ref, l_ref = _grad_sgd_reference(tp, sched)
    assert np.abs(x_ref).max() > 0.01  # the reference moved
    np.testing.assert_allclose(np.asarray(x), x_ref, rtol=0, atol=GRAD_REF_ATOL)
    np.testing.assert_allclose(np.asarray(losses), l_ref, rtol=0, atol=GRAD_REF_ATOL)


def test_fp32_bm_and_bk_none_bitwise():
    """bm row-tiling and the bk=None engine fallback are bitwise
    no-ops at fp32 (rows are independent; None → static 512)."""
    a, y = _ref_problem()
    base = _sched()
    tp = stack_row_teams(a, y, 2, row_multiple=base.s * base.b)
    ref = np.asarray(run_parallel_sgd(tp, jnp.zeros(100), base)[0])
    for variant in (
        dataclasses.replace(base, bm=4),
        dataclasses.replace(base, bk=None),
        dataclasses.replace(base, bk=None, bm=2),
    ):
        x, _ = run_parallel_sgd(tp, jnp.zeros(100), variant)
        np.testing.assert_array_equal(np.asarray(x), ref)


def test_fp32_shard_map_bitwise_vs_reference():
    """On the 2×2 mesh the fp32 default's row tiling is a bitwise no-op
    too, and the mesh run matches the simulated engine."""
    out = run_in_subprocess(
        """
        import dataclasses
        import numpy as np
        from repro.api import ExperimentSpec, MeshSpec, Session
        from repro.core import ParallelSGDSchedule

        sched = ParallelSGDSchedule.hybrid(2, 2, 4, 0.05, 8, rounds=3, loss_every=1)
        spec = ExperimentSpec(dataset="rcv1-sm", schedule=sched,
                              mesh=MeshSpec(p_r=2, p_c=2, backend="shard_map"))
        x = Session(spec).step_rounds(3).x
        tiled = dataclasses.replace(spec, schedule=dataclasses.replace(sched, bm=4))
        np.testing.assert_array_equal(Session(tiled).step_rounds(3).x, x)
        sim = dataclasses.replace(spec, mesh=MeshSpec(p_r=2, p_c=2))
        np.testing.assert_allclose(Session(sim).step_rounds(3).x, x, rtol=0, atol=1e-6)
        print("OK")
        """
    )
    assert "OK" in out


# ---- bf16 compute is real and bounded ----


def test_bf16_kernel_parity_and_deviation():
    """bf16 panels: pallas and the blocked twin agree to float32
    rounding (XLA may fuse the bf16 dots differently), outputs stay
    float32, and they deviate from fp32 by a small nonzero amount
    (proof the cast is live)."""
    rng = np.random.default_rng(5)
    sb, w, n = 64, 24, 2048
    idx = jnp.asarray(rng.integers(0, n, (sb, w)).astype(np.int32))
    val = jnp.asarray(rng.standard_normal((sb, w)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    g32, v32 = ell_gram_and_v(idx, val, x, n=n, bk=512)
    g16, v16 = ell_gram_and_v(idx, val, x, n=n, bk=512, precision="bf16")
    gb16, vb16 = ell_gram_and_v_blocked(idx, val, x, n=n, bk=512, precision="bf16")
    assert g16.dtype == v16.dtype == jnp.float32  # fp32 accumulate
    np.testing.assert_allclose(np.asarray(g16), np.asarray(gb16), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(v16), np.asarray(vb16), rtol=1e-6, atol=1e-6)
    rel = float(jnp.abs(g16 - g32).max() / jnp.abs(g32).max())
    assert 0.0 < rel < 0.02, rel  # bf16 has ~8 mantissa bits

    u32 = sstep_inner(g32, v32, 4, 16, 0.1)
    u16 = sstep_inner(g32, v32, 4, 16, 0.1, precision="bf16")
    du = float(jnp.abs(u16 - u32).max())
    assert 0.0 < du < 1e-2, du


def test_bf16_engine_close_to_fp32():
    a, y = _ref_problem()
    tp = stack_row_teams(a, y, 2, row_multiple=8)
    x32, l32 = run_parallel_sgd(tp, jnp.zeros(100), _sched())
    x16, l16 = run_parallel_sgd(tp, jnp.zeros(100), _sched(precision="bf16"))
    # documented tolerance: bf16-compute/fp32-accumulate on a 3-round
    # logistic problem stays within 1e-3 of fp32
    assert float(jnp.abs(x16 - x32).max()) < 1e-3
    assert float(jnp.abs(l16 - l32).max()) < 1e-3
    # and is genuinely a different trajectory (the wire cast is live)
    assert not np.array_equal(np.asarray(x16), np.asarray(x32))


def test_bf16_backend_parity_multidevice():
    """shard_map bf16 matches the simulated engine bf16 (the wire cast
    is applied identically around psum and the COUNTING identity), and
    the mesh ledger prices the (G, v) site at 2-byte words."""
    out = run_in_subprocess(
        """
        import dataclasses
        import numpy as np
        from repro.api import ExperimentSpec, MeshSpec, Session
        from repro.core import ParallelSGDSchedule

        sched = ParallelSGDSchedule.hybrid(2, 2, 4, 0.05, 8, rounds=3,
                                           loss_every=1, precision="bf16")
        spec = ExperimentSpec(dataset="rcv1-sm", schedule=sched,
                              mesh=MeshSpec(p_r=2, p_c=2, backend="simulated"))
        r_sim = Session(spec).run()
        r_dist = Session(dataclasses.replace(
            spec, mesh=MeshSpec(p_r=2, p_c=2, backend="shard_map"))).run()
        dx = float(np.abs(r_sim.x - r_dist.x).max())
        dl = float(np.abs(r_sim.losses - r_dist.losses).max())
        assert dx < 1e-5, dx
        assert dl < 1e-5, dl
        assert r_sim.ledger.rates == r_dist.ledger.rates
        gram = [r for r in r_dist.ledger.rates if r.axis == "cols" and r.span > 1]
        assert gram and all(r.word_bytes == 2 for r in gram), gram
        sync = [r for r in r_dist.ledger.rates if r.axis == "rows" and r.span > 1]
        assert sync and all(r.word_bytes == 4 for r in sync), sync
        print("OK", dx, dl)
        """,
        devices=4,
    )
    assert "OK" in out


# ---- ledger bytes: halved payload, invariant word counts ----


def test_ledger_bf16_halves_gram_bytes_not_words():
    n = 4736
    led32 = engine_comm_ledger(_sched(p_c=2), n)
    led16 = engine_comm_ledger(_sched(p_c=2, precision="bf16"), n)
    led32.add_rounds(3)
    led16.add_rounds(3)
    # word counts: identical, and exactly the Table 2–3 closed form
    cv = schedule_comm_volume(n, 2, 2, 2, 4, 8, rounds=3)
    assert led32.counted_words() == led16.counted_words() == cv.words_dict()
    b32, b16 = led32.counted_bytes(), led16.counted_bytes()
    assert b16["gram_bytes"] == b32["gram_bytes"] / 2
    assert b16["sync_bytes"] == b32["sync_bytes"]  # weights stay fp32
    assert led16.bytes_per_round() == led32.bytes_per_round() - (
        led32.counted_bytes(1)["gram_bytes"] / 2
    )
    # the legacy uniform override is untouched (calibration pricing)
    assert led16.bytes_per_round(8) == led32.bytes_per_round(8)


def test_fp32_ledger_serialization_unchanged():
    """fp32 ledgers serialize byte-identically to the pre-precision
    schema: no word_bytes, no counted_bytes."""
    led = engine_comm_ledger(_sched(p_c=2), 100)
    led.add_rounds(3)
    d = led.to_dict()
    assert "counted_bytes" not in json.dumps(d)
    assert "word_bytes" not in json.dumps(d)
    assert CommLedger.from_dict(d).rates == led.rates
    # bf16 ledgers opt the new fields in, and round-trip
    led16 = engine_comm_ledger(_sched(p_c=2, precision="bf16"), 100)
    led16.add_rounds(3)
    d16 = led16.to_dict()
    assert "counted_bytes" in d16 and "word_bytes" in json.dumps(d16)
    assert CommLedger.from_dict(d16).rates == led16.rates


def test_spec_serialization_emits_only_non_default():
    mesh = MeshSpec(p_r=2, p_c=1, backend="simulated")
    spec = ExperimentSpec(dataset="rcv1-sm", schedule=_sched(), mesh=mesh)
    d = spec.to_dict()
    assert "bm" not in d["schedule"] and "precision" not in d["schedule"]
    assert ExperimentSpec.from_dict(d).content_hash() == spec.content_hash()
    spec16 = ExperimentSpec(
        dataset="rcv1-sm", schedule=_sched(precision="bf16", bm=16), mesh=mesh
    )
    d16 = spec16.to_dict()
    assert d16["schedule"]["precision"] == "bf16"
    assert d16["schedule"]["bm"] == 16
    rt = ExperimentSpec.from_dict(d16)
    assert rt.schedule.precision == "bf16" and rt.schedule.bm == 16
    assert rt.content_hash() == spec16.content_hash()
    assert spec16.content_hash() != spec.content_hash()


# ---- tuner cache ----


def _profile(**kw):
    defaults = dict(rows=64, width=74, n_local=2368, dense=False, precision="fp32")
    defaults.update(kw)
    return tune.PanelProfile(**defaults)


def test_cache_key_deterministic_and_content_addressed():
    p = _profile()
    assert tune.cache_key(p, "cpu:cpu") == tune.cache_key(p, "cpu:cpu")
    assert tune.cache_key(p, "cpu:cpu") != tune.cache_key(p, "tpu:TPU v5 lite")
    assert tune.cache_key(p, "cpu:cpu") != tune.cache_key(
        _profile(precision="bf16"), "cpu:cpu"
    )
    assert tune.cache_key(p, "cpu:cpu") != tune.cache_key(
        p, "cpu:cpu", kernel_version=tune.KERNEL_VERSION + 1
    )


def test_resolve_hits_cache_without_retuning(tmp_path):
    """A stored record IS the answer: resolve returns it verbatim (the
    sentinel shape proves no sweep ran) and a kernel-version bump
    misses back to a fresh tune/fallback."""
    p = _profile()
    key = tune.cache_key(p, "cpu:cpu")
    tune.store_record(
        {"key": key, "kernel_version": tune.KERNEL_VERSION, "device": "cpu:cpu",
         "profile": p.to_dict(), "bk": 192, "bm": 8, "measured_s": 1.0,
         "attainable_s": 0.5, "efficiency": 0.5, "candidates": []},
        cache_dir=tmp_path,
    )
    assert tune.resolve_panel(p, device="cpu:cpu", cache_dir=tmp_path) == (192, 8)
    # same profile, bumped kernel version → different key → miss
    stale = tune.cache_key(p, "cpu:cpu", kernel_version=tune.KERNEL_VERSION + 1)
    assert tune.load_record(stale, tmp_path) is None
    # miss without tuning allowed → static fallback
    assert tune.resolve_panel(
        _profile(rows=32), device="cpu:cpu", cache_dir=tmp_path, allow_tune=False
    ) == (tune.FALLBACK_BK, tune.FALLBACK_BM)


def test_tune_writes_once_then_hits(tmp_path):
    p = _profile(rows=16, width=8, n_local=512)
    rec = tune.tune_panel(p, cache_dir=tmp_path, repeats=1, max_n=512)
    files = list(Path(tmp_path).glob("*.json"))
    assert [f.stem for f in files] == [rec["key"]]
    hit = tune.tune_panel(p, cache_dir=tmp_path, repeats=1, max_n=512)
    assert hit == rec  # byte-identical cache read, no re-measure
    assert rec["bk"] >= 1 and rec["measured_s"] > 0
    # off-TPU there are no chip peaks: every candidate is timed and none
    # carries a roofline bound
    assert rec["efficiency"] is None
    live = [c for c in rec["candidates"] if c.get("skipped") is None]
    assert live == rec["candidates"]
    assert all(c["attainable_s"] is None for c in live)


def test_tune_applies_the_roofline_of_the_measured_chip(tmp_path, monkeypatch):
    """On a TPU the candidates are cross-checked against that chip's
    peaks (looked up by device_kind): infeasible VMEM tiles are never
    timed and every timed candidate carries its attainable bound."""
    from repro.launch.roofline import peaks_for

    v5e = peaks_for("TPU v5 lite")
    small_vmem = dataclasses.replace(v5e, vmem_bytes=20 * 1024)
    monkeypatch.setattr(tune, "measured_peaks", lambda: small_vmem)
    monkeypatch.setattr(  # keep every timing above its (tiny) bound
        tune, "_time_candidate", lambda *a, **k: 1.0 + a[4] / 1e6
    )
    p = _profile(rows=16, width=8, n_local=512)
    rec = tune.tune_panel(p, cache_dir=tmp_path, repeats=1, max_n=512)
    skipped = [c for c in rec["candidates"] if c["skipped"] == "vmem"]
    live = [c for c in rec["candidates"] if c["skipped"] is None]
    assert skipped and live
    assert all(c["vmem_bytes"] > small_vmem.vmem_bytes for c in skipped)
    assert all(c["attainable_s"] > 0 for c in live)
    assert rec["efficiency"] == pytest.approx(rec["attainable_s"] / rec["measured_s"])


def test_tune_keeps_honest_compacted_timings(tmp_path, monkeypatch):
    """A bundle that touches fewer columns than the shard walks only the
    compacted panels, so the roofline counts those: a timing just above
    the compacted walk's bound is kept, not flagged as sub-roofline."""
    from repro.kernels.ell_gram import panels_walked
    from repro.launch.roofline import panel_roofline, peaks_for

    v5e = peaks_for("TPU v5 lite")
    rows, width, n = 16, 8, 4096
    monkeypatch.setattr(tune, "measured_peaks", lambda: v5e)

    def honest(idx, val, x, n_, bk, bm, precision, repeats):
        # what the compacted kernel walks: a shard of rows·width columns
        walk = panel_roofline(rows, width, rows * width, bk, bm, precision, peaks=v5e)
        return 1.1 * walk.attainable_s

    monkeypatch.setattr(tune, "_time_candidate", honest)
    p = _profile(rows=rows, width=width, n_local=n)
    rec = tune.tune_panel(p, cache_dir=tmp_path, repeats=1, max_n=n)
    timed = [c for c in rec["candidates"] if c["skipped"] != "vmem"]
    assert timed and all(c["skipped"] is None for c in timed)
    assert all(panels_walked(rows, width, n, c["bk"]) < -(-n // c["bk"]) for c in timed)
    assert rec["efficiency"] == pytest.approx(1 / 1.1)


def test_peaks_are_keyed_by_device_kind():
    from repro.launch.roofline import PEAKS, peaks_for

    v5e = peaks_for("TPU v5 lite")  # what a v5e chip reports
    assert (v5e.bf16_flops, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9, 16 * 2**30)
    assert "TPU v5e" in v5e.source
    with pytest.raises(ValueError, match="no published peaks"):
        peaks_for("TPU v5e")  # a name no chip reports is not a default
    with pytest.raises(ValueError, match="no published peaks"):
        peaks_for("cpu")
    assert set(PEAKS) == {"TPU v5 lite"}


def test_session_resolves_bk_none_and_reports(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
    from repro.api.session import Session

    sched = _sched(bk=None)
    spec = ExperimentSpec(dataset="rcv1-sm", schedule=sched,
                          mesh=MeshSpec(p_r=2, p_c=2, backend="simulated"))
    pl = plan(spec)
    assert "bk=auto (tuned at build)" in pl.summary()  # cold cache
    sess = Session(spec)
    assert sess.spec.schedule.bk is not None  # resolved
    assert sess.input_spec.schedule.bk is None  # checkpoints key pre-resolve
    pl2 = plan(spec)  # warm cache now
    assert pl2.tuned_panel == (sess.spec.schedule.bk, sess.spec.schedule.bm)
    assert f"bk=auto→{sess.spec.schedule.bk}" in pl2.summary()


def test_session_gram_autoselect_rides_autotune_optin(tmp_path, monkeypatch):
    """Heavy-tailed ELL width (w > 4·s·b) flips the tuned build to the
    dense oracle; the default bk=512 build never flips (bitwise pin)."""
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
    from repro.api.session import Session

    # rcv1-sm built at s·b=8 has ELL width ≫ 32 → heavy-tailed
    tuned = ExperimentSpec(dataset="rcv1-sm", schedule=_sched(bk=None),
                           mesh=MeshSpec(p_r=2, p_c=2, backend="simulated"))
    assert Session(tuned).spec.schedule.gram == "dense"
    static = ExperimentSpec(dataset="rcv1-sm", schedule=_sched(),
                            mesh=MeshSpec(p_r=2, p_c=2, backend="simulated"))
    assert Session(static).spec.schedule.gram == "pallas"
    # an explicit gram choice is always honored
    manual = ExperimentSpec(dataset="rcv1-sm", schedule=_sched(bk=None, gram="blocked"),
                            mesh=MeshSpec(p_r=2, p_c=2, backend="simulated"))
    assert Session(manual).spec.schedule.gram == "blocked"


def test_select_gram_path_rule():
    assert tune.select_gram_path(33, 8) == "dense"  # 33 > 4·8
    assert tune.select_gram_path(32, 8) == "pallas"
    assert tune.select_gram_path(104, 64) == "pallas"
    assert tune.select_gram_path(1000, 64, "pallas") == "dense"
    assert tune.select_gram_path(1000, 64, "blocked") == "blocked"  # honored


# ---- plan prices bytes ----


def test_plan_prices_bf16_gram_bytes():
    spec32 = ExperimentSpec(dataset="rcv1-sm", schedule=_sched(p_c=2),
                            mesh=MeshSpec(p_r=2, p_c=2, backend="simulated"))
    spec16 = dataclasses.replace(spec32, schedule=_sched(p_c=2, precision="bf16"))
    from repro.costmodel.machines import MACHINES

    w = MACHINES[spec32.machine].word_bytes
    p32, p16 = plan(spec32), plan(spec16)
    assert p16.cost.gram_bw == pytest.approx(p32.cost.gram_bw * 2 / w)
    assert p16.cost.sync_bw == p32.cost.sync_bw  # weights stay full words
    assert "2-byte Gram wire words" in p16.summary()
