"""``repro.obs`` spans and named device scopes in a ``jax.profiler``
trace, on the CPU.

* ``span`` writes a ``repro.<category>`` host event, with its name and
  args as stats, into any running profile — and, with a recorder
  installed, a ``Span`` into the recorder too;
* a profiled ``Session.step_rounds`` nests ``repro.step`` ⊃
  ``repro.round`` / ``repro.probe`` / ``repro.copy_x``, with iterates
  and losses bitwise those of an unprofiled run, on both backends;
* the round programs and the loss program name their device work with
  the six scopes, on both backends.
"""

import glob
import json
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.api import ExperimentSpec, MeshSpec, Session
from repro.core.engine import ParallelSGDSchedule, engine_loss, lower_engine_chunk
from repro.obs import trace as obs_trace

REPO = Path(__file__).resolve().parent.parent
ROUND_SCOPES = ("bundle_gram", "allreduce_gv", "corrections", "update", "param_avg")


def host_events(trace_dir) -> list[tuple[str, int, int, dict]]:
    """(name, start, end, stats) of every ``repro.*`` host event."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns), dict(e.stats))
                        for e in line.events if e.name.startswith("repro.")]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def spec(backend="simulated", p_c=2, precision="fp32"):
    return ExperimentSpec(
        dataset="rcv1-sm",
        schedule=ParallelSGDSchedule.hybrid(
            p_r=2, s=2, b=4, eta=0.2, tau=8, rounds=4, loss_every=2, precision=precision
        ),
        mesh=MeshSpec(p_r=2, p_c=p_c, backend=backend),
    )


def scopes_in(hlo_text: str) -> set[str]:
    """The round/probe scope names among the op_name paths of an HLO text."""
    names = set()
    for path in re.findall(r'op_name="([^"]*)"', hlo_text):
        names |= set(path.split("/")) & {*ROUND_SCOPES, "loss_probe"}
    return names


@pytest.mark.parametrize("recorder", [False, True], ids=["profile_only", "profile_and_recorder"])
def test_span_writes_a_profile_event(tmp_path, recorder):
    jax.profiler.start_trace(str(tmp_path))
    try:
        if recorder:
            with obs_trace.install() as rec:
                with obs_trace.span("ckpt_save", name="ck-3", rounds_done=3):
                    pass
        else:
            with obs_trace.span("ckpt_save", name="ck-3", rounds_done=3):
                pass
            assert obs_trace.active() is None
    finally:
        jax.profiler.stop_trace()
    (ev,) = host_events(tmp_path)
    assert ev[0] == "repro.ckpt_save" and ev[2] >= ev[1]
    assert ev[3] == {"name": "ck-3", "rounds_done": 3}
    if recorder:
        (s,) = rec.spans
        assert (s.category, s.name, s.args) == ("ckpt_save", "ck-3", {"rounds_done": 3})


def _simulated_profiled(tmp_path) -> dict:
    a = Session(spec())
    while not a.done:
        a.step_rounds()
    b = Session(spec())
    jax.profiler.start_trace(str(tmp_path))
    try:
        while not b.done:
            b.step_rounds()
    finally:
        jax.profiler.stop_trace()
    return {
        "bitwise": bool(np.array_equal(a.current_x(), b.current_x())
                        and np.array_equal(np.asarray(a.losses), np.asarray(b.losses))),
        "losses": len(b.losses),
        "events": host_events(tmp_path),
    }


MESH_BODY = """
import glob, json, os, re, sys, tempfile
import jax, numpy as np
sys.path.insert(0, {tests!r})
from test_obs_profile import host_events, scopes_in, spec
from repro.api import Session

a = Session(spec("shard_map"))
while not a.done:
    a.step_rounds()
b = Session(spec("shard_map"))
with tempfile.TemporaryDirectory() as td:
    jax.profiler.start_trace(td)
    try:
        while not b.done:
            b.step_rounds()
    finally:
        jax.profiler.stop_trace()
    events = host_events(td)
d = b._driver
step = d.lower_step().as_text(dialect="hlo", debug_info=True)
loss = d._loss.lower(d._idx, d._val, d._x_pad).as_text(dialect="hlo", debug_info=True)
print("MESH_RESULT", json.dumps({{
    "bitwise": bool(np.array_equal(a.current_x(), b.current_x())
                    and np.array_equal(np.asarray(a.losses), np.asarray(b.losses))),
    "losses": len(b.losses),
    "events": events,
    "round_scopes": sorted(scopes_in(step)),
    "loss_scopes": sorted(scopes_in(loss)),
}}))
"""


@pytest.fixture(scope="module")
def mesh_result():
    """The shard_map half, in one subprocess on 4 virtual CPU devices."""
    env = {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": str(REPO / "src"),
        "PATH": "/usr/bin:/bin:/usr/local/bin",
        "JAX_PLATFORMS": "cpu",
    }
    code = textwrap.dedent(MESH_BODY.format(tests=str(REPO / "tests")))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          env=env, timeout=600)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    line = next(ln for ln in proc.stdout.splitlines() if ln.startswith("MESH_RESULT "))
    return json.loads(line.split(" ", 1)[1])


@pytest.mark.parametrize("backend", ["simulated", "shard_map"])
def test_profiled_session_nests_spans_and_keeps_iterates(tmp_path, backend, request):
    if backend == "shard_map":
        res = request.getfixturevalue("mesh_result")
    else:
        res = _simulated_profiled(tmp_path)
    assert res["bitwise"], "profiling changed the iterates or losses"
    events = res["events"]
    steps = [e for e in events if e[0] == "repro.step"]
    assert [e[3]["step_num"] for e in steps] == [0, 2]
    inner = [e for e in events if e[0] != "repro.step"]
    for name, start, end, stats in inner:
        assert any(s[1] <= start and end <= s[2] for s in steps), name
    names = [e[0] for e in inner]
    assert names == ["repro.compile", "repro.probe", "repro.copy_x",
                     "repro.round", "repro.probe", "repro.copy_x"]
    assert names.count("repro.probe") == res["losses"] == 2
    first = inner[0][3]
    assert first["start_round"] == 0 and first["rounds"] == 2
    assert all(e[3]["bytes"] > 0 for e in inner if e[0] == "repro.copy_x")


@pytest.mark.parametrize("backend", ["simulated", "shard_map"])
def test_round_and_loss_programs_carry_the_scopes(backend, request):
    if backend == "shard_map":
        res = request.getfixturevalue("mesh_result")
        round_scopes, loss_scopes = set(res["round_scopes"]), set(res["loss_scopes"])
    else:
        # fp32 on one device: the (G, v) Allreduce is the identity and
        # leaves no op; bf16 casts to the wire dtype inside that scope
        sess = Session(spec(precision="bf16"))
        round_scopes = scopes_in(lower_engine_chunk(
            sess.bundle.team, sess._x, 1, sess.spec.schedule
        ).as_text(dialect="hlo", debug_info=True))
        loss_scopes = scopes_in(engine_loss.lower(sess._gp, sess._x).as_text(
            dialect="hlo", debug_info=True))
    assert round_scopes == set(ROUND_SCOPES)
    assert loss_scopes == {"loss_probe"}
