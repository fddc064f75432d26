"""The program's own spans and scopes: the host spans the train loop and
the trainer write, the named scopes in the compiled programs, and the
readers that reduce them (``bench/program_trace.py``).  Every test that
starts the profiler is in this file."""
import dataclasses
import glob
import os
import re
import shutil
import tempfile
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, program_trace, trace
from bench.metrics import (adaln_ms, attention_ms, loop_host_ms, mlp_ms,
                           optimizer_ms, rewards_ms, rollout_ms, update_ms)
from repro import configs, registry
from repro.api import loop as loop_lib
from repro.config import (DistConfig, FlowRLConfig, OptimConfig,
                          PerfConfig, RewardSpec)
from repro.core.preprocess import ConditionProvider
from repro.core.rollout import group_repeat
from repro.data.prompts import PromptDataset, synthetic_prompts

DATA = os.path.join(os.path.dirname(__file__), "data")
SCOPED = os.path.join(DATA, "v5e_awm_scoped.xplane.pb")
SCOPE_READERS = (attention_ms, mlp_ms, adaln_ms, optimizer_ms)
READERS = (*SCOPE_READERS, loop_host_ms)

# the span that directly holds each of the program's spans
PARENT = {"repro.step": None,
          "repro.loop.conditions": "repro.step",
          "repro.loop.dispatch": "repro.step",
          "repro.trainer.sample": "repro.loop.dispatch",
          "repro.trainer.rewards": "repro.loop.dispatch",
          "repro.trainer.update": "repro.loop.dispatch",
          "repro.loop.prefetch": "repro.step",
          "repro.loop.drain": "repro.step",
          "repro.loop.fetch": "repro.loop.drain",
          "repro.loop.callbacks": "repro.loop.drain",
          "repro.checkpoint.save": "repro.loop.callbacks"}

# ------------------------------------------------------------ tiny loop
TINY_ENCODER = dict(cond_dim=32, cond_len=4, vocab=256, hidden=64)
TINY_FLOW = FlowRLConfig(
    num_steps=2, group_size=2, latent_tokens=4, latent_dim=4,
    rewards=(RewardSpec("text_render", 1.0,
                        args={"latent_dim": 4, "latent_tokens": 4,
                              "cond_dim": 32}),))
STEPS = 3


def _trainer():
    """AWM on the reduced flux_dit, with the cell's remat and
    microbatching."""
    return registry.build(
        "trainer", "awm", configs.get_reduced("flux_dit"), TINY_FLOW,
        OptimConfig(lr=1e-3, total_steps=64, warmup_steps=2),
        key=jax.random.PRNGKey(0), cond_dim=32,
        perf=PerfConfig(remat="scan"), dist=DistConfig(microbatch=2))


def _run(ckpt_dir, profile_dir=None):
    tr = _trainer()
    loop = loop_lib.TrainLoop(
        tr, ConditionProvider(preprocessing=False, encoder_kw=TINY_ENCODER),
        PromptDataset(synthetic_prompts(16), batch_size=2, seed=0),
        steps=STEPS, key=jax.random.PRNGKey(7),
        callbacks=[loop_lib.PeriodicCheckpoint(ckpt_dir, every=2)])
    if profile_dir is None:
        return tr, loop.run()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(profile_dir, profiler_options=opts)
    try:
        history = loop.run()
    finally:
        jax.profiler.stop_trace()
    return tr, history


def _bits(tree):
    out = []
    for x in jax.tree.leaves(tree):
        arr = np.asarray(jax.device_get(x))
        out.append(arr.view(np.uint16) if arr.dtype == jnp.bfloat16
                   else arr)
    return out


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The same three steps with the profiler off and on."""
    tmp = tmp_path_factory.mktemp("spans")
    off = _run(str(tmp / "ckpt_off"))
    on = _run(str(tmp / "ckpt_on"), str(tmp / "trace"))
    path = glob.glob(str(tmp / "trace" / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    return {"off": off, "on": on, "path": path}


def _parent(span, spans):
    holders = [s for s in spans if s is not span and s.start <= span.start
               and span.end <= s.end]
    return min(holders, key=lambda s: s.dur).name if holders else None


def test_program_spans_nest(runs):
    spans = program_trace.load(runs["path"]).program_spans
    names = [s.name for s in spans]
    assert set(names) == set(PARENT)
    assert names.count("repro.step") == STEPS
    for name in ("repro.loop.fetch", "repro.trainer.update",
                 "repro.loop.callbacks"):
        assert names.count(name) == STEPS
    assert names.count("repro.checkpoint.save") == 1
    for s in spans:
        assert _parent(s, spans) == PARENT[s.name], s
    from jax.profiler import ProfileData
    step_nums = sorted(
        int(dict(e.stats)["step_num"])
        for p in ProfileData.from_file(runs["path"]).planes
        for line in p.lines for e in line.events if e.name == "repro.step")
    assert step_nums == list(range(STEPS))


def test_profiler_changes_no_result(runs):
    (tr_off, h_off), (tr_on, h_on) = runs["off"], runs["on"]
    wall = ("dt", "steps_per_s")
    assert [{k: v for k, v in r.items() if k not in wall} for r in h_off] \
        == [{k: v for k, v in r.items() if k not in wall} for r in h_on]
    for a, b in zip(_bits(tr_off.state), _bits(tr_on.state)):
        np.testing.assert_array_equal(a, b)


def _op_names(compiled):
    return re.findall(r'op_name="([^"]*)"', compiled.as_text())


def test_scopes_in_compiled_programs(runs):
    tr = runs["on"][0]
    cond = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 32))
    cond_g = group_repeat(cond, TINY_FLOW.group_size)
    key = jax.random.PRNGKey(2)
    mask = jnp.ones((TINY_FLOW.num_steps,), bool)
    sample = _op_names(tr._sample_jit.lower(tr.state.params, cond_g, key,
                                            mask).compile())
    traj = tr._sample_jit(tr.state.params, cond_g, key, mask)
    adv = jnp.zeros((cond_g.shape[0],))
    update = _op_names(tr._update_jit.lower(tr.state, traj, adv, key,
                                            ()).compile())

    def has(paths, seg, *also):
        return any(seg in p.split("/") and all(a in p for a in also)
                   for p in paths)

    for seg in ("dit_attention", "dit_mlp", "dit_adaln"):
        assert has(sample, seg) and has(update, seg)
        # the backward, rematerialized in the microbatch scan, keeps them
        assert has(update, seg, "transpose(jvp")
    assert has(update, "optim_adamw") and not has(sample, "optim_adamw")


# ------------------------------------------------------ synthetic events
def ev(name, start, end, scope=None):
    return NS(name=name, start_ns=start, end_ns=end,
              stats=[("tf_op", scope)] if scope else [])


SAMPLE = "jit(_sample)/while/body/closed_call/"
UPDATE = "jit(_update)/while/body/closed_call/transpose(jvp())/"


def synthetic(scopes=True, program_spans=True):
    """Two steps on one TPU.  Programs: sample [100, 400], rewards
    [420, 440], update [450, 900], sample [1100, 1400].  The instruction
    ``fusion.1`` is in both ``sample`` and ``update``."""
    s = (lambda path: path) if scopes else (lambda path: None)
    ops = [ev("%fusion.1 = f32[4] fusion()", 100, 200,
              s(SAMPLE + "dit_adaln/mul")),
           ev("%fusion.2 = f32[4] fusion()", 200, 300,
              s(SAMPLE + "dit_attention/dot_general")),
           ev("%fusion.3 = f32[4] fusion()", 300, 400,
              s(SAMPLE + "dit_mlp/dot_general")),
           ev("%reduce.7 = f32[] reduce()", 420, 440,
              s("jit(_rewards)/dit_mlp_like/reduce_sum")),
           ev("%while.9 = (s32[]) while()", 450, 900,
              s("jit(_update)/while")),
           ev("%fusion.1 = f32[4] fusion()", 450, 600,
              s(UPDATE + "dit_attention/dot_general")),
           ev("%fusion.4 = f32[4] fusion()", 600, 800,
              s(UPDATE + "dit_mlp/dot_general")),
           ev("%fusion.5 = f32[4] fusion()", 800, 900,
              s("jit(_update)/optim_adamw/mul")),
           ev("%fusion.1 = f32[4] fusion()", 1100, 1200,
              s(SAMPLE + "dit_adaln/mul")),
           ev("%fusion.2 = f32[4] fusion()", 1200, 1400,
              s(SAMPLE + "dit_attention/dot_general"))]
    modules = [ev("jit__sample(1)", 100, 400),
               ev("jit__rewards(2)", 420, 440),
               ev("jit__update(3)", 450, 900),
               ev("jit__sample(1)", 1100, 1400)]
    bench = [ev("bench.dispatch", 50, 90), ev("bench.drain", 95, 1005),
             ev("bench.conditions", 1015, 1055),
             ev("bench.dispatch", 1060, 1090), ev("bench.drain", 1095, 1450)]
    program = [ev("repro.step", 40, 1010),
               ev("repro.loop.conditions", 40, 50),
               ev("repro.loop.dispatch", 50, 90),
               ev("repro.trainer.sample", 55, 70),
               ev("repro.trainer.rewards", 70, 80),
               ev("repro.trainer.update", 80, 88),
               ev("repro.loop.prefetch", 90, 95),
               ev("repro.loop.drain", 96, 1004),
               ev("repro.loop.fetch", 97, 905),
               ev("repro.loop.callbacks", 950, 1003),
               ev("repro.step", 1010, 1452),
               ev("repro.loop.conditions", 1010, 1060),
               ev("repro.loop.dispatch", 1060, 1090),
               ev("repro.loop.prefetch", 1090, 1095),
               ev("repro.loop.drain", 1096, 1450),
               ev("repro.loop.fetch", 1097, 1405),
               ev("repro.loop.callbacks", 1420, 1449)]
    host = bench + (program if program_spans else []) + [
        ev("other", 0, 2000)]
    return [NS(name="/device:TPU:0",
               lines=[NS(name="XLA Ops", events=ops),
                      NS(name="XLA Modules", events=modules)]),
            NS(name="/host:CPU", lines=[NS(name="python", events=host)])]


def ctx_for(tr, steps=2):
    lo, hi = trace.window(tr)
    return harness.Context(trace=tr, lo=lo, hi=hi, steps=steps, config={},
                           traffic={}, peaks=None, shapes={})


def test_synthetic_program_trace():
    tr = program_trace.from_planes(synthetic())
    assert trace.window(tr) == (50, 1450)
    # the benchmark's own reduction is unchanged by the program's spans
    assert [s.name for s in tr.spans] == [s.name for s in trace.from_planes(
        synthetic(program_spans=False)).spans]
    assert [e.module for e in tr.ops["/device:TPU:0"]] == [
        "jit__sample"] * 3 + ["jit__rewards"] + ["jit__update"] * 4 + [
        "jit__sample"] * 2
    lo, hi = 50, 1450
    assert program_trace.scope_ns(tr, "dit_attention", lo, hi) == 450
    assert program_trace.scope_ns(tr, "dit_mlp", lo, hi) == 300
    assert program_trace.scope_ns(tr, "dit_adaln", lo, hi) == 200
    assert program_trace.scope_ns(tr, "optim_adamw", lo, hi) == 100
    # a scope is a whole segment: dit_mlp_like is not dit_mlp
    assert program_trace.scope_ns(tr, "dit_mlp", 410, 450) == 0
    assert program_trace.scope_ns(tr, "dit_attention", 0, 250) == 50
    # steps [50, 1450] less the fetches (808 + 308)
    assert program_trace.host_ns(tr, lo, hi) == 1400 - 808 - 308


def test_synthetic_readers():
    c = ctx_for(program_trace.from_planes(synthetic()))
    assert attention_ms.read(c) == pytest.approx(225e-6)
    assert mlp_ms.read(c) == pytest.approx(150e-6)
    assert adaln_ms.read(c) == pytest.approx(100e-6)
    assert optimizer_ms.read(c) == pytest.approx(50e-6)
    assert loop_host_ms.read(c) == pytest.approx(142e-6)
    # the programs' readers read what they read from the plain reduction
    plain = ctx_for(trace.from_planes(synthetic()))
    for mod in (rollout_ms, update_ms, rewards_ms):
        assert mod.read(c) == mod.read(plain)


@pytest.mark.parametrize("planes,silent", [
    (synthetic(scopes=False), set(SCOPE_READERS)),
    (synthetic(program_spans=False), {loop_host_ms}),
    ([p for p in synthetic() if p.name.startswith("/host")], set(READERS)),
], ids=["no_scopes", "no_program_spans", "no_device"])
def test_readers_are_silent_without_their_spans(planes, silent):
    c = ctx_for(program_trace.from_planes(planes))
    for mod in READERS:
        assert (mod.read(c) is None) == (mod in silent), mod.__name__


def test_breakdown_names_programs_scopes_and_program_spans():
    tr = program_trace.from_planes(synthetic())
    top = program_trace.top_ops(tr, 50, 1450, top=10)
    assert top[:3] == [("jit__sample/fusion.2 [dit_attention]", 300e-9),
                       ("jit__sample/fusion.1 [dit_adaln]", 200e-9),
                       ("jit__update/fusion.4 [dit_mlp]", 200e-9)]
    # fusion.1 of update is a row of its own, not added to sample's
    assert ("jit__update/fusion.1 [dit_attention]", 150e-9) in top
    assert ("jit__rewards/reduce.7 [-]", 20e-9) in top
    assert not any("while" in name for name, _ in top)
    gaps = program_trace.idle_gaps(tr, 50, 1450, top=3)
    assert gaps == [("repro.loop.callbacks", 200e-9),
                    ("repro.trainer.rewards", 50e-9),
                    ("repro.loop.callbacks", 50e-9)]
    # the benchmark's own breakdown still names bench.* spans alone
    assert trace.idle_gaps(tr, 50, 1450, top=1) == [("bench.drain", 200e-9)]


def test_breakdown_tool_splits_each_program():
    from bench.tools import breakdown
    out = breakdown.split(program_trace.from_planes(synthetic()), 50, 1450,
                          steps=2)
    ms = lambda ns: pytest.approx(ns / 2e6)
    assert out["programs"]["jit__sample"] == {
        "total": ms(600), "dit_attention": ms(300), "dit_mlp": ms(100),
        "dit_adaln": ms(200), "rest": ms(0), "rest_parts": []}
    upd = out["programs"]["jit__update"]
    assert upd["total"] == ms(450) and upd["optim_adamw"] == ms(100)
    assert out["programs"]["jit__rewards"]["rest_parts"] == [
        ["dit_mlp_like/reduce_sum", ms(20)]]
    assert out["idle"] == ms(330) and out["loop_host"] == ms(284)
    assert out["idle_gaps_ms"][0] == ["repro.loop.callbacks",
                                      pytest.approx(200e-6)]


# ------------------------------------------------------------ xspace
@pytest.mark.parametrize("name", ["v5e_grpo_tiny", "synthetic"])
def test_xspace_reads_what_profile_data_reads(name, tmp_path):
    from jax.profiler import ProfileData
    from bench import xspace
    from bench.tools.record_scoped_trace import serialize
    path = os.path.join(DATA, f"{name}.xplane.pb")
    if name == "synthetic":
        path = str(tmp_path / "t.xplane.pb")
        open(path, "wb").write(serialize(synthetic()))
    ours = xspace.planes(path, lambda plane: True)
    theirs = ProfileData.from_file(path).planes
    flat = lambda planes: [
        (p.name, line.name, e.name, e.start_ns, e.end_ns,
         [(k, v) for k, v in e.stats])
        for p in planes for line in p.lines for e in line.events]
    assert flat(ours) == flat(theirs) and len(flat(ours)) > 30


def test_xspace_adds_the_metadata_stats():
    """An instruction's scope stored once, on its event metadata, as a
    trace stores what every run of the instruction shares."""
    from jax.profiler import ProfileData
    from bench import xspace
    text = """planes { id: 1 name: "/device:TPU:0"
      lines { id: 1 name: "XLA Ops" timestamp_ns: 100
        events { metadata_id: 1 offset_ps: 1000 duration_ps: 2500
                 stats { metadata_id: 9 int64_value: 5 } }
        events { metadata_id: 1 offset_ps: 9000 duration_ps: 500 } }
      event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[4]"
        stats { metadata_id: 7 ref_value: 8 } } }
      stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
      stat_metadata { key: 8 value { id: 8 name: "jit(f)/dit_mlp/dot" } }
      stat_metadata { key: 9 value { id: 9 name: "run_id" } } }"""
    with tempfile.NamedTemporaryFile(suffix=".xplane.pb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
        f.flush()
        (plane,) = xspace.planes(f.name, lambda name: True)
    first, second = plane.lines[0].events
    # whole nanoseconds, as ProfileData gives them
    assert (first.start_ns, first.end_ns) == (101.0, 103.0)
    assert first.stats == [("run_id", 5), ("tf_op", "jit(f)/dit_mlp/dot")]
    assert second.stats == [("tf_op", "jit(f)/dit_mlp/dot")]


# ------------------------------------------------------- recorded on v5e
def test_recorded_v5e_scoped_trace():
    """The AWM cell cut to tiny sizes, one whole step of its window,
    recorded on a TPU v5e (bench/tools/record_scoped_trace.py)."""
    tr = program_trace.load(SCOPED)
    assert tr.devices == ["/device:TPU:0"]
    c = ctx_for(tr, steps=1)
    programs = sum(m.read(c) for m in (rollout_ms, update_ms))
    scoped = {m: m.read(c) for m in SCOPE_READERS}
    assert all(v > 0 for v in scoped.values()), scoped
    assert sum(scoped.values()) <= programs
    host = loop_host_ms.read(c)
    assert 0 < host < (c.hi - c.lo) / 1e6
    top = program_trace.top_ops(tr, c.lo, c.hi)
    assert all(name.startswith("jit_") for name, _ in top)
    assert any(name.endswith(("[dit_attention]", "[dit_mlp]"))
               for name, _ in top)
    assert all(label.startswith(("repro.", "bench."))
               for label, _ in program_trace.idle_gaps(tr, c.lo, c.hi))


def test_readers_find_the_harness_trace_by_its_window(tmp_path,
                                                      monkeypatch):
    """The harness gives its readers ``trace.load``'s reduction; the
    program's spans and scopes are read again from the file it profiled
    into, known by its window."""
    from bench.tools.record_scoped_trace import serialize
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    run = tmp_path / "run.xplane.pb"
    run.write_bytes(serialize(synthetic()))
    for name, src, mtime in (("run", run, 1e9),
                             ("newer", os.path.join(
                                 DATA, "v5e_grpo_tiny.xplane.pb"), 2e9)):
        d = tmp_path / f"bench-trace-{name}" / "plugins" / "profile" / "1"
        d.mkdir(parents=True)
        shutil.copy(src, d / "x.xplane.pb")
        os.utime(d / "x.xplane.pb", (mtime, mtime))
    plain = ctx_for(trace.load(str(run)))
    scoped = ctx_for(program_trace.from_planes(synthetic()))
    for mod in READERS:
        assert mod.read(plain) == mod.read(scoped) is not None
    # a window no file has: silent
    moved = dataclasses.replace(plain, hi=plain.hi + 1)
    assert all(mod.read(moved) is None for mod in READERS)
