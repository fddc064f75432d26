"""repro.analysis (jaxlint) — the static analyzer that encodes this repo's
JAX bug classes as checkable rules.

Fixture pairs per rule (positive MUST flag with the right rule id,
negative MUST stay clean), including source-level reconstructions of the
two incidents that motivated the linter:

* the PR-2 NFT bug — a jitted loss reading ``self.ref_params`` that
  ``update_extras`` mutates between rounds (R003 mutable-closure-capture);
* the PR-5 perf bug — per-metric ``float()`` host syncs inside the train
  step loop (R002 host-sync-in-hot-loop).

Plus the meta self-run: ``src/repro`` + ``benchmarks`` + ``examples`` must
be clean modulo the committed baseline, so a future PR reintroducing
either class fails tier-1; and the stdlib-only contract: importing
``repro.analysis`` must not pull in jax or numpy.
"""
import json
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import ScopeGraph, rule_ids
from repro.analysis.core import Module, parse_suppressions
from repro.analysis import baseline as bl
from repro.analysis.cli import main as cli_main, run_paths

ROOT = Path(__file__).resolve().parent.parent


def lint(tmp_path: Path, source: str, name: str = "mod.py"):
    """Write one fixture module, lint it, return reportable findings."""
    f = tmp_path / name
    f.write_text(source)
    findings, suppressed, graph = run_paths([str(f)])
    return findings


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------------------------- R001

def test_r001_key_reuse_flagged(tmp_path):
    findings = lint(tmp_path, """\
import jax

def sample(key):
    a = jax.random.normal(key, (3,))
    b = jax.random.uniform(key, (3,))
    return a + b
""")
    assert rules_of(findings) == ["R001"]
    assert len(findings) == 1           # only the second consumption


def test_r001_split_then_use_clean(tmp_path):
    findings = lint(tmp_path, """\
import jax

def sample(key):
    k1, k2 = jax.random.split(key)
    a = jax.random.normal(k1, (3,))
    b = jax.random.uniform(k2, (3,))
    return a + b
""")
    assert findings == []


def test_r001_exclusive_return_branches_clean(tmp_path):
    # the sample_timesteps shape: each branch consumes the key once and
    # returns — never two consumptions on one path
    findings = lint(tmp_path, """\
import jax

def sample(key, how):
    if how == "uniform":
        return jax.random.uniform(key, (3,))
    if how == "normal":
        return jax.random.normal(key, (3,))
    return jax.random.bernoulli(key, 0.5, (3,))
""")
    assert findings == []


def test_r001_loop_reuse_flagged_fold_in_clean(tmp_path):
    flagged = lint(tmp_path, """\
import jax

def noisy(key, n):
    out = []
    for i in range(n):
        out.append(jax.random.normal(key, (3,)))
    return out
""")
    assert rules_of(flagged) == ["R001"]
    clean = lint(tmp_path, """\
import jax

def noisy(key, n):
    out = []
    for i in range(n):
        k = jax.random.fold_in(key, i)
        out.append(jax.random.normal(k, (3,)))
    return out
""", name="clean.py")
    assert clean == []


# ------------------------------------------------------------------- R002

PR5_SYNC_LOOP = """\
import jax

def run(trainer, steps):
    history = []
    for it in range(steps):
        m = jax.device_get(trainer.step(it))
        history.append({
            "reward": float(m["reward_mean"]),
            "loss": float(m["loss"]),
            "grad_norm": float(m["grad_norm"]),
        })
    return history
"""


def test_r002_pr5_per_metric_sync_loop_flagged(tmp_path):
    """Reconstruction of the PR-5 incident: metrics arrive via ONE
    device_get but are then float()ed per value inside the step loop."""
    findings = lint(tmp_path, PR5_SYNC_LOOP)
    assert rules_of(findings) == ["R002"]
    assert len(findings) == 3           # one per float()


def test_r002_convert_at_transfer_site_clean(tmp_path):
    # the PR-5 fix shape: one device_get, tree-mapped to float once
    findings = lint(tmp_path, """\
import jax

def run(trainer, steps):
    history = []
    for it in range(steps):
        m = jax.tree.map(float, jax.device_get(trainer.step(it)))
        history.append({"reward": m["reward_mean"], "loss": m["loss"]})
    return history
""")
    assert findings == []


def test_r002_sync_on_fresh_device_compute_flagged(tmp_path):
    # the serve.py:88 shape — flagged even outside a loop
    findings = lint(tmp_path, """\
import jax.numpy as jnp

def report(latents):
    return float(jnp.sqrt((latents ** 2).mean()))
""")
    assert rules_of(findings) == ["R002"]


def test_r002_host_floats_clean(tmp_path):
    findings = lint(tmp_path, """\
def run(rows):
    out = []
    for r in rows:
        out.append({"a": float(r["a"]), "b": float(r["b"])})
    return out
""")
    assert findings == []


# ------------------------------------------------------------------- R003

PR2_NFT_CAPTURE = """\
import jax

class Trainer:
    def __init__(self):
        self.ref_params = {"w": 1.0}
        self._update_jit = jax.jit(self.loss_fn)

    def update_extras(self):
        self.ref_params = {"w": 2.0}   # refresh the reference policy

    def loss_fn(self, params):
        ref = self.ref_params
        return params["w"] - ref["w"]
"""


def test_r003_pr2_nft_closure_capture_flagged(tmp_path):
    """Reconstruction of the PR-2 incident: the jitted loss closes over
    ``self.ref_params``, which ``update_extras`` mutates between rounds —
    the traced constant never sees the refresh (flat reward curve)."""
    findings = lint(tmp_path, PR2_NFT_CAPTURE)
    assert rules_of(findings) == ["R003"]
    (f,) = findings
    assert "ref_params" in f.message and "update_extras" in f.message


def test_r003_init_only_attr_clean(tmp_path):
    findings = lint(tmp_path, """\
import jax

class Trainer:
    def __init__(self):
        self.scale = 2.0
        self._fn = jax.jit(self.loss_fn)

    def loss_fn(self, params):
        return params["w"] * self.scale
""")
    assert findings == []


def test_r003_nonlocal_rebind_after_def_flagged(tmp_path):
    findings = lint(tmp_path, """\
import jax

def build(scale):
    def body(x):
        return x * scale
    scale = scale * 2
    return jax.jit(body)
""")
    assert rules_of(findings) == ["R003"]


# ------------------------------------------------------------------- R004

def test_r004_branch_on_tracer_flagged(tmp_path):
    findings = lint(tmp_path, """\
import jax
import jax.numpy as jnp

@jax.jit
def clip(x):
    y = jnp.sum(x)
    if y > 0:
        return x
    return -x
""")
    assert rules_of(findings) == ["R004"]


def test_r004_static_branches_clean(tmp_path):
    # config-style branching on plain params / shapes is static and fine
    findings = lint(tmp_path, """\
import jax
import jax.numpy as jnp

@jax.jit
def f(x, mode="a"):
    if mode == "a":
        return jnp.tanh(x)
    if x.shape[0] > 4:
        return x[:4]
    return x
""")
    assert findings == []


def test_r004_untraced_function_clean(tmp_path):
    # host code may branch on concrete array values freely
    findings = lint(tmp_path, """\
import jax.numpy as jnp

def early_stop(history):
    v = jnp.asarray(history)
    if v.sum() > 0:
        return True
    return False
""")
    assert findings == []


# ------------------------------------------------------------------- R005

def test_r005_read_after_donate_flagged(tmp_path):
    findings = lint(tmp_path, """\
import jax

def train(step_fn, state, batch):
    step = jax.jit(step_fn, donate_argnums=0)
    new_state = step(state, batch)
    return new_state, state["metrics"]
""")
    assert rules_of(findings) == ["R005"]


def test_r005_reassign_result_clean(tmp_path):
    # the repo idiom: the donated buffer is immediately reassigned
    findings = lint(tmp_path, """\
import jax

def train(step_fn, state, batch):
    step = jax.jit(step_fn, donate_argnums=0)
    state = step(state, batch)
    return state["metrics"]
""")
    assert findings == []


def test_r005_with_body_is_walked_in_line(tmp_path):
    # a donating call inside a with-block: reassigning the result there is
    # clean, reading the donated buffer after the block is flagged
    clean = lint(tmp_path, """\
import jax

def train(step_fn, state, batch, span):
    step = jax.jit(step_fn, donate_argnums=0)
    with span("update"):
        state = step(state, batch)
    return state["metrics"]
""")
    assert clean == []
    flagged = lint(tmp_path, """\
import jax

def train(step_fn, state, batch, span):
    step = jax.jit(step_fn, donate_argnums=0)
    with span("update"):
        new_state = step(state, batch)
    return new_state, state["metrics"]
""")
    assert rules_of(flagged) == ["R005"]


# ------------------------------------------------------------------- R006

def test_r006_unhashable_static_arg_flagged(tmp_path):
    findings = lint(tmp_path, """\
import functools
import jax

@functools.partial(jax.jit, static_argnames=("cfg",))
def run(x, cfg):
    return x

def driver(x):
    return run(x, cfg={"width": 8})
""")
    assert rules_of(findings) == ["R006"]


def test_r006_hashable_static_arg_clean(tmp_path):
    findings = lint(tmp_path, """\
import functools
import jax

@functools.partial(jax.jit, static_argnames=("cfg",))
def run(x, cfg):
    return x

def driver(x):
    return run(x, cfg=("width", 8))
""")
    assert findings == []


def test_r006_jit_in_loop_flagged(tmp_path):
    findings = lint(tmp_path, """\
import jax

def sweep(fns, x):
    outs = []
    for fn in fns:
        outs.append(jax.jit(fn)(x))
    return outs
""")
    assert rules_of(findings) == ["R006"]


# ------------------------------------------------------------------- R007

def test_r007_pre_pipeline_loop_flagged(tmp_path):
    # source-level reconstruction of the pre-pipeline TrainLoop: dispatch a
    # jitted step, then immediately device_get its metrics in the same
    # iteration — the drain blocks the next dispatch
    findings = lint(tmp_path, """\
import jax

class Loop:
    def __init__(self, fn, state):
        self._step_jit = jax.jit(fn)
        self.state = state

    def run(self, batches, key):
        history = []
        for it, batch in enumerate(batches):
            self.state, metrics = self._step_jit(self.state, batch, key)
            m = jax.device_get(metrics)
            history.append(m)
        return history
""")
    assert rules_of(findings) == ["R007"]
    assert len(findings) == 1


def test_r007_float_on_dispatched_output_flagged(tmp_path):
    findings = lint(tmp_path, """\
import jax

def run(step, xs, state):
    step_jit = jax.jit(step)
    losses = []
    for x in xs:
        state, loss = step_jit(state, x)
        losses.append(float(loss))
    return losses
""")
    assert rules_of(findings) == ["R007"]


def test_r007_lagged_deque_drain_clean(tmp_path):
    # the pipelined shape this PR's TrainLoop uses: buffer the in-flight
    # step's outputs and drain them >=1 step late / after the loop
    findings = lint(tmp_path, """\
import collections
import jax

def run(step, xs, state):
    step_jit = jax.jit(step)
    pending = collections.deque()
    out = []
    for x in xs:
        state, loss = step_jit(state, x)
        pending.append(loss)
        if len(pending) > 1:
            out.append(float(jax.device_get(pending.popleft())))
    while pending:
        out.append(float(jax.device_get(pending.popleft())))
    return out
""")
    assert findings == []


def test_r007_drain_after_loop_clean(tmp_path):
    findings = lint(tmp_path, """\
import jax

def run(step, xs, state):
    step_jit = jax.jit(step)
    losses = []
    for x in xs:
        state, loss = step_jit(state, x)
        losses.append(loss)
    return [float(v) for v in jax.device_get(losses)]
""")
    assert findings == []


# ----------------------------------------------------- suppressions / R000

def test_suppression_with_reason_honored(tmp_path):
    findings = lint(tmp_path, """\
import jax

def sample(key):
    a = jax.random.normal(key, (3,))
    # jaxlint: disable=R001 — deliberate common-random-numbers baseline
    b = jax.random.normal(key, (3,))
    return a + b
""")
    assert findings == []


def test_suppression_without_reason_is_a_finding(tmp_path):
    findings = lint(tmp_path, """\
import jax

def sample(key):
    a = jax.random.normal(key, (3,))
    b = jax.random.normal(key, (3,))  # jaxlint: disable=R001
    return a + b
""")
    # the bare disable= is itself flagged AND does not suppress
    assert rules_of(findings) == ["R000", "R001"]


def test_suppression_in_docstring_is_prose(tmp_path):
    findings = lint(tmp_path, '''\
def helper():
    """Mentions `# jaxlint: disable=R001` as documentation only."""
    return 1
''')
    assert findings == []
    mod = Module.parse(tmp_path / "mod.py")
    assert mod.suppressions == []


def test_multiline_standalone_suppression_covers_next_code_line(tmp_path):
    src = (
        "import jax\n"
        "def sample(key):\n"
        "    a = jax.random.normal(key, (3,))\n"
        "    # jaxlint: disable=R001 — first half of the why,\n"
        "    # wrapped onto a continuation comment line\n"
        "    b = jax.random.normal(key, (3,))\n"
        "    return a + b\n")
    assert lint(tmp_path, src) == []


def test_unknown_rule_id_flagged(tmp_path):
    findings = lint(tmp_path, """\
x = 1  # jaxlint: disable=R999 — no such rule
""")
    assert rules_of(findings) == ["R000"]


def test_list_suppressions_mode(tmp_path, capsys):
    f = tmp_path / "mod.py"
    f.write_text("""\
import jax

def g(key):
    # jaxlint: disable=R001 — audit me
    b = jax.random.normal(key, (3,))
    return b
""")
    rc = cli_main(["--list-suppressions", str(f)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "R001" in out and "audit me" in out and "1 suppression(s)" in out


# -------------------------------------------------------- scope graph unit

def test_wrapper_layer_traces_argument(tmp_path):
    """The distributed.jit_* idiom: passing a function through a wrapper
    whose parameter flows into jax.jit marks it traced."""
    f = tmp_path / "mod.py"
    f.write_text("""\
import jax

def jit_update(fn, mesh):
    return jax.jit(fn, donate_argnums=(0,))

def _update(state, batch):
    return state

def host_side(rows):
    return len(rows)

def build(mesh):
    return jit_update(_update, mesh)
""")
    mod = Module.parse(f)
    graph = ScopeGraph([mod])
    by_name = {fi.name: fi for fi in graph.module_functions(mod)}
    assert graph.is_traced(by_name["_update"])
    assert not graph.is_traced(by_name["host_side"])
    assert not graph.is_traced(by_name["build"])


def test_wrapper_layer_transitive_through_helper(tmp_path):
    """The 2-D distributed.jit_* layering: jit_sample forwards fn into a
    shared _plan_jit helper which calls jax.jit.  The forwarding function
    must itself become a wrapper (its call sites trace the argument), and
    the non-donating helper chain must NOT acquire donation marks."""
    f = tmp_path / "mod.py"
    f.write_text("""\
import jax

def _plan_jit(fn, in_shardings, out_shardings=None):
    kw = {}
    if out_shardings is not None:
        kw["out_shardings"] = out_shardings
    return jax.jit(fn, in_shardings=in_shardings, **kw)

def jit_sample(fn, mesh, params_sharding=None):
    return _plan_jit(fn, (params_sharding, None), None)

def _sample(params, cond, key):
    return params

def host_side(rows):
    return len(rows)

def build(mesh):
    return jit_sample(_sample, mesh)
""")
    mod = Module.parse(f)
    graph = ScopeGraph([mod])
    by_name = {fi.name: fi for fi in graph.module_functions(mod)}
    # transitive: _sample reaches jax.jit through jit_sample -> _plan_jit
    assert graph.is_traced(by_name["_sample"])
    assert not graph.is_traced(by_name["host_side"])
    # position 0 of both layers is a wrapper position...
    assert 0 in graph.wrapper_positions[id(by_name["jit_sample"].node)]
    assert 0 in graph.wrapper_positions[id(by_name["_plan_jit"].node)]
    # ...and neither layer donates (no donate_argnums anywhere)
    assert id(by_name["jit_sample"].node) not in graph.wrapper_donates
    assert id(by_name["_plan_jit"].node) not in graph.wrapper_donates


def test_wrapper_donation_inherited_through_forwarding(tmp_path):
    """Donation marks propagate up a forwarding chain: a helper whose
    jax.jit passes donate_argnums hands its donated positions to every
    wrapper that forwards a function into it — R005's donated-buffer
    tracking keys off the outermost call site."""
    f = tmp_path / "mod.py"
    f.write_text("""\
import jax

def _donating_jit(fn, shardings):
    return jax.jit(fn, in_shardings=shardings, donate_argnums=(0,))

def jit_update(fn, mesh, state_sharding=None):
    return _donating_jit(fn, (state_sharding, None))

def _update(state, batch):
    return state

def not_forwarding(fn, mesh):
    # fn never reaches a traced position: stays a plain function
    return (fn, mesh)
""")
    mod = Module.parse(f)
    graph = ScopeGraph([mod])
    by_name = {fi.name: fi for fi in graph.module_functions(mod)}
    assert graph.wrapper_donates[id(by_name["_donating_jit"].node)] == {0}
    # inherited by the forwarding layer
    assert graph.wrapper_donates[id(by_name["jit_update"].node)] == {0}
    assert 0 in graph.wrapper_positions[id(by_name["jit_update"].node)]
    # a function that merely receives fn without forwarding it into a
    # traced position is neither wrapper nor donor
    assert id(by_name["not_forwarding"].node) not in graph.wrapper_positions
    assert id(by_name["not_forwarding"].node) not in graph.wrapper_donates


# ---------------------------------------------------------------- baseline

def test_baseline_roundtrip_and_staleness(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text(PR5_SYNC_LOOP)
    findings, _, _ = run_paths([str(f)])
    assert findings
    base_file = tmp_path / "base.json"
    bl.save(base_file, findings)
    base = bl.load(base_file)
    new, matched, stale = bl.split(findings, base)
    assert new == [] and len(matched) == len(findings) and stale == []
    # fingerprints survive a pure line shift
    f.write_text("# a new leading comment\n" + PR5_SYNC_LOOP)
    shifted, _, _ = run_paths([str(f)])
    new, matched, stale = bl.split(shifted, base)
    assert new == [] and len(matched) == len(findings) and stale == []
    # fixing the bug makes the entries stale, not failing
    f.write_text("def run():\n    return []\n")
    fixed, _, _ = run_paths([str(f)])
    new, matched, stale = bl.split(fixed, base)
    assert new == [] and matched == [] and len(stale) == len(findings)


def test_cli_json_format_and_exit_codes(tmp_path, capsys, monkeypatch):
    f = tmp_path / "mod.py"
    f.write_text(PR5_SYNC_LOOP)
    monkeypatch.chdir(tmp_path)
    rc = cli_main(["--format", "json", "--no-baseline", str(f)])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert {x["rule"] for x in payload["new"]} == {"R002"}
    # accept into a baseline -> clean exit
    rc = cli_main(["--update-baseline", str(f)])
    capsys.readouterr()
    assert rc == 0
    rc = cli_main([str(f)])
    out = capsys.readouterr().out
    assert rc == 0 and "0 new" in out


# ---------------------------------------------------------- meta self-runs

def test_repo_is_clean_modulo_baseline(monkeypatch, capsys):
    """Any future PR reintroducing a linted bug class fails here."""
    monkeypatch.chdir(ROOT)
    rc = cli_main(["src/repro", "benchmarks", "examples"])
    out = capsys.readouterr().out
    assert rc == 0, f"jaxlint found new violations:\n{out}"


def test_every_rule_has_fixture_coverage():
    covered = {"R000", "R001", "R002", "R003", "R004", "R005", "R006",
               "R007"}
    assert set(rule_ids()) == covered, (
        "new rule registered — add positive/negative fixtures for it in "
        "this file and extend `covered`")


def test_analysis_imports_are_stdlib_only():
    """`python -m repro.analysis` must work with jax/numpy absent."""
    code = (
        "import sys\n"
        "sys.modules['jax'] = None\n"
        "sys.modules['numpy'] = None\n"
        "import repro.analysis\n"
        "import repro.analysis.cli\n"
        "assert sys.modules.get('jax') is None\n"
        "print('ok')\n")
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True,
        env={"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "ok"
