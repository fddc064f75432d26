"""The comparison that decides ``correct`` fails a run with the timed path
broken underneath (the chip check skipped, tiny sizes on the CPU)."""
import pytest

from bench import harness
from bench.tools.tiny import shrink
from repro.core.rollout import Trajectory
from repro.core.trainers.base import BaseTrainer

CELLS = [w["name"] for w in harness.load_json(
    harness.ROOT / "BENCHMARK.json")["workloads"]]
SEED = 2 ** 31 + 5


def unchanged_state(update):
    def broken(self, state, traj, adv, key, extras=()):
        _, aux = update(self, state, traj, adv, key, extras)
        return state, aux
    return broken


def half_batch(update):
    def broken(self, state, traj, adv, key, extras=()):
        h = adv.shape[0] // 2
        traj = Trajectory(xs=traj.xs[:, :h], logps=traj.logps[:, :h],
                          ts=traj.ts, sde_mask=traj.sde_mask,
                          cond=traj.cond[:h])
        return update(self, state, traj, adv[:h], key, extras)
    return broken


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault,number,seed",
                         [(unchanged_state, "change_gap", SEED),
                          (half_batch, None, SEED + 1)])
def test_broken_step_is_not_correct(workload, fault, number, seed,
                                    monkeypatch):
    monkeypatch.setattr(BaseTrainer, "_update",
                        fault(BaseTrainer._update))
    r = harness.measure(harness.resolve_cell(workload), seed, 0.0, False,
                        require_tpu=False,
                        edit=shrink)
    assert r["correct"] is False
    failed = [n for n, c in r["checks"].items()
              if not c["value"] <= c["limit"]]
    assert failed
    if number:
        assert number in failed
        assert r["checks"][number]["value"] > 0.9
