"""Reusable RL training loop with a small callback protocol.

One loop serves every entry point (``launch/train.py``, the examples, sweep
workers): iterate the prompt dataset, fetch condition embeddings from the
:class:`ConditionProvider`, run ``trainer.step``, and fan the metric row out
to callbacks.  Checkpointing saves the trainer's **full** ``RLState``
(params *and* AdamW moments), so a resumed run continues bit-identically.

Pipelining (``LoopConfig.pipeline``): ``trainer.step`` returns *device*
scalars, and jax dispatch is asynchronous — the only thing that forces the
host to wait for step N is fetching its metrics.  With ``pipeline=K`` the
loop keeps up to K dispatched-not-yet-drained steps in a queue and fetches
metrics one-or-more steps late, so the host-side work of iteration N+1
(prompt batching, condition lookup, metric-row IO) overlaps step N's device
execution.  Buffer donation single-buffers the RLState, so the in-flight
depth is bounded by design; K only bounds the *metric* lag.  One backend
caveat: the CPU PJRT client runs a *donated* execution synchronously when
its input buffer came off the device, so on XLA:CPU ``trainer.step`` only
returns once the update finished and nothing is ever in flight — set
``dist.donate_state=false`` there to get real run-ahead (double-buffers
the state; on GPU/TPU donation dispatches asynchronously and should stay
on).  The contract:

* ``pipeline=1`` is bit-identical to the historical sequential loop —
  same dispatch order, same keys, same rows, same callback timing.
* ``pipeline>1`` changes *when* metrics are observed, never *what* is
  computed: params after N steps are bitwise equal for every K, the rows
  are the same set, and callbacks still fire in step order — just lagged.

Callbacks are lag-aware: they fire on *drained* steps.  A callback that
must see ``trainer.state`` exactly as of its step (``PeriodicCheckpoint``)
returns True from :meth:`Callback.wants_sync`; the loop then drains fully
after dispatching that step, before anything newer is dispatched — with
donation there is exactly one live state, so the barrier is what keeps
crash/resume bit-identical under any K.  :class:`EarlyStop` observes
metrics up to K-1 steps late, so a stop request lands after at most K-1
extra dispatched steps (which are drained and logged — they did run).

Per-row timing under pipelining: ``dt`` is the dispatch→drain latency of
that step (for K=1 exactly the old per-step wall time), while
``steps_per_s`` is the end-to-end drained-step rate excluding the first
(compile-laden) step — the number that shows the overlap win.  The window
is anchored at the *dispatch* of the second step, not its drain: drain
times bunch when the device runs ahead during a blocking fetch (a short
pipelined run drains its whole tail microseconds apart), and a
drain-to-drain span would then divide by ~zero.  Every counted step's
device work happens after its dispatch, and the jit trace/compile block
lives in the first step's dispatch, so the dispatch anchor measures real
work.  Reporting ``dt`` and ``steps_per_s`` separately avoids the PR-3
"inf req/s" artifact class: a lagged drain makes per-step deltas
meaningless as throughput.

Built-in callbacks: :class:`MetricLogger` (console), :class:`JSONLogSink`
(metric-log file), :class:`PeriodicCheckpoint` (full-state saves),
:class:`EarlyStop` (patience on any metric).  Custom callbacks subclass
:class:`Callback` and may call ``loop.request_stop()``.
"""
from __future__ import annotations

import json
import os
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax

from repro import checkpoint


def _no_sync(loop: "TrainLoop", step: int) -> bool:
    """Default for duck-typed callbacks that don't define ``wants_sync``."""
    return False


class Callback:
    """No-op base; override any subset of the hooks."""

    def on_train_start(self, loop: "TrainLoop") -> None:
        pass

    def on_step(self, loop: "TrainLoop", step: int,
                metrics: Dict[str, Any]) -> None:
        pass

    def wants_sync(self, loop: "TrainLoop", step: int) -> bool:
        """Return True if ``on_step(step)`` must observe ``trainer.state``
        exactly as of ``step``.  The loop then drains every in-flight step
        (including this one) before dispatching anything newer — donation
        keeps a single live state, so this barrier is the only way a
        callback can see the post-``step`` state under ``pipeline>1``."""
        return False

    def on_train_end(self, loop: "TrainLoop",
                     history: List[Dict[str, Any]]) -> None:
        pass


class MetricLogger(Callback):
    """Console progress every ``every`` steps (and on the final step).

    Prints both per-row numbers the loop reports under pipelining: ``dt``
    (that step's dispatch→drain latency) and ``steps/s`` (end-to-end
    drained-step throughput, compile step excluded)."""

    def __init__(self, every: int = 10):
        self.every = every

    def on_step(self, loop, step, metrics):
        if self.every and (step % self.every == 0
                           or step == loop.steps - 1):
            sps = metrics.get("steps_per_s", 0.0)
            print(f"  step {step:4d}  reward={metrics['reward']:+.4f}  "
                  f"loss={metrics['loss']:+.4f}  dt={metrics['dt']:.2f}s  "
                  f"{sps:.2f} steps/s",
                  flush=True)


class JSONLogSink(Callback):
    """Maintain the full metric history at ``path`` as a JSON array,
    flushed incrementally so a crashed/preempted run keeps every step it
    logged (``PeriodicCheckpoint`` already saved the state; losing the
    metric history to a crash made the two sinks inconsistent).

    Each flush writes the whole array to a temp file and atomically renames
    it over ``path`` — a kill mid-write can never leave a truncated log.
    ``flush_every`` throttles the rewrite for long runs (the final state is
    always written at train end).

    Lag-aware for free: rows are appended to ``loop.history`` at *drain*
    time, in step order, so under ``pipeline>1`` the log never contains a
    step whose device work had not finished — a crash mid-pipeline loses
    only not-yet-drained steps, which resume recomputes bit-identically.

    Resume-aware: rows from a previous (interrupted) run that precede this
    run's ``start_step`` are preserved, so the log always covers step 0..N
    even across restarts; a resume with nothing left to do keeps the
    existing log untouched."""

    def __init__(self, path: str, flush_every: int = 1):
        self.path = path
        self.flush_every = max(1, flush_every)
        self._prior: List[Dict[str, Any]] = []

    def on_train_start(self, loop):
        self._prior = []
        if loop.start_step and os.path.exists(self.path):
            try:
                with open(self.path) as f:
                    rows = json.load(f)
                self._prior = [r for r in rows if r.get("step", -1)
                               < loop.start_step]
            except (ValueError, OSError):
                pass                     # unreadable prior log: start fresh

    def _flush(self, history) -> None:
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self._prior + history, f)
        os.replace(tmp, self.path)

    def on_step(self, loop, step, metrics):
        if len(loop.history) % self.flush_every == 0:
            self._flush(loop.history)

    def on_train_end(self, loop, history):
        if not history:
            return                       # nothing ran: leave the log alone
        self._flush(history)


class PeriodicCheckpoint(Callback):
    """Save the trainer's full RLState every ``every`` steps.

    Declares ``wants_sync`` on its save steps: with donation the trainer
    holds ONE live state (the newest dispatched step's), so the loop must
    drain the pipeline before the save for the checkpoint to be exactly
    the post-``step`` state — which is what keeps resume bit-identical
    under any ``pipeline`` depth."""

    def __init__(self, ckpt_dir: str, every: int = 50):
        self.ckpt_dir = ckpt_dir
        self.every = every

    def wants_sync(self, loop, step):
        return bool(self.every) and (step + 1) % self.every == 0

    def on_step(self, loop, step, metrics):
        if self.every and (step + 1) % self.every == 0:
            with jax.profiler.TraceAnnotation("repro.checkpoint.save"):
                checkpoint.save_checkpoint(self.ckpt_dir, step + 1,
                                           loop.trainer.state)


class EarlyStop(Callback):
    """Stop when ``metric`` hasn't improved by ``min_delta`` for
    ``patience`` consecutive steps (higher is better).

    Under ``pipeline=K`` the metrics arrive up to K-1 steps late, so the
    stop request lands after at most K-1 extra steps were dispatched;
    those are drained and logged (their device work already ran) and the
    loop stops before dispatching anything further."""

    def __init__(self, metric: str = "reward", patience: int = 20,
                 min_delta: float = 0.0):
        self.metric = metric
        self.patience = patience
        self.min_delta = min_delta
        self.best: Optional[float] = None
        self.stale = 0

    def on_step(self, loop, step, metrics):
        val = float(metrics[self.metric])
        if self.best is None or val > self.best + self.min_delta:
            self.best, self.stale = val, 0
            return
        self.stale += 1
        if self.stale >= self.patience:
            print(f"[early-stop] {self.metric} stalled at {self.best:+.4f} "
                  f"for {self.patience} steps", flush=True)
            loop.request_stop()


class TrainLoop:
    """Drive ``trainer.step`` over a prompt dataset.

    ``start_step > 0`` resumes: the data stream is fast-forwarded past the
    batches already consumed (``dataset.infinite(skip)`` — O(1) for
    :class:`repro.data.prompts.PromptDataset`; datasets without the skip
    parameter are replay-skipped) and iteration keys are re-derived from
    the step index (``trainer.step`` folds the key by ``it``), so a
    resumed run replays the exact schedule of an uninterrupted one.

    ``pipeline`` is the max number of dispatched-not-yet-drained steps
    (see the module docstring for the exactness contract).  Between a
    dispatch and its drain the loop also arms the overlap hooks when the
    collaborators provide them: ``provider.prefetch(next_prompts)`` warms
    the next condition batch on a background thread, and
    ``trainer.prefetch_reward_params()`` starts the H2D copy of
    host-offloaded reward towers (``perf.offload_rewards``) — both run
    while the in-flight step's device work proceeds.
    """

    def __init__(self, trainer, provider, dataset, *, steps: int,
                 key: jax.Array, start_step: int = 0,
                 callbacks: Sequence[Callback] = (), pipeline: int = 1):
        if pipeline < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {pipeline}")
        self.trainer = trainer
        self.provider = provider
        self.dataset = dataset
        self.steps = steps
        self.key = key
        self.start_step = start_step
        self.callbacks = list(callbacks)
        self.pipeline = pipeline
        self.history: List[Dict[str, Any]] = []
        self._stop = False
        self._t_window0: Optional[float] = None
        self._n_drained = 0

    def request_stop(self) -> None:
        self._stop = True

    # ------------------------------------------------------------- plumbing
    def _stream(self):
        """Prompt-batch iterator positioned past ``start_step`` batches."""
        try:
            return self.dataset.infinite(self.start_step)
        except TypeError:
            # dataset without the skip fast path: replay-skip (O(n))
            stream = self.dataset.infinite()
            for _ in range(self.start_step):
                next(stream)
            return stream

    def _drain_one(self, pending: Deque[Tuple[int, Any, float]]) -> None:
        """Fetch the oldest in-flight step's metrics and fan out the row.
        ONE host transfer for the whole metric dict — the trainer returns
        device scalars (reward_mean included, computed inside the
        rewards/fused jit); fetching per-metric with float() cost ~8
        separate syncs per step.  Converting at the transfer site keeps
        the loop body sync-free (jaxlint R002/R007)."""
        with jax.profiler.TraceAnnotation("repro.loop.drain"):
            self._drain(*pending.popleft())

    def _drain(self, it: int, metrics: Any, t_dispatch: float) -> None:
        with jax.profiler.TraceAnnotation("repro.loop.fetch"):
            m = jax.tree.map(float, jax.device_get(metrics))
        now = time.time()
        self._n_drained += 1
        # end-to-end drained-step rate over a window anchored at the SECOND
        # step's dispatch: the first step carries the compile, and anchoring
        # at a drain time is unsafe — tail drains bunch microseconds apart
        # once the device has run ahead, collapsing the span (the PR-3
        # "inf req/s" artifact class)
        span = (now - self._t_window0
                if self._t_window0 is not None else 0.0)
        sps = ((self._n_drained - 1) / span
               if self._n_drained > 1 and span > 0 else 0.0)
        row: Dict[str, Any] = {
            "step": it,
            "reward": m["reward_mean"],
            "loss": m["loss"],
            "grad_norm": m["grad_norm"],
            "encode_resident": self.provider.encoder_resident,
            "dt": round(now - t_dispatch, 3),
            "steps_per_s": round(sps, 3),
        }
        for k, v in m.items():
            if k.startswith("reward/"):
                row[k] = v
        self.history.append(row)
        with jax.profiler.TraceAnnotation("repro.loop.callbacks"):
            for cb in self.callbacks:
                cb.on_step(self, it, row)

    # ------------------------------------------------------------------ run
    def run(self) -> List[Dict[str, Any]]:
        for cb in self.callbacks:
            cb.on_train_start(self)
        self._t_window0 = None
        self._n_drained = 0
        stream = self._stream()
        pending: Deque[Tuple[int, Any, float]] = deque()
        next_prompts: Optional[List[str]] = None
        can_prefetch = hasattr(self.provider, "prefetch")
        can_prefetch_rewards = hasattr(self.trainer,
                                       "prefetch_reward_params")
        for it in range(self.start_step, self.steps):
            if self._stop:
                break
            # host spans (profiler only): what the host does in each step
            with jax.profiler.StepTraceAnnotation("repro.step", step_num=it):
                prompts = next_prompts if next_prompts is not None \
                    else next(stream)
                next_prompts = None
                with jax.profiler.TraceAnnotation("repro.loop.conditions"):
                    cond = self.provider.get(prompts)["cond"]
                t_dispatch = time.time()
                with jax.profiler.TraceAnnotation("repro.loop.dispatch"):
                    out = self.trainer.step(cond, self.key, it=it)
                pending.append((it, out, t_dispatch))
                if it == self.start_step + 1:  # second dispatch: compiled
                    self._t_window0 = t_dispatch
                # overlap host work with the in-flight device step(s): pull
                # the next prompt batch, warm its conditions, start the
                # reward-tower H2D copy — all before blocking on any drain
                with jax.profiler.TraceAnnotation("repro.loop.prefetch"):
                    if it + 1 < self.steps:
                        next_prompts = next(stream)
                        if can_prefetch:
                            self.provider.prefetch(next_prompts)
                    if can_prefetch_rewards:
                        self.trainer.prefetch_reward_params()
                # a sync-hungry callback (checkpoint) forces a full drain so
                # it observes trainer.state exactly as of this step;
                # otherwise keep at most `pipeline` steps in flight
                # (duck-typed: user callbacks need not subclass Callback, so
                # wants_sync is optional)
                barrier = any(
                    getattr(cb, "wants_sync", _no_sync)(self, it)
                    for cb in self.callbacks)
                limit = 0 if barrier else self.pipeline - 1
                while len(pending) > limit:
                    self._drain_one(pending)
        while pending:                    # drain the tail (and on stop: the
            self._drain_one(pending)      # already-dispatched steps DID run)
        for cb in self.callbacks:
            cb.on_train_end(self, self.history)
        return self.history
