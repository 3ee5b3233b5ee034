"""``Trainer.run``'s one step of input lookahead (DESIGN.md §12): step
k+1's batch is built between step k's dispatch and its wait, never past
the run's end, and every step trains on ``batch_at(step)`` through
retry, recovery and a pipeline that raises."""
import time

import jax.numpy as jnp
import pytest


class Boom(RuntimeError):
    """Raised by a ``Recording`` pipeline at its ``raise_at`` step."""


class Recording:
    """``batch_at`` of ``pipe`` that records each step it is asked for,
    and raises ``Boom`` at ``raise_at``."""

    def __init__(self, pipe, raise_at: int | None = None):
        self.pipe, self.raise_at = pipe, raise_at
        self.steps: list[int] = []

    def batch_at(self, step: int):
        self.steps.append(step)
        if step == self.raise_at:
            raise Boom(f"no batch at step {step}")
        return self.pipe.batch_at(step)


def _reference_losses(tiny_train, n: int) -> list[float]:
    """The losses of a loop that builds each step's batch just before it."""
    ts, pipe, params, opt = tiny_train
    p, s, out = params, opt.init(params), []
    for k in range(n):
        p, s, m = ts.fn(p, s, pipe.batch_at(k), jnp.int32(k))
        out.append(float(m["loss"]))
    return out


def _counters(trainer) -> tuple[float, float]:
    snap = trainer.metrics.snapshot()
    return snap["input_ahead_total"], snap["input_ahead_dropped_total"]


def test_next_input_is_built_inside_the_step_before_it(tiny_train):
    from repro.obs import recorded_spans
    from repro.runtime import Trainer

    ts, pipe, params, opt = tiny_train
    lo = time.time_ns()
    Trainer(ts, pipe, None, log_every=1000).run(params, opt.init(params), 3)
    got = recorded_spans(lo, time.time_ns())

    def one(name, step, **attrs):
        (s,) = [s for s in got if s.name == name and s.step == step
                and all(s.attrs.get(k) == v for k, v in attrs.items())]
        return s

    assert one("train.input", 0, ahead=False).parent == "train.step"
    for k in (0, 1):
        step, dispatch, wait = (one("train.step", k),
                                one("train.dispatch", k),
                                one("train.wait", k))
        ahead = one("train.input", k, ahead=True)    # step k+1's input
        assert ahead.parent == "train.step"
        assert (step.start_ns <= dispatch.end_ns <= ahead.start_ns
                <= ahead.end_ns <= wait.start_ns <= step.end_ns)
    # the last step builds nothing ahead
    assert not [s for s in got if s.name == "train.input" and s.step == 2]


@pytest.mark.parametrize("num_steps,start_step", [(1, 0), (4, 0), (5, 2)])
def test_inputs_are_built_only_for_the_run_s_steps(tiny_train, num_steps,
                                                   start_step):
    from repro.runtime import Trainer

    ts, pipe, params, opt = tiny_train
    rec = Recording(pipe)
    tr = Trainer(ts, rec, None, log_every=1000)
    tr.run(params, opt.init(params), num_steps, start_step=start_step)
    assert rec.steps == list(range(start_step, num_steps))
    assert _counters(tr) == (num_steps - start_step - 1, 0)


def test_losses_equal_a_loop_that_builds_each_input_in_place(tiny_train):
    from repro.runtime import Trainer

    ts, pipe, params, opt = tiny_train
    _, _, hist = Trainer(ts, pipe, None, log_every=1000).run(
        params, opt.init(params), 5)
    assert hist["losses"] == _reference_losses(tiny_train, 5)


@pytest.mark.parametrize("start_step,raise_at", [(0, 3), (2, 4), (1, 1)])
def test_a_pipeline_error_surfaces_at_its_own_step(tiny_train, tmp_path,
                                                   start_step, raise_at):
    from repro.checkpoint import CheckpointManager
    from repro.runtime import Trainer

    ts, pipe, params, opt = tiny_train
    rec = Recording(pipe, raise_at=raise_at)
    ckpt = CheckpointManager(str(tmp_path), every=1, keep=0, blocking=True)
    tr = Trainer(ts, rec, ckpt, log_every=1000)
    with pytest.raises(Boom):
        tr.run(params, opt.init(params), 8, start_step=start_step)
    committed = raise_at - start_step
    # the steps before it waited, accounted and checkpointed
    assert tr.metrics.counter("steps_total").value == committed
    assert ckpt.latest() == (raise_at if committed else None)
    assert rec.steps == list(range(start_step, raise_at + 1))


def test_recovery_drops_the_kept_input_and_replays_each_step_s_own(
        tiny_train, tmp_path):
    from repro.checkpoint import CheckpointManager
    from repro.runtime import Trainer

    ts, pipe, params, opt = tiny_train
    rec = Recording(pipe)
    ckpt = CheckpointManager(str(tmp_path), every=2, keep=0, blocking=True)
    tr = Trainer(ts, rec, ckpt, log_every=1000, fail_at=frozenset({5}))
    _, _, hist = tr.run(params, opt.init(params), 8)
    # step 5's batch, built during step 4, is dropped: the run resumes
    # from the checkpoint at step 4, whose batch is built in place
    assert rec.steps == [0, 1, 2, 3, 4, 5, 4, 5, 6, 7]
    ref = _reference_losses(tiny_train, 8)
    assert hist["losses"] == ref[:5] + ref[4:]
    assert _counters(tr) == (7, 1)


@pytest.mark.parametrize("retry_at", [0, 2], ids=["built-in-place",
                                                  "built-ahead"])
def test_a_retried_step_reuses_its_input(tiny_train, retry_at):
    from repro.runtime import Trainer
    from repro.runtime.train_loop import TransientStepError

    ts, pipe, params, opt = tiny_train
    fired = []

    def inject(step):
        if step == retry_at and not fired:
            fired.append(step)
            raise TransientStepError(f"injected @ {step}")

    rec = Recording(pipe)
    tr = Trainer(ts, rec, None, log_every=1000, step_retries=1,
                 fault_injector=inject)
    _, _, hist = tr.run(params, opt.init(params), 4)
    assert fired == [retry_at]
    assert [e["kind"] for e in hist["events"]].count("retry") == 1
    assert rec.steps == [0, 1, 2, 3]
    assert hist["losses"] == _reference_losses(tiny_train, 4)
    assert _counters(tr) == (3, 0)

