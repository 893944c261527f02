"""``repro.spans``: host spans that record only while a profiler runs, the
JAX compile phases logged under them, and the training engine's spans."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro import spans
from repro.configs.base import (ExpansionConfig, ModelConfig, OptimizerConfig,
                                ScheduleConfig, TrainConfig)
from repro.data.synthetic import DataConfig, SyntheticLM
from repro.train.engine import ProgressiveTrainer

CFG = ModelConfig(name="tspan", family="dense", num_layers=2, d_model=32,
                  num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64,
                  max_seq_len=16)
STEPS = 6
JAX_PHASES = {"jax.trace", "jax.lower", "jax.compile"}


@pytest.fixture
def log_dir(tmp_path):
    spans.clear()
    yield str(tmp_path)
    spans.clear()


def _names(log):
    return [r[0] for r in log]


def test_nothing_recorded_without_a_profiler(log_dir):
    with spans.span("test.outer") as outer:
        with spans.span("test.inner") as inner:
            jax.jit(lambda x: x * 3.0)(jnp.ones(3)).block_until_ready()
    assert spans.log() == []
    assert outer.seconds >= inner.seconds > 0


def test_nested_spans_keep_parents_and_reach_the_trace(log_dir):
    with jax.profiler.trace(log_dir):
        with spans.span("test.outer") as outer:
            with spans.span("test.inner"):
                pass
            with spans.span("test.sibling"):
                pass
    log = spans.log()
    assert _names(log) == ["test.outer", "test.inner", "test.sibling"]
    assert [r[1] for r in log] == [None, 0, 0]
    assert all(r[2] <= r[3] for r in log)
    assert log[0][2] <= log[1][2] and log[2][3] <= log[0][3]
    assert outer.seconds == (log[0][3] - log[0][2]) / 1e9
    from jax.profiler import ProfileData
    pb = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    host = {e.name for p in ProfileData.from_file(pb[0]).planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events}
    assert {"test.outer", "test.inner", "test.sibling"} <= host


def test_jit_first_call_logs_its_compile_phases(log_dir):
    f = jax.jit(lambda x: jnp.sin(x) * 2.0)
    x = jnp.ones(5)
    with jax.profiler.trace(log_dir):
        with spans.span("test.first"):
            f(x).block_until_ready()
        with spans.span("test.second"):
            f(x).block_until_ready()
    log = spans.log()
    first, second = _names(log).index("test.first"), \
        _names(log).index("test.second")
    under = {r[0] for r in log if r[1] == first}
    assert under == JAX_PHASES
    assert not [r for r in log if r[1] == second]
    compiles = [r for r in log if r[0] == "jax.compile"]
    assert all(isinstance(r[4]["cache_hit"], bool) for r in compiles)
    assert any("<lambda>" in r[4]["fun_name"] for r in compiles)
    for r in log:
        if r[1] == first:       # children lie inside their parent
            assert log[first][2] <= r[2] <= r[3] <= log[first][3]


def test_span_opened_before_the_profiler_is_not_recorded(log_dir):
    with spans.span("test.before") as before:
        with jax.profiler.trace(log_dir):
            with spans.span("test.after"):
                pass
    assert [(r[0], r[1]) for r in spans.log()] == [("test.after", None)]
    assert before.seconds > 0


class _ProfileFrom:
    """The trainer's data, starting the profiler as step ``at`` is fetched
    (as the benchmark opens its window inside a step)."""

    def __init__(self, at, log_dir):
        self.data = SyntheticLM(DataConfig(vocab_size=CFG.vocab_size,
                                           seq_len=16, global_batch=4))
        self.at, self.log_dir = at, log_dir

    def batch(self, step, shard=0, num_shards=1):
        if step == self.at:
            jax.profiler.start_trace(self.log_dir)
        return self.data.batch(step, shard, num_shards)


@pytest.mark.parametrize("grows", [False, True])
def test_trainer_compiles_only_where_the_depth_is_new(grows, log_dir):
    """Fixed depth, profiled from the start: JAX compiles only under the
    first ``train.dispatch``.  Progressive, profiled from the step after
    the first: only under ``train.expand`` and the first deep
    ``train.dispatch``.  The straggler monitor's step times are the
    dispatch spans' seconds."""
    exps = (ExpansionConfig(at_frac=0.5, target_layers=2,
                            init="random"),) if grows else ()
    tc = TrainConfig(total_steps=STEPS, seq_len=16, global_batch=4,
                     source_layers=1 if grows else 2, expansions=exps,
                     optimizer=OptimizerConfig(name="adamw",
                                               learning_rate=1e-3),
                     schedule=ScheduleConfig(name="constant"),
                     eval_every=10_000, log_every=1)
    data = _ProfileFrom(1 if grows else 0, log_dir)
    trainer = ProgressiveTrainer(CFG, tc, data=data, eval_batches=[],
                                 log_fn=lambda *a: None)
    try:
        res = trainer.run()
    finally:
        jax.profiler.stop_trace()
    log = spans.log()
    names = _names(log)
    dispatch = [i for i, n in enumerate(names) if n == "train.dispatch"]
    # the fetch of step ``at`` opened before the profiler did
    assert names.count("train.fetch") + 1 == len(dispatch) == STEPS - data.at
    assert names.count("train.expand") == int(grows)
    if grows:
        expand = names.index("train.expand")
        owners = {expand, next(i for i in dispatch if i > expand)}
    else:
        owners = {dispatch[0]}
    phases = [r for r in log if r[0] in JAX_PHASES]
    assert phases and {r[1] for r in phases} == owners
    assert {r[0] for r in phases} == JAX_PHASES
    assert res.history["step_time"][data.at:] == \
        [(log[i][3] - log[i][2]) / 1e9 for i in dispatch]


def test_last_seconds_always_and_notes_only_while_logged(log_dir):
    with spans.span("test.noted") as s:
        s.note(k=1)
    assert spans.log() == [] and spans.last("test.noted") == s.seconds
    with jax.profiler.trace(log_dir):
        with spans.span("test.noted") as t:
            t.note(k=2)
    assert spans.log()[-1][0] == "test.noted" and spans.log()[-1][4] == \
        {"k": 2}
    assert spans.last("test.noted") == t.seconds
    assert spans.last("test.never") is None


@pytest.mark.parametrize("profiled", [False, True])
def test_trainer_init_span_once_a_run_with_its_state_bytes(profiled,
                                                           log_dir):
    """``train.init`` covers the creation of the weights and the optimizer
    state: logged once a run, with the bytes of that state on the mesh's
    first device, only while a profiler runs; its seconds always."""
    tc = TrainConfig(total_steps=2, seq_len=16, global_batch=4,
                     source_layers=2,
                     optimizer=OptimizerConfig(name="adamw",
                                               learning_rate=1e-3),
                     schedule=ScheduleConfig(name="constant"),
                     eval_every=10_000, log_every=1)
    data = SyntheticLM(DataConfig(vocab_size=CFG.vocab_size, seq_len=16,
                                  global_batch=4))
    trainer = ProgressiveTrainer(CFG, tc, data=data, eval_batches=[],
                                 log_fn=lambda *a: None)
    if profiled:
        jax.profiler.start_trace(log_dir)
    try:
        res = trainer.run()
    finally:
        if profiled:
            jax.profiler.stop_trace()
    inits = [r for r in spans.log() if r[0] == "train.init"]
    assert spans.last("train.init") > 0
    if not profiled:
        assert inits == []
        return
    assert len(inits) == 1 and inits[0][1] is None
    state = jax.tree.leaves((res.params, res.opt_state))
    assert inits[0][4] == {"state_bytes": sum(x.nbytes for x in state)}
    assert (inits[0][3] - inits[0][2]) / 1e9 == pytest.approx(
        spans.last("train.init"))
