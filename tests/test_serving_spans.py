"""The serving layer's spans, read back from a CPU profile of the tiny
two-stage server of ``test_serving``: every name of the vocabulary, one
dispatch per micro-batch, the ids that tie a request's and a batch's spans
together, client callbacks outside egress, leaves only, and stage programs
named ``stage_{k}``."""
import collections
import glob

import jax
import numpy as np
import pytest
from test_serving import tiny_graph

from repro.core import Pipeline, PipelinePlan
from repro.serving import PipelineServer
from repro.serving import metrics as sm

N_IMAGES = 10
STAGES = 2


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    g = tiny_graph()
    params = g.init(jax.random.PRNGKey(0))
    n = len(g.descriptors())
    plan = PipelinePlan(
        Pipeline((("B", 4), ("s", 4))), (tuple(range(n // 2)), tuple(range(n // 2, n)))
    )
    rng = np.random.default_rng(0)
    images = [rng.standard_normal((16, 16, 3)).astype(np.float32) for _ in range(N_IMAGES)]
    srv = PipelineServer(g, params, plan, batch_size=4, flush_timeout_s=0.005)
    srv.warmup()
    log_dir = tmp_path_factory.mktemp("profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        with srv:
            tickets = [srv.submit(x) for x in images]
            for t in tickets:
                t.result(timeout=120)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(log_dir / "plugins" / "profile" / "*" / "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    lines = [
        [(e.name, dict(e.stats), e.start_ns, e.duration_ns) for e in line.events]
        for plane in pd.planes if plane.name.startswith("/host:")
        for line in plane.lines
    ]
    return srv, tickets, lines


def _vocabulary():
    names = {sm.TO_DEVICE, sm.ADMIT, sm.GATHER, sm.STACK, sm.EGRESS, sm.RESOLVE}
    for k in range(STAGES):
        s = sm.stage_spans(k)
        names |= {s.dispatch, s.wait, s.handoff} | ({s.take} if k else set())
    return names


def _events(lines, name):
    return [e for line in lines for e in line if e[0] == name]


def test_every_name_of_the_vocabulary_appears(profiled):
    srv, _, lines = profiled
    assert len(srv.metrics.stages) == STAGES
    seen = {e[0] for line in lines for e in line}
    assert _vocabulary() <= seen
    assert sm.stage_spans(1) == ("serve.stage1.take", "serve.stage1.dispatch",
                                 "serve.stage1.wait", "serve.stage1.handoff")


def test_one_dispatch_and_wait_per_micro_batch(profiled):
    srv, _, lines = profiled
    for k, stage in enumerate(srv.metrics.stages):
        s = sm.stage_spans(k)
        assert stage.batches > 0
        assert len(_events(lines, s.dispatch)) == stage.batches
        assert len(_events(lines, s.wait)) == stage.batches
        assert len(_events(lines, s.handoff)) == stage.batches
    assert len(_events(lines, sm.STACK)) == len(_events(lines, sm.EGRESS))


def test_ids_tie_requests_and_batches_together(profiled):
    srv, tickets, lines = profiled
    ids = {t.id for t in tickets}
    for name in (sm.TO_DEVICE, sm.ADMIT):
        assert sorted(e[1]["ticket"] for e in _events(lines, name)) == sorted(ids)
    stacks = _events(lines, sm.STACK)
    assert sum(e[1]["n"] for e in stacks) == N_IMAGES
    batches = sorted(e[1]["batch"] for e in stacks)
    assert batches == sorted(set(batches))  # one number per micro-batch
    for k in range(STAGES):
        s = sm.stage_spans(k)
        for name in (s.dispatch, s.wait, s.handoff):
            assert sorted(e[1]["batch"] for e in _events(lines, name)) == batches
    assert sorted(e[1]["batch"] for e in _events(lines, sm.EGRESS)) == batches
    # each request resolves once, under the batch that carried it
    resolves = _events(lines, sm.RESOLVE)
    assert sorted(e[1]["ticket"] for e in resolves) == sorted(ids)
    per_batch = collections.Counter(e[1]["batch"] for e in resolves)
    assert per_batch == {e[1]["batch"]: e[1]["n"] for e in stacks}


def test_tickets_resolve_after_their_batchs_egress_closes(profiled):
    """Clients' done-callbacks run in ``serve.resolve``, which opens only
    once its batch's ``serve.egress`` has closed: egress holds no client
    code."""
    _, _, lines = profiled
    egress = {e[1]["batch"]: e[2] + e[3] for e in _events(lines, sm.EGRESS)}
    for _, stats, start, _ in _events(lines, sm.RESOLVE):
        assert start >= egress[stats["batch"]]


def test_a_refused_submit_takes_no_ticket_id():
    g = tiny_graph()
    params = g.init(jax.random.PRNGKey(0))
    n = len(g.descriptors())
    plan = PipelinePlan(Pipeline((("B", 4),)), (tuple(range(n)),))
    x = np.zeros((16, 16, 3), np.float32)
    with PipelineServer(g, params, plan, batch_size=2, flush_timeout_s=0.005) as srv:
        first = srv.submit(x)
        with pytest.raises(ValueError, match="ONE image"):
            srv.submit(np.zeros((2, 16, 16, 3), np.float32))
        second = srv.submit(x)
        assert second.id == first.id + 1
        first.result(timeout=120), second.result(timeout=120)


def test_spans_are_leaves(profiled):
    """On each thread's line, no span of the vocabulary opens inside another."""
    _, _, lines = profiled
    vocab = _vocabulary()
    for line in lines:
        mine = sorted((e[2], e[2] + e[3]) for e in line if e[0] in vocab)
        for (_, end), (start, _) in zip(mine, mine[1:]):
            assert start >= end


def test_stage_programs_are_named(profiled):
    _, _, lines = profiled
    calls = collections.Counter(
        e[0] for line in lines for e in line if e[0].startswith("PjitFunction(")
    )
    assert "PjitFunction(<lambda>)" not in calls
    assert {f"PjitFunction(stage_{k})" for k in range(STAGES)} <= set(calls)

