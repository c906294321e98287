"""Egress: each finished micro-batch reaches the host in one copy, and
every ticket gets a read-only row of it that is bit for bit the device
slice ``out[i:i+1]`` it used to get.  Uses the tiny two-stage server of
``test_serving``."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_serving import tiny_graph

from repro.core import Pipeline, PipelinePlan
from repro.serving import MicroBatch, PipelineServer, split_rows
from repro.serving.engine import build_stage_fns

BATCH = 4
CLASSES = 10


@pytest.fixture(scope="module")
def model():
    g = tiny_graph()
    params = g.init(jax.random.PRNGKey(0))
    n = len(g.descriptors())
    plan = PipelinePlan(
        Pipeline((("B", 4), ("s", 4))), (tuple(range(n // 2)), tuple(range(n // 2, n)))
    )
    rng = np.random.default_rng(0)
    images = [rng.standard_normal((16, 16, 3)).astype(np.float32) for _ in range(8)]
    return g, params, plan, images


def _recording_server(model, outs):
    """A server whose last stage also appends each output it returns to
    ``outs``; the flush timeout is so long that only a full batch or
    ``stop()`` flushes, so the batches are known in advance."""
    g, params, plan, _ = model

    def build(graph, plan):
        fns = build_stage_fns(graph, plan)
        last = fns[-1]

        def recorded(params, env):
            out = last(params, env)
            outs.append(out)
            return out

        return fns[:-1] + [recorded]

    srv = PipelineServer(
        g, params, plan, batch_size=BATCH, flush_timeout_s=60.0, stage_fn_builder=build
    )
    srv.warmup()
    outs.clear()
    return srv


def _bits(a):
    return np.asarray(a).view(np.uint32)


@pytest.mark.parametrize("valid", [BATCH, BATCH - 1, 1])
def test_split_rows_is_the_device_slices_bit_for_bit(valid):
    x = jax.random.normal(jax.random.PRNGKey(valid), (BATCH, CLASSES), jnp.float32)
    rows = split_rows(x, valid)
    assert len(rows) == valid
    for i, row in enumerate(rows):
        assert isinstance(row, np.ndarray)
        assert row.shape == (1, CLASSES) and row.dtype == np.float32
        assert not row.flags.writeable
        np.testing.assert_array_equal(_bits(row), _bits(x[i : i + 1]))


@pytest.mark.parametrize(
    "n_images", [BATCH, BATCH - 1, 2 * BATCH - 1], ids=["full", "partial", "full+partial"]
)
def test_results_are_rows_of_one_copy_a_batch(model, n_images):
    images = model[3][:n_images]
    outs = []
    srv = _recording_server(model, outs)
    srv.start()
    tickets = [srv.submit(x) for x in images]
    srv.stop()  # flushes the last, partial batch
    n_batches = -(-n_images // BATCH)
    assert len(outs) == n_batches
    outputs = [out for env in outs for out in env.values()]  # one tensor each
    for k, t in enumerate(tickets):
        res = t.result(timeout=0)
        assert isinstance(res, np.ndarray)
        assert res.shape == (1, CLASSES) and res.dtype == np.float32
        i = k % BATCH
        np.testing.assert_array_equal(_bits(res), _bits(outputs[k // BATCH][i : i + 1]))
        with pytest.raises(ValueError):
            res[0, 0] = 0.0  # read-only: every ticket shares its batch's copy
    snap = srv.metrics.snapshot()
    assert snap["egress_copies"] == n_batches == snap["stages"][-1]["batches"]
    assert snap["egress_rows"] == n_images == snap["completed"]


def test_counters_over_many_batches(model):
    g, params, plan, images = model
    with PipelineServer(g, params, plan, batch_size=BATCH, flush_timeout_s=0.005) as srv:
        srv.run(images)
        srv.run(images[:5])
        snap = srv.metrics.snapshot()
    assert snap["egress_copies"] == snap["stages"][-1]["batches"] >= 4
    assert snap["egress_rows"] == snap["completed"] == len(images) + 5


def test_late_duplicate_batch_is_suppressed_once(model):
    """A re-dispatched micro-batch whose tickets already resolved (a
    stalled worker's late result) reaches egress: it is copied like any
    batch, and every row is dropped through ``note_duplicate``."""
    images = model[3][:BATCH]
    outs = []
    srv = _recording_server(model, outs)
    with srv:
        tickets = [srv.submit(x) for x in images]
        first = [t.result(timeout=60) for t in tickets]
        before = srv.metrics.snapshot()
        (out,) = outs
        srv._qs[-1].put(MicroBatch(tuple(tickets), out, valid=BATCH, batch=10**6))
        deadline = time.perf_counter() + 60
        while srv.metrics.recovery.duplicates_suppressed < BATCH:
            assert time.perf_counter() < deadline
            time.sleep(0.005)
    after = srv.metrics.snapshot()  # egress has exited: nothing more comes
    assert after["recovery"]["duplicates_suppressed"] == BATCH
    assert after["egress_copies"] == before["egress_copies"] + 1
    assert after["completed"] == before["completed"] == BATCH
    assert after["egress_rows"] == before["egress_rows"] + BATCH
    assert all(t.result(timeout=0) is r for t, r in zip(tickets, first))
