"""The plain reference against the program's own graph, and the control.

On the CPU at 64x64 images (every width as published): the reference
must agree with ``Graph.apply`` on the ``"xla"`` route, and its int8
control must read above the configuration's limit, which the program's
answers read far below.  Storing every layer's output in bfloat16 reads
about what the chip's own bfloat16 operands read, which is why the
configurations state bfloat16 as their precision and int8 is the control.
"""
import numpy as np
import pytest

from bench import harness, models, reference
from repro.cnn.models import resnet50, vgg16

PROGRAM = {"vgg16": vgg16, "resnet50": resnet50}
HW = 64


def _small(name):
    cfg = harness.load_json(harness.BENCH_DIR, "configs", name + ".json")
    return dict(cfg, input_shape=[HW, HW, 3])


@pytest.fixture(scope="module", params=sorted(PROGRAM))
def small(request):
    """(cfg, params, images, reference answers, program answers)."""
    import jax

    name = request.param
    cfg = _small(name)
    params = models.make_params(cfg, 2**31 + 5)
    images = models.make_images(cfg, 2**31 + 5, 4)
    graph = PROGRAM[name]()
    graph.input_shape = (HW, HW, 3)
    prog = np.asarray(jax.jit(lambda p, x: graph.apply(p, x, backend="xla"))(params, images))
    ref = reference.run_blocks(cfg, params, images, block=2)
    return cfg, params, images, ref, prog


def test_reference_matches_program_graph(small):
    cfg, params, images, ref, prog = small
    assert ref.shape == prog.shape == (4, 1000)
    assert np.allclose(ref.sum(axis=1), 1.0, atol=1e-5)
    assert harness.logit_gap(prog, ref).max() < 1e-4


def test_softmax_not_saturated(small):
    """The weights keep the answers informative: no class takes nearly all
    of the probability, so a wrong logit anywhere moves the answer."""
    ref = small[3]
    assert ref.max() < 0.5


class _Bf16Operands(reference.Ops):
    """The arithmetic of the TPU's default matmul precision: float32 kept
    between layers, every conv and fc operand rounded to bfloat16."""

    def _operands(self, x, w, x_axes, w_axes):
        return reference.Ops("bf16").store(x), reference.Ops("bf16").store(w)


def test_bf16_storage_reads_like_bf16_operands(small):
    import jax

    cfg, params, images, ref, prog = small
    fam = models.family(cfg["family"])

    def gap(ops):
        out = jax.jit(lambda p, x: jax.nn.softmax(fam.forward(cfg, p, x, ops), axis=-1))(
            params, images)
        return harness.logit_gap(np.asarray(out), ref).max()

    operands, storage = gap(_Bf16Operands()), gap(reference.Ops("bf16"))
    limit = cfg["check"]["limit"]
    assert 1e-3 < operands < limit and 1e-3 < storage < limit
    assert storage < 3 * operands  # no limit could hold one and fail the other


def test_unknown_precision_is_refused():
    with pytest.raises(ValueError):
        reference.Ops("fp4")


def test_int8_control_fails_the_limit(small):
    cfg, params, images, ref, prog = small
    ctl = reference.run_blocks(cfg, params, images, block=2, quant="int8")
    limit = cfg["check"]["limit"]
    assert harness.logit_gap(ctl, ref).max() > limit
    assert harness.logit_gap(prog, ref).max() < limit / 10
