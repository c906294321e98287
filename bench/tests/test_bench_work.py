"""Work counts, weights layout and peaks of the benchmark's yardstick."""
import jax
import pytest

from bench import harness, models
from repro.cnn.models import resnet50, vgg16

CONFIGS = {"vgg16": vgg16, "resnet50": resnet50}


def _cfg(name):
    return harness.load_json(harness.BENCH_DIR, "configs", name + ".json")


@pytest.mark.parametrize("name,gflop", [("vgg16", 30.94), ("resnet50", 7.72)])
def test_flops_per_image(name, gflop):
    assert models.flops_per_image(_cfg(name)) / 1e9 == pytest.approx(gflop, abs=5e-3)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_flops_match_program_descriptors(name):
    """Layer by layer, the benchmark's own shapes give the FLOPs the
    program's descriptors give (the counts are independent copies)."""
    ours = [(l.name, models.flops(l)) for l in models.layers(_cfg(name))]
    theirs = [(d.name, d.gemm_dims().flops) for d in CONFIGS[name]().descriptors()]
    assert sorted(ours) == sorted(theirs)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_params_have_the_programs_layout(name):
    """The weights the benchmark makes have the names and shapes the
    program's parameter dict has, so serve() takes them as they are."""
    graph = CONFIGS[name]()
    theirs = jax.eval_shape(graph.init, jax.random.PRNGKey(0))
    ours = models.param_shapes(_cfg(name))
    assert {k: {p: tuple(a.shape) for p, a in v.items()} for k, v in theirs.items()} == ours


def test_min_bytes_and_roofline():
    l = models.Layer("c", "conv", (4, 4), 2, 3, 3, 1, 1)
    # input 4*4*2, weights 3*3*2*3, bias 3, output 4*4*3, f32, batch 1
    assert models.min_bytes(l, 1, 4) == 4 * (32 + 54 + 3 + 48)
    assert models.flops(l, 2) == 2 * 2 * 16 * 9 * 2 * 3
    peak = {"flops_per_s": 1e3, "hbm_bytes_per_s": 1e9}
    assert models.roofline_seconds(l, 2, 4, peak) == pytest.approx(models.flops(l, 2) / 1e3)


def test_peaks_lookup():
    assert harness.peak_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peak_for("TPU v9 imaginary")


def test_seed_words_separate_large_seeds():
    seeds = [0, 5, 2**32 + 5, 2**33 + 5, 2**31 + 11, -1, 10**30]
    words = {models.seed_words(s, "weights") for s in seeds}
    assert len(words) == len(seeds)
    assert models.seed_words(7, "weights") == models.seed_words(7, "weights")
    assert models.seed_words(7, "weights") != models.seed_words(7, "images")
