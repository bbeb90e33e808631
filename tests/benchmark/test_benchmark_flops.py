"""The model-FLOP functions against counts worked by hand."""

import json
import os

import pytest

from bench_helpers import BENCH


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_bert_large_flops_by_hand():
    from benchmark.reference import bert

    # per token, multiply-adds: 24 layers x (4 x 1024^2 projections
    # + 2 x 1024 x 4096 FFN + 2 x 128 x 1024 attention)
    # = 24 x 12,845,056 = 308,281,344; MLM head 1024^2 + 1024 x 30522
    # = 32,303,104; together 340,584,448. Forward + backward = 3 x,
    # 2 FLOPs each; 64 x 128 = 8192 tokens.
    want = 6 * 340_584_448 * 8192
    assert bert.model_flops_per_step(64, config("bert-large")) == want
    assert want == pytest.approx(16.74e12, rel=1e-3)


def test_vgg16_flops_by_hand():
    from benchmark.reference import vgg

    # multiply-adds per image, configuration D at 224 x 224:
    convs = [(3, 64, 224), (64, 64, 224), (64, 128, 112), (128, 128, 112),
             (128, 256, 56), (256, 256, 56), (256, 256, 56),
             (256, 512, 28), (512, 512, 28), (512, 512, 28),
             (512, 512, 14), (512, 512, 14), (512, 512, 14)]
    macs = sum(9 * cin * cout * n * n for cin, cout, n in convs)
    assert macs == 15_346_630_656
    fc = 25088 * 4096 + 4096 * 4096 + 4096 * 1000
    assert fc == 123_633_664
    first = 9 * 3 * 64 * 224 * 224
    want = 2 * (3 * (macs + fc) - first) * 64
    assert vgg.model_flops_per_step(64, config("vgg16")) == want
    assert want == pytest.approx(5.93e12, rel=1e-2)


def test_parameter_counts_are_the_published_ones():
    import jax

    from benchmark.reference import bert, vgg

    def count(model, cfg):
        shapes = jax.eval_shape(lambda k: model.init_params(k, cfg),
                                jax.random.PRNGKey(0))
        return sum(x.size for x in jax.tree.leaves(shapes))

    assert count(vgg, config("vgg16")) == 138_357_544
    # 335.1 M with the MLM head and 512 positions (the paper rounds the
    # encoder to 340 M)
    assert count(bert, config("bert-large")) == 335_174_458
