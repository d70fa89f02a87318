"""Smoke tests for the example apps (≙ the reference's example/ tree:
capability demos proving train + import + serve compose)."""

import numpy as np

from bigdl_tpu.utils import random as rnd


def test_languagemodel_example():
    from bigdl_tpu.example.languagemodel.train import main

    rnd.set_seed(1)
    trained = main(["--vocab", "20", "--num-steps", "8", "--batch-size", "8",
                    "--max-epoch", "1", "--hidden", "16", "--embed", "8"])
    assert trained is not None


def test_textclassification_example():
    from bigdl_tpu.example.textclassification.train import main

    rnd.set_seed(2)
    _, acc = main(["--class-num", "3", "--seq-len", "16", "--embed-dim", "8",
                   "--batch-size", "16", "--max-epoch", "4",
                   "--samples", "96"])
    assert acc > 0.6, acc


def test_imageclassification_example(tmp_path):
    from bigdl_tpu import nn
    from bigdl_tpu.example.imageclassification.predict import main
    from bigdl_tpu.utils.file import save_module

    rnd.set_seed(3)
    model = (nn.Sequential()
             .add(nn.SpatialConvolution(3, 4, 3, 3, 1, 1, 1, 1))
             .add(nn.ReLU())
             .add(nn.SpatialAveragePooling(32, 32, global_pooling=True))
             .add(nn.View(4)).add(nn.Linear(4, 3)).add(nn.SoftMax()))
    mpath = str(tmp_path / "m.bigdl")
    save_module(model, mpath)
    rng = np.random.RandomState(0)
    paths = []
    for i in range(3):
        p = str(tmp_path / f"img{i}.npy")
        np.save(p, rng.rand(16, 16, 3).astype(np.float32))
        paths.append(p)
    preds = main(["--model", mpath, "--model-type", "bigdl",
                  "--images", str(tmp_path / "img*.npy")])
    assert len(preds) == 3 and all(1 <= c <= 3 for c in preds)


def test_udfpredictor_example():
    from bigdl_tpu.example.udfpredictor.predict import main

    df = main(["--rows", "16"])
    assert set(df["prediction"].unique()) <= {1, 2}


def test_tree_lstm_sentiment_example():
    from bigdl_tpu.example.treeLSTMSentiment.train import main

    rnd.set_seed(5)
    loss, acc = main(["--samples", "16", "--leaves", "2", "--embed-dim", "4",
                      "--hidden", "8", "--epochs", "15", "--lr", "0.3"])
    assert acc >= 0.7, acc


def test_mlpipeline_example():
    from bigdl_tpu.example.MLPipeline.train import main

    acc = main(["--rows", "96", "--epochs", "20"])
    assert acc > 0.7, acc


def test_longcontext_example():
    # tiny config: remat + MoE + 2-way sequence parallel on the CPU mesh
    from bigdl_tpu.example.longcontext import train as lc

    losses = lc.main(["--seq-len", "32", "--batch", "2", "--layers", "1",
                      "--embed", "16", "--heads", "2", "--vocab", "32",
                      "--steps", "3", "--experts", "2",
                      "--seq-parallel", "2"])
    assert len(losses) == 3
    assert losses[-1] < losses[0]


def test_widedeep_example_feature_columns_learn():
    """Wide&Deep over BucketizedCol/HashBucket/CrossCol/IndicatorCol: the
    crossed wide feature must lift accuracy well above the majority class."""
    from bigdl_tpu.example.widedeep.train import main

    rnd.set_seed(3)
    _, acc, base = main(["--samples", "1024", "--max-epoch", "8"])
    assert acc > base + 0.08, (acc, base)


def test_serving_example(monkeypatch, tmp_path):
    """The serving walkthrough (one-dispatch generate/beam, ragged,
    int8-draft speculation, concurrent GenerationService) runs end to
    end and returns the concurrently-served rows (exactly prompt + n
    tokens each — the service contract)."""
    from bigdl_tpu.example.serving.serve import main

    # the example enables the persistent compile cache: place it outside
    # the checkout, so tier-1 writes no CPU entries the chip tool would copy
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    rows = main(["--tokens", "8", "--vocab", "64"])
    assert len(rows) == 4
    for row, (t0, want_n) in zip(rows, ((5, 8), (9, 4), (12, 8), (7, 4))):
        assert row is not None and row.ndim == 1
        assert row.shape[0] == t0 + want_n
