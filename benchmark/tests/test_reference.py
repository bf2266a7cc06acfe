"""The plain references against the program's models, at tiny sizes on the
CPU in float32: same parameters, same batch, loss and gradient agree to
float32 rounding. (On the chip the comparison runs at the published widths,
in bf16, after the window: ``harness/checks.reference``.)"""

import json
import os

import numpy as np
import pytest

from benchmark.harness import checks, runner

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = {
    "bert_base_mlm": dict(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                          num_attention_heads=4, intermediate_size=128,
                          max_position_embeddings=64, compute_dtype="float32"),
    "resnet50_imagenet": dict(width=8, stage_sizes=[2, 1, 1, 1],
                              num_classes=10, image_size=32,
                              compute_dtype="float32"),
}


def batch_for(name, rng):
    if name == "bert_base_mlm":
        b, s, p = 3, 48, 7
        mask = np.ones((b, s), np.int32)
        mask[1, 40:] = 0                       # a padded tail is masked out
        weights = np.ones((b, p), np.float32)
        weights[2, 5:] = 0.0                   # unused prediction slots
        return {"input_ids": rng.integers(0, 512, (b, s)).astype(np.int32),
                "attention_mask": mask,
                "mlm_positions": rng.integers(0, 40, (b, p)).astype(np.int32),
                "mlm_labels": rng.integers(0, 512, (b, p)).astype(np.int32),
                "mlm_weights": weights}, {"seq_len": s, "max_predictions": p}
    return {"image": rng.standard_normal((6, 32, 32, 3)).astype(np.float32),
            "label": rng.integers(0, 10, (6,)).astype(np.int32)}, {}


@pytest.mark.parametrize("name", sorted(TINY))
def test_reference_agrees_with_the_programs_model(name):
    import jax

    from distributeddeeplearningspark_tpu.train.state import TrainState

    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = {**json.load(f), **TINY[name]}
    cfg["check"] = {**cfg["check"], "loss_abs_tol": 1e-5, "grad_rel_tol": 1e-4}
    config_mod = runner.load_module(os.path.join(BENCH, "configs", name + ".py"))
    ref_mod = runner.load_module(os.path.join(BENCH, "reference",
                                              cfg["reference"] + ".py"))
    batch, traffic = batch_for(name, np.random.default_rng(0))
    built = config_mod.build(cfg, traffic)
    key = jax.random.PRNGKey(1)
    variables = dict(built["model"].init({"params": key, "dropout": key},
                                         batch, train=False))
    params = variables.pop("params")
    # zero-initialised scales (the last batch norm of each block) would make
    # every block an identity: perturb everything so each path carries signal
    leaves, treedef = jax.tree.flatten(params)
    rng = np.random.default_rng(2)
    params = jax.tree.unflatten(treedef, [
        np.asarray(x) + 0.1 * rng.standard_normal(x.shape).astype(np.float32)
        for x in leaves])
    state = TrainState.create(params=params, opt_state=(), mutable=variables,
                              rng=key, embed_state={})
    failures, facts = checks.reference(config_mod, ref_mod, cfg, built, state,
                                       batch)
    assert failures == [], facts
    assert facts["grad_norm_reference"] > 0
    assert facts["examples"] == next(iter(batch.values())).shape[0]


def test_a_wrong_model_fails_the_check():
    """The tolerance bites: the same BERT with one layer's output scaled by
    1.05 is outside a tolerance of 1e-4."""
    import jax

    from distributeddeeplearningspark_tpu.train.state import TrainState

    name = "bert_base_mlm"
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = {**json.load(f), **TINY[name]}
    cfg["check"] = {**cfg["check"], "loss_abs_tol": 1e-5, "grad_rel_tol": 1e-4}
    config_mod = runner.load_module(os.path.join(BENCH, "configs", name + ".py"))
    ref_mod = runner.load_module(os.path.join(BENCH, "reference", name + ".py"))
    batch, traffic = batch_for(name, np.random.default_rng(0))
    built = config_mod.build(cfg, traffic)
    key = jax.random.PRNGKey(1)
    params = built["model"].init({"params": key, "dropout": key}, batch,
                                 train=False)["params"]
    state = TrainState.create(params=params, opt_state=(), mutable={},
                              rng=key, embed_state={})

    class Skewed:
        @staticmethod
        def loss(p, mutable, b, c):
            p = jax.tree.map(lambda x: x, p)
            enc = dict(p["encoder"])
            layer = dict(enc["layer_1"])
            layer["mlp_out"] = {"kernel": layer["mlp_out"]["kernel"] * 1.05,
                                "bias": layer["mlp_out"]["bias"]}
            enc["layer_1"] = layer
            return ref_mod.loss({**p, "encoder": enc}, mutable, b, c)

    failures, _ = checks.reference(config_mod, Skewed, cfg, built, state, batch)
    assert any("gradient differs" in f for f in failures)
