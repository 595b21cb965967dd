"""Substrate tests: optimizer, schedule, data pipeline, checkpointing."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.data.pipeline import SyntheticTokens, batch_for
from repro.optim.adamw import adamw_init, adamw_update
from repro.optim.schedule import cosine_schedule
from repro import configs


def test_adamw_reduces_quadratic():
    """AdamW minimizes a simple quadratic."""
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = adamw_init(params)
    target = jnp.asarray([1.0, 2.0])
    for _ in range(400):
        grads = {"w": 2 * (params["w"] - target)}
        params, state, _ = adamw_update(params, grads, state, lr=0.05,
                                        weight_decay=0.0)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=0.05)


def test_adamw_bf16_moments_close_to_f32():
    params = {"w": jnp.ones((64,))}
    g = {"w": 0.1 * jnp.arange(64, dtype=jnp.float32)}
    s32 = adamw_init(params, jnp.float32)
    s16 = adamw_init(params, jnp.bfloat16)
    p32, s32, _ = adamw_update(params, g, s32, lr=1e-2)
    p16, s16, _ = adamw_update(params, g, s16, lr=1e-2)
    np.testing.assert_allclose(np.asarray(p32["w"]), np.asarray(p16["w"]),
                               atol=1e-3)
    assert s16.mu["w"].dtype == jnp.bfloat16


def test_grad_clipping():
    params = {"w": jnp.zeros((4,))}
    state = adamw_init(params)
    huge = {"w": 1e8 * jnp.ones((4,))}
    new_params, _, gnorm = adamw_update(params, huge, state, lr=1.0,
                                        grad_clip=1.0, weight_decay=0.0)
    assert float(gnorm) == pytest.approx(2e8, rel=1e-3)
    assert float(jnp.abs(new_params["w"]).max()) < 10.0


@given(step=st.integers(1, 10_000))
@settings(max_examples=30, deadline=None)
def test_schedule_bounds(step):
    lr = float(cosine_schedule(jnp.int32(step), peak_lr=3e-4, warmup=100,
                               total=10_000))
    assert 0.0 < lr <= 3e-4 + 1e-9


def test_schedule_warmup_then_decay():
    lrs = [float(cosine_schedule(jnp.int32(s), peak_lr=1.0, warmup=10, total=100))
           for s in [1, 5, 10, 50, 100]]
    assert lrs[0] < lrs[1] < lrs[2]           # warmup
    assert lrs[2] >= lrs[3] >= lrs[4]         # decay
    assert lrs[4] >= 0.1 - 1e-6               # min ratio floor


def test_synthetic_tokens_deterministic_and_structured():
    a = next(iter(SyntheticTokens(vocab=64, seq_len=32, batch=4, seed=7)))
    b = next(iter(SyntheticTokens(vocab=64, seq_len=32, batch=4, seed=7)))
    np.testing.assert_array_equal(np.asarray(a["tokens"]), np.asarray(b["tokens"]))
    np.testing.assert_array_equal(
        np.asarray(a["tokens"][:, 1:]), np.asarray(a["targets"][:, :-1]))


def test_batch_for_modalities():
    au = batch_for(configs.get("hubert-xlarge", reduced=True), 2, 64)
    assert au["embeds"].shape == (2, 64, 256) and au["mask"].dtype == bool
    vl = batch_for(configs.get("llava-next-34b", reduced=True), 2, 64)
    assert "patches" in vl and "tokens" in vl


def test_checkpoint_roundtrip():
    from repro.checkpoint import load_checkpoint, restore_latest, save_checkpoint

    tree = {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": [jnp.ones((2,), jnp.int32), {"c": jnp.asarray(2.5)}]}
    with tempfile.TemporaryDirectory() as td:
        save_checkpoint(td, tree, step=3)
        save_checkpoint(td, jax.tree_util.tree_map(lambda x: x * 2, tree), step=7)
        like = jax.tree_util.tree_map(lambda x: jnp.zeros_like(x), tree)
        restored, step = restore_latest(td, like)
        assert step == 7
        np.testing.assert_allclose(np.asarray(restored["a"]),
                                   2 * np.arange(12, dtype=np.float32).reshape(3, 4))


def test_checkpoint_shape_mismatch_rejected():
    from repro.checkpoint import load_checkpoint, save_checkpoint

    with tempfile.TemporaryDirectory() as td:
        fn = save_checkpoint(td, {"a": jnp.zeros((3,))}, step=0)
        with pytest.raises(AssertionError):
            load_checkpoint(fn, {"a": jnp.zeros((4,))})


def test_training_reduces_loss_tiny_model():
    """Integration: a tiny LM learns the Markov stream (fast version of
    examples/train_100m.py)."""
    import dataclasses

    from repro.models.transformer import Model
    from repro.train import trainer

    cfg = dataclasses.replace(
        configs.get("tinyllama-1.1b", reduced=True),
        vocab=128, d_model=128, n_heads=4, n_kv_heads=4, d_ff=256, n_layers=2)
    model = Model(cfg)
    data = iter(SyntheticTokens(vocab=cfg.vocab, seq_len=64, batch=8, seed=0))
    state, hist = trainer.train_loop(model, data, steps=60, peak_lr=3e-3,
                                     warmup=10, total=60, log_every=20)
    assert hist[-1]["loss"] < hist[0]["loss"] * 0.98
