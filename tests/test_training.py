"""Training substrate: learning, LoRA-freeze semantics, checkpoint
roundtrip, optimizer math."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data import DataConfig, SyntheticLM
from repro.lora.adapter import init_adapter
from repro.models import model as M
from repro.training import (AdamWConfig, adamw_init, adamw_update,
                            global_norm, load_checkpoint,
                            make_lora_train_step, make_train_step,
                            save_checkpoint)


def test_loss_decreases():
    cfg = get_smoke_config("stablelm-1.6b")
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    opt = adamw_init(params)
    oc = AdamWConfig(lr=3e-3, warmup_steps=5, total_steps=100,
                     weight_decay=0.01)
    step = jax.jit(make_train_step(cfg, oc))
    it = SyntheticLM(DataConfig(cfg.vocab_size, 64, 8)).batches()
    losses = []
    for _ in range(40):
        t, l = next(it)
        params, opt, m = step(params, opt,
                              {"tokens": jnp.asarray(t),
                               "labels": jnp.asarray(l)})
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5


def test_lora_training_freezes_base():
    cfg = get_smoke_config("llama-7b-paper")
    key = jax.random.PRNGKey(1)
    params = M.init_params(cfg, key)
    adapter = init_adapter(cfg, 8, key)
    opt = adamw_init(adapter)
    oc = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=20)
    step = jax.jit(make_lora_train_step(cfg, oc))
    it = SyntheticLM(DataConfig(cfg.vocab_size, 32, 4)).batches()
    base_before = jax.tree.map(lambda x: x.copy(), params)
    a0 = jax.tree.map(lambda x: x.copy(), adapter)
    for _ in range(3):
        t, l = next(it)
        adapter, opt, m = step(adapter, opt, params,
                               {"tokens": jnp.asarray(t),
                                "labels": jnp.asarray(l)})
    # base unchanged, adapter B matrices moved off zero
    for a, b in zip(jax.tree.leaves(base_before), jax.tree.leaves(params)):
        assert bool(jnp.array_equal(a, b))
    moved = any(bool(jnp.any(a != b)) for a, b in
                zip(jax.tree.leaves(a0), jax.tree.leaves(adapter)))
    assert moved


def test_adamw_clipping():
    p = {"w": jnp.ones((4,))}
    g = {"w": jnp.full((4,), 100.0)}
    opt = adamw_init(p)
    cfg = AdamWConfig(lr=1e-2, clip_norm=1.0, warmup_steps=0,
                      weight_decay=0.0)
    p2, opt2, m = adamw_update(cfg, g, opt, p)
    assert float(m["grad_norm"]) == pytest.approx(200.0)
    assert bool(jnp.all(p2["w"] < p["w"]))
    assert int(opt2["step"]) == 1


def test_trainable_mask_freezes():
    p = {"a": jnp.ones((2,)), "b": jnp.ones((2,))}
    g = {"a": jnp.ones((2,)), "b": jnp.ones((2,))}
    opt = adamw_init(p)
    cfg = AdamWConfig(lr=1e-2, warmup_steps=0)
    mask = {"a": True, "b": False}
    p2, _, _ = adamw_update(cfg, g, opt, p, trainable_mask=mask)
    assert bool(jnp.all(p2["a"] != p["a"]))
    assert bool(jnp.array_equal(p2["b"], p["b"]))


def test_checkpoint_roundtrip(tmp_path):
    cfg = get_smoke_config("internlm2-1.8b")
    params = M.init_params(cfg, jax.random.PRNGKey(2))
    opt = adamw_init(params)
    state = {"params": params, "opt": opt}
    path = os.path.join(tmp_path, "ck.msgpack")
    save_checkpoint(path, state)
    restored = load_checkpoint(path, state)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_data_pipeline_deterministic():
    c = DataConfig(vocab_size=128, seq_len=16, batch_size=2, seed=3)
    a1 = next(SyntheticLM(c).batches())
    a2 = next(SyntheticLM(c).batches())
    np.testing.assert_array_equal(a1[0], a2[0])
    # labels are next-token shifted
    np.testing.assert_array_equal(a1[0][:, 1:], a1[1][:, :-1])


def test_moe_training_loss_carries_the_balance_term():
    """DeepSeek-V2-Lite holds its whole expert layer when trained: the
    loss is the cross entropy plus the Switch load-balance term of every
    MoE layer, and that term is not zero."""
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    assert not cfg.moe.holds_share
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    t, l = next(SyntheticLM(DataConfig(cfg.vocab_size, 16, 2)).batches())
    batch = {"tokens": jnp.asarray(t), "labels": jnp.asarray(l)}
    _, aux = M.forward(cfg, params, batch["tokens"], remat=False)
    # one term per MoE layer, E * sum(mean prob * top-1 share): about 1
    # for a router near uniform
    assert float(aux) > 0.5 * (cfg.n_layers - cfg.n_dense_layers)
    with_aux = M.loss_fn(cfg, params, batch, remat=False)
    without = M.loss_fn(cfg, params, batch, remat=False, aux_coef=0.0)
    np.testing.assert_allclose(float(with_aux - without), 0.01 * float(aux),
                               rtol=1e-4)
