"""Training substrate: optimizer, checkpoint/restart fault tolerance, compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.train import (
    OptConfig,
    adamw_update,
    clip_by_global_norm,
    crosspod_mean_int8,
    init_error_feedback,
    init_opt,
    latest_step,
    restore_checkpoint,
    restore_latest,
    save_checkpoint,
    shard_map_compat,
)


def test_adamw_moves_toward_minimum():
    params = {"w": jnp.asarray([5.0, -3.0])}
    opt = init_opt(params)
    cfg = OptConfig(lr=0.1, weight_decay=0.0, warmup_steps=1)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}  # d/dw w^2
        grads, _ = clip_by_global_norm(grads, cfg.clip_norm)
        params, opt = adamw_update(params, grads, opt, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.1


def test_clip_by_global_norm():
    grads = {"a": jnp.full((10,), 100.0)}
    clipped, gn = clip_by_global_norm(grads, 1.0)
    np.testing.assert_allclose(float(jnp.linalg.norm(clipped["a"])), 1.0, rtol=1e-5)
    assert float(gn) > 100


def test_checkpoint_roundtrip_and_atomicity(tmp_path):
    tree = {
        "params": {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)},
        "opt": {"m": jnp.ones((2, 3)), "step": jnp.int32(7)},
    }
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 5, tree)
    save_checkpoint(d, 10, tree)
    assert latest_step(d) == 10
    # a torn write (tmp dir) must not be picked up
    os.makedirs(os.path.join(d, "step_00000099.tmp"))
    assert latest_step(d) == 10
    restored, step = restore_latest(d, tree)
    assert step == 10
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]), np.asarray(tree["params"]["w"]))
    assert int(restored["opt"]["step"]) == 7


def test_int8_crosspod_compression_accuracy():
    """int8 all-gather mean over a 1-pod axis == identity within quant error,
    and error feedback carries the residual."""
    mesh = jax.make_mesh((1,), ("pod",))
    grads = {"w": jnp.asarray(np.random.default_rng(0).normal(0, 1, (64,)), jnp.float32)}
    err = init_error_feedback(grads)

    from jax.sharding import PartitionSpec as P

    f = shard_map_compat(
        lambda g, e: crosspod_mean_int8(g, e, "pod"),
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), grads), jax.tree.map(lambda _: P(), err)),
        out_specs=(jax.tree.map(lambda _: P(), grads), jax.tree.map(lambda _: P(), err)),
        axis_names={"pod"},
        check_vma=False,
    )
    mean, new_err = f(grads, err)
    # quantization error bounded by one step of the scale
    scale = float(jnp.abs(grads["w"]).max()) / 127.0
    np.testing.assert_allclose(np.asarray(mean["w"]), np.asarray(grads["w"]), atol=scale)
    # error feedback holds exactly the residual
    np.testing.assert_allclose(
        np.asarray(new_err["w"]),
        np.asarray(grads["w"] - mean["w"]),
        atol=1e-6,
    )


def test_grad_accumulation_matches_full_batch():
    from repro.configs import get_smoke_config
    from repro.models import init_params
    from repro.train.step import grads_and_loss

    cfg = get_smoke_config("yi_34b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32)}
    l1, g1 = grads_and_loss(params, cfg, batch, accum=1)
    l2, g2 = grads_and_loss(params, cfg, batch, accum=4)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)
    flat1 = jax.tree_util.tree_leaves(g1)
    flat2 = jax.tree_util.tree_leaves(g2)
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=2e-3, atol=2e-4
        )
