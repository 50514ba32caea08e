"""Per-architecture smoke tests: REDUCED variant of each assigned arch runs
one forward + one train step on CPU; output shapes + no NaNs.  (The FULL
configs are exercised via the dry-run only — ShapeDtypeStruct, no alloc.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import transformer as T
from repro import optim

B, S = 2, 64


def _batch(cfg, key, seq=S):
    if cfg.frontend == "vision":
        s_text = seq - cfg.n_patches
        return {
            "tokens": jax.random.randint(key, (B, s_text), 0, cfg.vocab_size),
            "patch_embeds": jax.random.normal(key, (B, cfg.n_patches, cfg.d_model)),
            "labels": jax.random.randint(key, (B, s_text), 0, cfg.vocab_size),
        }
    if cfg.frontend == "audio":
        return {"codes": jax.random.randint(
            key, (B, cfg.n_codebooks, seq), 0, cfg.vocab_size)}
    return {
        "tokens": jax.random.randint(key, (B, seq), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (B, seq), 0, cfg.vocab_size),
    }


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_forward_shapes_and_train_step(arch):
    cfg = get_config(arch).reduced()
    assert cfg.d_model <= 256 and cfg.n_layers <= (
        len(cfg.lead) + len(cfg.pattern) + len(cfg.tail))
    if cfg.moe:
        assert cfg.moe.n_experts <= 4
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    batch = _batch(cfg, key)

    logits, aux, _ = T.forward(params, cfg, batch, "train")
    exp_s = S if cfg.frontend != "vision" else S
    if cfg.frontend == "audio":
        assert logits.shape == (B, S, cfg.n_codebooks, cfg.padded_vocab)
    else:
        assert logits.shape == (B, exp_s, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits).any())

    # one train step: loss finite and params move
    opt = optim.adam(1e-3)
    state = opt.init(params)

    def loss_fn(p):
        return T.loss_fn(p, cfg, batch)[0]

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    upd, state = opt.update(grads, state, params)
    new_params = optim.apply_updates(params, upd)
    loss2 = loss_fn(new_params)
    assert np.isfinite(float(loss2))
    moved = jax.tree.reduce(
        lambda a, l: a + float(jnp.sum(jnp.abs(l))),
        jax.tree.map(lambda a, b: a - b, new_params, params), 0.0)
    assert moved > 0.0


@pytest.mark.parametrize("arch", ["smollm-360m", "mamba2-780m",
                                  "recurrentgemma-2b", "gemma3-4b",
                                  "deepseek-v2-lite-16b", "musicgen-large"])
def test_reduced_decode_matches_train(arch):
    """Prefill + one-token decode must reproduce the teacher-forced logits."""
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(42)
    params = T.init_params(key, cfg)
    s, cap = 33, 48
    batch = _batch(cfg, key, seq=s)
    full, _, _ = T.forward(params, cfg, batch, "train")
    if cfg.frontend == "audio":
        pre = {"codes": batch["codes"][:, :, :s - 1]}
        dec = {"codes": batch["codes"][:, :, s - 1:]}
    else:
        pre = {"tokens": batch["tokens"][:, :s - 1]}
        dec = {"tokens": batch["tokens"][:, s - 1:]}
    _, _, caches = T.forward(params, cfg, pre, "prefill", capacity=cap)
    dec_logits, _, _ = T.forward(params, cfg, dec, "decode", caches=caches,
                                 capacity=cap, pos_offset=s - 1)
    np.testing.assert_allclose(np.asarray(dec_logits[:, 0]),
                               np.asarray(full[:, -1]), rtol=2e-4, atol=2e-4)
