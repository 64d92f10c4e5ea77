"""Per-architecture smoke tests (deliverable f).

For each of the 10 assigned architectures: instantiate the REDUCED
same-family config (configs.smoke_config), run one forward/train step on
CPU asserting output shapes + no NaNs, and check the serving path
(prefill -> paged/ring/recurrent decode) reproduces the one-shot forward
logits exactly.  The FULL configs are exercised by the dry-run only.
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import models
from repro.configs import get_config, list_archs, smoke_config
from repro.configs.base import MoEConfig
from repro.models.decode_init import empty_decode_state, load_prefill
from repro.models.layers import logits_apply
from repro.optim import adamw

ARCHS = list_archs()


def _batch(cfg, B, S, rng):
    batch = {
        "tokens": jnp.asarray(rng.randint(1, cfg.vocab, (B, S)), jnp.int32),
        "labels": jnp.asarray(rng.randint(1, cfg.vocab, (B, S)), jnp.int32),
    }
    if cfg.arch_kind == "vlm":
        batch["img_embeds"] = jnp.asarray(
            rng.randn(B, cfg.img_tokens, cfg.d_model), jnp.float32)
    if cfg.arch_kind == "encdec":
        batch["enc_embeds"] = jnp.asarray(
            rng.randn(B, cfg.enc_len, cfg.d_model), jnp.float32)
    return batch


def test_all_archs_registered():
    assert len(ARCHS) == 10


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_train_step(arch):
    cfg = smoke_config(get_config(arch))
    rng = np.random.RandomState(0)
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 32
    batch = _batch(cfg, B, S, rng)

    # forward: shapes + finite
    x = models.forward_train(cfg, params, batch["tokens"], extra=batch,
                             remat=False)
    S_out = S + (cfg.img_tokens if cfg.arch_kind == "vlm" else 0)
    assert x.shape == (B, S_out, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))

    # one full train step: loss finite, params updated, no NaNs
    opt = adamw.init(params)
    ocfg = adamw.AdamWConfig(warmup_steps=2, decay_steps=10)

    loss, grads = jax.value_and_grad(
        lambda p: models.loss_fn(cfg, p, batch))(params)
    assert bool(jnp.isfinite(loss))
    gn = adamw.global_norm(grads)
    assert bool(jnp.isfinite(gn)), f"{arch}: NaN gradients"
    new_params, _, _ = adamw.apply(ocfg, opt, grads, params)
    for leaf in jax.tree.leaves(new_params):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_consistency(arch):
    """Paged/ring/recurrent decode == one-shot forward, per arch."""
    cfg = smoke_config(get_config(arch))
    if cfg.moe:   # no-drop capacity so the comparison is exact
        cfg = dataclasses.replace(cfg, moe=MoEConfig(
            cfg.moe.num_experts, cfg.moe.top_k,
            float(cfg.moe.num_experts)))
    rng = np.random.RandomState(1)
    params = models.init_params(cfg, jax.random.PRNGKey(1))
    B, T0, T1, dp, bl = 4, 16, 4, 2, 2
    toks = jnp.asarray(rng.randint(1, cfg.vocab, (B, T0 + T1)), jnp.int32)
    extra = {k: v for k, v in _batch(cfg, B, T0, rng).items()
             if k not in ("tokens", "labels")}

    x_full = models.forward_train(cfg, params, toks, extra=extra, remat=False)
    if cfg.arch_kind == "vlm":
        x_full = x_full[:, cfg.img_tokens:]
    logits_full = logits_apply(cfg, params["embed"], x_full)

    batch = dict(extra)
    batch["tokens"] = toks[:, :T0]
    logits_p, caches = models.prefill(cfg, params, batch)
    plen = T0 + (cfg.img_tokens if cfg.arch_kind == "vlm" else 0)
    state = empty_decode_state(cfg, dp, bl, max_len=64)
    state = load_prefill(cfg, state, caches, plen)

    errs = [float(jnp.max(jnp.abs(logits_p - logits_full[:, T0 - 1])))]
    for t in range(T1 - 1):
        tok = toks[:, T0 + t].reshape(dp, bl)
        logits_d, state = models.decode_step(cfg, params, tok, state)
        ref = logits_full[:, T0 + t].reshape(dp, bl, -1)
        errs.append(float(jnp.max(jnp.abs(logits_d - ref))))
    assert max(errs) < 2e-3, f"{arch}: decode diverges {max(errs):.2e}"


@pytest.mark.parametrize("arch", ARCHS)
def test_param_counts_sane(arch):
    cfg = get_config(arch)
    n = models.count_params(cfg)
    na = models.count_active_params(cfg)
    assert n > 0 and 0 < na <= n
    if cfg.moe is None:
        assert n == na


def test_long_context_support_flags():
    """Sub-quadratic rule (DESIGN.md): SSM/hybrid/windowed run long_500k."""
    expected_long = {"mamba2-370m", "recurrentgemma-2b", "gemma3-27b",
                     "mixtral-8x7b"}
    got = {a for a in ARCHS if get_config(a).supports_long}
    assert got == expected_long


@pytest.mark.parametrize("shape,axes,fan", [
    ((4, 256, 8), ("layers", "embed", "mlp"), 256),
    ((4, 4, 16, 32), ("layers", "heads", "head_dim", "embed"), 64),
    ((4, 3, 128, 8), ("layers", "experts", "embed", "mlp"), 128),
], ids=["stacked", "attn_out", "experts"])
def test_init_scale_is_one_layers_fan_in(shape, axes, fan):
    """A stacked weight is initialised as one layer's matrix would be:
    std 1/sqrt(inputs summed), never scaled by the stack depth."""
    from repro.models.layers import ParamDef, init_tree
    w = init_tree(jax.random.PRNGKey(0),
                  {"w": ParamDef(shape, axes, jnp.float32)})["w"]
    np.testing.assert_allclose(float(jnp.std(w)), fan ** -0.5, rtol=0.1)
