"""Train / serve step functions (the "learner" compute of the platform).

``make_train_step`` builds a pure (state, batch) -> (state, metrics) function:
grad accumulation over microbatches (scan), optional int8 gradient
compression with error feedback, global-norm clip, AdamW.  It is jit-able
and pjit-able; shardings come from the abstract param tree + rule table.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.dist.compression import (
    CompressionConfig,
    compress_grads,
    init_error_buffers,
    resolve_compression,
)
from repro.models.layers import Ctx
from repro.models.model import forward
from repro.models.params import init_params
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update

Tree = Dict[str, Any]
TrainState = Dict[str, Any]       # {params, opt, step, [err]}


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------
def loss_fn(
    cfg: ModelConfig,
    params: Tree,
    batch: Tree,
    ctx: Ctx,
    remat_policy: str = "none",
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits, _, aux = forward(cfg, params, batch, ctx, mode="train",
                             remat_policy=remat_policy)
    labels = batch["labels"]
    valid = labels >= 0
    lab = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
    ce = -(ll * valid).sum() / jnp.maximum(valid.sum(), 1)
    return ce + aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------
def _compression_for(run: RunConfig, flag) -> Optional[CompressionConfig]:
    """The explicit argument wins (None = unspecified, fall back to the
    run config's knob; False/"none" = explicit opt-out)."""
    if flag is None:
        return resolve_compression(run.grad_compression)
    return resolve_compression(flag)


def init_train_state(cfg: ModelConfig, key: jax.Array,
                     run: Optional[RunConfig] = None,
                     grad_compression=None) -> TrainState:
    run = run or RunConfig()
    params = init_params(cfg, key)
    if run.master_dtype != "float32":
        params = jax.tree.map(
            lambda p: p.astype(run.master_dtype) if p.ndim >= 2 else p, params)
    state: TrainState = {
        "params": params,
        "opt": adamw_init(params, jnp.dtype(run.opt_dtype)),
        "step": jnp.zeros((), jnp.int32),
    }
    if _compression_for(run, grad_compression) is not None:
        state["err"] = init_error_buffers(params)
    return state


def make_train_step(
    cfg: ModelConfig,
    ctx: Ctx,
    run: RunConfig,
    opt_cfg: Optional[AdamWConfig] = None,
    grad_compression=None,
) -> Callable[[TrainState, Tree], Tuple[TrainState, Dict[str, jax.Array]]]:
    opt_cfg = opt_cfg or AdamWConfig(
        learning_rate=run.learning_rate, weight_decay=run.weight_decay,
        grad_clip_norm=run.grad_clip_norm, warmup_steps=run.warmup_steps,
        total_steps=run.total_steps)
    n_mb = run.num_microbatches
    comp = _compression_for(run, grad_compression)

    def loss_for_grad(params, mb):
        return loss_fn(cfg, params, mb, ctx, run.remat_policy)

    grad_fn = jax.value_and_grad(loss_for_grad, has_aux=True)

    def split_mb(batch):
        def r(x):
            B = x.shape[0]
            assert B % n_mb == 0, (B, n_mb)
            return x.reshape(n_mb, B // n_mb, *x.shape[1:])
        return jax.tree.map(r, batch)

    def train_step(state: TrainState, batch: Tree):
        params = state["params"]
        if n_mb == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            mbs = split_mb(batch)

            def acc_body(carry, mb):
                (loss, metrics), g = grad_fn(params, mb)
                gsum, lsum = carry
                gsum = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), gsum, g)
                return (gsum, lsum + loss), metrics

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), ms = jax.lax.scan(
                acc_body, (g0, jnp.zeros((), jnp.float32)), mbs)
            grads = jax.tree.map(lambda g: g / n_mb, gsum)
            loss = lsum / n_mb
            metrics = jax.tree.map(lambda m: m.mean(), ms)

        new_state = dict(state)
        if comp is not None:
            # The all-reduced gradient is what the wire delivers: quantize
            # (+ carried error) here, before clip/optimizer, so the update
            # math sees exactly the transported values.
            grads, new_state["err"] = compress_grads(grads, state["err"], comp)
        new_p, new_opt, opt_metrics = adamw_update(
            opt_cfg, grads, params, state["opt"])
        new_state.update(params=new_p, opt=new_opt, step=state["step"] + 1)
        metrics = dict(metrics, loss=loss, **opt_metrics)
        return new_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# Serve steps
#
# Both steps are cache-layout agnostic: the layout (dense fallback vs the
# paged pool + page tables, selected by ``cfg.cache_layout`` /
# ``init_cache(layout=...)``) rides in the cache pytree itself and the
# model dispatches on it.  Decode ``pos`` is a scalar for lockstep batches
# or a per-sequence (B,) vector for continuous batching (paged layout;
# inactive slots carry -1 and their logits are garbage to be ignored).
# ---------------------------------------------------------------------------
def make_prefill_step(cfg: ModelConfig, ctx: Ctx):
    """(params, batch, cache, lengths=None) -> (last_logits, filled_cache).

    ``lengths`` (B,) switches to the *ragged* prefill path: prompts padded
    to the batch max, per-row last-valid logits, per-row masked cache
    writes (length-0 rows untouched — see models.model.forward).
    ``starts`` (B,) additionally makes it *chunked* (prefix caching): row
    ``b``'s tokens are the uncached tail of its prompt, opening at
    absolute position ``starts[b]``.  A MoE stack's step also returns
    its expert counts (float32 ``(picks_here, expert_rows)``, see
    ``models.moe``)."""
    @jax.named_scope("prefill")
    def prefill_step(params, batch, cache, lengths=None, starts=None):
        logits, new_cache, counts = forward(cfg, params, batch, ctx,
                                            mode="prefill", cache=cache,
                                            lengths=lengths, starts=starts)
        return (logits, new_cache, counts) if cfg.is_moe \
            else (logits, new_cache)
    return prefill_step


def make_decode_step(cfg: ModelConfig, ctx: Ctx):
    """(params, tokens (B,1), cache, pos scalar|(B,)) -> (logits, cache),
    and a MoE stack's expert counts after them."""
    @jax.named_scope("decode")
    def decode_step(params, batch, cache, pos):
        logits, new_cache, counts = forward(cfg, params, batch, ctx,
                                            mode="decode", cache=cache,
                                            pos=pos)
        return (logits, new_cache, counts) if cfg.is_moe \
            else (logits, new_cache)
    return decode_step


def make_serve_steps(cfg: ModelConfig, ctx: Ctx, *, donate_cache: bool = True):
    """Jitted (prefill, decode) pair for the serving driver.  Both donate
    their cache argument, so the page pools (and dense buffers) are
    updated in place: across the token loop, and by every admission's
    prefill, whose one-row view passes the pools whole."""
    donate = (2,) if donate_cache else ()
    prefill = jax.jit(make_prefill_step(cfg, ctx), donate_argnums=donate)
    decode = jax.jit(make_decode_step(cfg, ctx), donate_argnums=donate)
    return prefill, decode
