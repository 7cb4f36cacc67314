"""Train-step builders: value_and_grad -> clip -> AdamW, for both the LM
substrate and the paper's SimGNN model.

Under jit with NamedSharding'd params, XLA SPMD derives the FSDP collectives
(all-gather params on use, reduce-scatter grads) automatically; the optimizer
update then runs fully sharded (ZeRO-3 equivalent). Optional int8 gradient
compression (distributed/compression.py) targets the cross-pod DCN
all-reduce. Gradient accumulation microbatches via lax.scan when
`accum_steps > 1`.

The SimGNN step delegates its entire forward/backward to
`ScoringEngine.loss_and_grad` (DESIGN.md §11): path selection between the
dense reference and the custom-VJP packed executors lives in the engine,
never here.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.distributed.sharding import Runtime
from repro.models import encdec, lm
from repro.models.config import ModelConfig
from repro.train import optimizer as opt

Array = jax.Array


def loss_for(cfg: ModelConfig) -> Callable:
    if cfg.is_enc_dec:
        return encdec.encdec_loss
    return lm.lm_loss


def build_train_step(cfg: ModelConfig, rt: Runtime, *,
                     peak_lr: float = 3e-4, max_grad_norm: float = 1.0,
                     accum_steps: int = 1, compress_grads: bool = False,
                     constrain_grads: bool = True):
    """Returns step_fn(params, opt_state, batch) -> (params, opt_state, metrics).
    batch leaves may carry a leading accum dim when accum_steps > 1.

    constrain_grads pins each gradient to its parameter's sharding at the
    autodiff output. Measured neutral on the gemma2 cell (the partitioner
    already lands grads in param sharding there — §Perf appendix D,
    iteration D2 refuted); kept as a zero-cost guard against partitioner
    drift on other architectures."""
    loss_fn = loss_for(cfg)

    def fwd_bwd(params, batch):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, rt, batch))(params)
        if constrain_grads and rt.mesh is not None:
            from repro.distributed.sharding import param_shardings
            shardings = param_shardings(rt, grads)
            grads = jax.tree.map(
                lambda g, s: jax.lax.with_sharding_constraint(g, s)
                if s is not None else g, grads, shardings)
        return loss, grads

    def step_fn(params, opt_state, batch):
        if accum_steps > 1:
            def micro(acc, mb):
                loss, grads = fwd_bwd(params, mb)
                return (acc[0] + loss,
                        jax.tree.map(jnp.add, acc[1], grads)), None
            zero = (jnp.zeros((), jnp.float32),
                    jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params))
            (loss, grads), _ = jax.lax.scan(micro, zero, batch)
            loss = loss / accum_steps
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
        else:
            loss, grads = fwd_bwd(params, batch)

        if compress_grads:
            from repro.distributed.compression import int8_compress_tree
            grads = int8_compress_tree(grads)

        grads, grad_norm = opt.clip_by_global_norm(grads, max_grad_norm)
        lr = opt.cosine_schedule(opt_state.step, peak_lr=peak_lr)
        params, opt_state = opt.adamw_update(grads, opt_state, params, lr=lr)
        metrics = {"loss": loss.astype(jnp.float32), "grad_norm": grad_norm,
                   "lr": lr, "step": opt_state.step}
        return params, opt_state, metrics

    return step_fn


def build_simgnn_apply(*, peak_lr: float = 1e-3,
                       max_grad_norm: float = 1.0):
    """The jitted SimGNN optimizer half-step (clip -> cosine schedule ->
    AdamW), shared by `build_simgnn_train_step` and any baseline that must
    pair a different loss with the SAME update — one source for the
    schedule constants."""
    @jax.jit
    def apply(params, opt_state, loss, grads):
        grads, grad_norm = opt.clip_by_global_norm(grads, max_grad_norm)
        lr = opt.cosine_schedule(opt_state.step, peak_lr=peak_lr, warmup=50,
                                 total=2_000)
        params, opt_state = opt.adamw_update(grads, opt_state, params, lr=lr,
                                             weight_decay=1e-4)
        return params, opt_state, {"loss": loss, "grad_norm": grad_norm,
                                   "lr": lr, "step": opt_state.step}

    return apply


def build_simgnn_train_step(engine, *, peak_lr: float = 1e-3,
                            max_grad_norm: float = 1.0,
                            accum_steps: int = 1,
                            clock: Callable[[], float] | None = None):
    """Train step for the paper's model (MSE on exp(-nGED) targets), routed
    through a `core.engine.ScoringEngine` (DESIGN.md §11) — the engine is
    the single dispatch point for BOTH directions of the model, so no path
    selection (packing, bucketing, kernel choice) happens here.

    batch: {"pairs": [(g1, g2), ...], "target": [B]} — raw graph-pair dicts
    (e.g. `data.graphs.pair_stream` batches). The engine packs once per
    batch and reuses the packed layout across `accum_steps` accumulation
    microbatches; the optimizer update runs in one jitted region.

    Non-finite guard (DESIGN.md §12): if the loss or any gradient leaf is
    NaN/Inf after the engine has exhausted its own degradation options, the
    update is SKIPPED — params and optimizer state pass through unchanged
    (no momentum poisoning, no step-count advance), the skip is counted on
    `engine.counters["train_skipped_steps"]`, and the metrics carry
    `skipped=1` so loops and dashboards can see the gap.

    Tracing (DESIGN.md §15): each full step also lands one `kind="train"`
    / `path="train_step"` record on `engine.recorder` — the end-to-end
    step latency next to the engine's own per-rung `train:<path>` records,
    so the replay harness can compare optimizer overhead against forward/
    backward time. `clock` defaults to the engine's injectable clock.
    """
    from repro.core.engine import tree_all_finite

    apply = build_simgnn_apply(peak_lr=peak_lr, max_grad_norm=max_grad_norm)
    clk = clock if clock is not None else engine._clock

    def _trace(n_pairs: int, wall_s: float) -> None:
        rec = getattr(engine, "recorder", None)
        if rec is None:
            return
        stats = getattr(engine.last_plan, "stats", None)
        rec.record(kind="train", path="train_step", n_pairs=n_pairs,
                   max_nodes=getattr(stats, "max_nodes", 0),
                   mean_nodes=getattr(stats, "mean_nodes", 0.0),
                   avg_degree=getattr(stats, "avg_degree", 0.0),
                   density=getattr(stats, "density", 0.0),
                   wall_s=wall_s)

    def step_fn(params, opt_state, batch):
        t0 = clk()
        loss, grads = engine.loss_and_grad(batch["pairs"], batch["target"],
                                           params=params,
                                           accum_steps=accum_steps)
        if not tree_all_finite(loss, grads):
            engine.counters["train_skipped_steps"] += 1
            metrics = {"loss": loss.astype(jnp.float32),
                       "grad_norm": jnp.zeros((), jnp.float32),
                       "lr": jnp.zeros((), jnp.float32),
                       "step": opt_state.step,
                       "skipped": jnp.ones((), jnp.float32)}
            _trace(len(batch["pairs"]), clk() - t0)
            return params, opt_state, metrics
        params, opt_state, metrics = apply(params, opt_state, loss, grads)
        jax.block_until_ready(metrics["loss"])
        _trace(len(batch["pairs"]), clk() - t0)
        return params, opt_state, metrics

    return step_fn
