"""Closed-loop SimGNN training through `build_simgnn_train_step(engine)`.

Set-up draws a pool of batches of (graph, edited graph) pairs with
SimGNN's GED-derived targets, builds one engine, one step function and
one optimizer state, and drives that same object through its first
`check_steps` steps on distinct batches, keeping what the comparison
needs; it then warms the remaining batch shapes with further steps and
hands the object to the window, which cycles through the pool.

The plain reference follows the first `check_steps` steps from the same
weights: float32 loss and gradients at `highest` precision, global-norm
clipping, the warm-up/cosine learning rate and AdamW, with the constants
of the traffic file. Compared: each step's loss, the first step's
gradient as the optimizer received it (its first moment over 1 - beta1),
and each leaf's change over the checked steps, as norms per leaf.
"""

from __future__ import annotations

import time

import numpy as np

from harness import cost, graphs
from harness.drivers.common import bad_counters, fresh, program_config


def _leaves(tree) -> list[np.ndarray]:
    import jax

    return [np.asarray(x, np.float64) for x in jax.tree.leaves(tree)]


def norm_gap(prog: list, ref: list, keep=None) -> float:
    """Worst leaf's gap between the two norms, over the larger of that
    leaf's reference norm and the median leaf's."""
    pn = np.asarray([np.linalg.norm(x) for x in prog])
    rn = np.asarray([np.linalg.norm(x) for x in ref])
    keep = np.ones(len(rn), bool) if keep is None else keep
    floor = np.median(rn[keep])
    gaps = np.abs(pn - rn) / np.maximum(rn, floor)
    return float(np.max(gaps[keep]))


class Driver:
    kind = "train"

    def __init__(self, cell, seed: int, *, control: str | None = None):
        self.cell, self.seed, self.control = cell, seed, control
        self.config, self.traffic = cell.config, cell.traffic

    # ---------------------------------------------------------------- set-up

    def setup(self, compiles) -> dict:
        import jax

        from reference import simgnn as ref

        cfg, tr = self.config, self.traffic
        self.params = ref.init_params(self.seed, cfg)
        jax.block_until_ready(self.params)
        rng = np.random.default_rng(self.seed)
        sizes = np.random.default_rng(tr["size_seed"])
        self.batches = [graphs.edit_pairs(rng, cfg["graphs"],
                                          tr["batch_pairs"], tr["max_edits"],
                                          sizes)
                        for _ in range(tr["pool_batches"])]
        self.batch_flops = [3.0 * sum(cost.pair_cost(cfg, a, b)[0]
                                      for a, b in pairs)
                            for pairs, _ in self.batches]
        self.p0 = _leaves(self.params)
        if self.control:
            self.step = self._control_step
            self.state = (self.params, None)
        else:
            from repro.core.engine import ScoringEngine
            from repro.train.optimizer import adamw_init
            from repro.train.step import build_simgnn_train_step

            self.engine = ScoringEngine(self.params, program_config(cfg))
            step_fn = build_simgnn_train_step(
                self.engine, peak_lr=tr["peak_lr"],
                max_grad_norm=tr["max_grad_norm"])
            self.step = lambda state, batch: self._program_step(step_fn,
                                                                state, batch)
            self.state = (self.params, adamw_init(self.params))
        self.losses: list[float] = []
        self.skipped = 0
        self.steps = 0
        n_check = tr["check_steps"]
        for i in range(n_check):
            loss = self._advance(i)
            self.losses.append(loss)
            if i == 0:
                self.g1 = self._first_gradient()
        self.p_checked = _leaves(self.state[0])
        passes = []
        for _ in range(3):
            before = compiles.lowered
            for i in range(len(self.batches)):
                if passes or i >= n_check:
                    self._advance(i)
            passes.append(compiles.lowered - before)
            if passes[-1] == 0:
                break
        return {"warm_passes_lowered": passes,
                "pool": graphs.realized([g for pairs, _ in self.batches
                                         for p in pairs for g in p])}

    def _request(self, i: int) -> dict:
        pairs, targets = self.batches[i]
        return {"pairs": [(fresh(a), fresh(b)) for a, b in pairs],
                "target": targets}

    def _advance(self, i: int) -> float:
        self.state, loss = self.step(self.state, self._request(i))
        self.steps += 1
        return loss

    def _program_step(self, step_fn, state, batch):
        params, opt = state
        params, opt, metrics = step_fn(params, opt, batch)
        if "skipped" in metrics:
            self.skipped += 1
        return (params, opt), float(metrics["loss"])

    def _first_gradient(self):
        """The first step's gradient as the optimizer received it: AdamW's
        first moment after one step over (1 - beta1)."""
        if self.control:
            return self._control_g1
        b1 = self.traffic["adam_b1"]
        return [m / (1.0 - b1) for m in _leaves(self.state[1].m)]

    def _control_step(self, state, batch):
        """The reference's step at the control's precision."""
        params, mv = state
        loss, grads, params, mv = self._reference_step(
            params, mv, batch["pairs"], batch["target"], self.steps,
            self.control)
        if self.steps == 0:
            self._control_g1 = _leaves(grads)
        return (params, mv), loss

    def _reference_step(self, params, mv, pairs, targets, step: int,
                        mode: str = "highest"):
        import jax

        from reference import simgnn as ref

        tr = self.traffic
        n_labels = self.config["n_node_labels"]
        width = ref.size_class(max(max(a["adj"].shape[0], b["adj"].shape[0])
                                   for a, b in pairs))
        g1 = ref.pad([a for a, _ in pairs], n_labels, width)
        g2 = ref.pad([b for _, b in pairs], n_labels, width)
        loss, grads = ref.loss_and_grad_jit(params, g1, g2,
                                            np.asarray(targets, np.float32),
                                            mode=mode)
        grads = ref.clip(grads, tr["max_grad_norm"])
        if mv is None:
            zeros = jax.tree.map(lambda x: x * 0.0, params)
            mv = (zeros, zeros)
        lr = ref.cosine_lr(step, tr["peak_lr"], tr["warmup_steps"],
                           tr["total_steps"])
        params, m, v = ref.adamw(params, grads, mv[0], mv[1], step + 1, lr,
                                 b1=tr["adam_b1"], b2=tr["adam_b2"],
                                 eps=tr["adam_eps"],
                                 weight_decay=tr["weight_decay"])
        return float(loss), grads, params, (m, v)

    # ---------------------------------------------------------------- window

    def window(self, seconds: float, clock=time.perf_counter) -> None:
        import jax

        n = len(self.batches)
        self.window_steps = 0
        self.window_flops = 0.0
        t0 = clock()
        deadline = t0 + seconds
        while True:
            i = self.steps % n
            with jax.profiler.TraceAnnotation("bench.train_step"):
                self._advance(i)
            b = clock()
            self.window_steps += 1
            self.window_flops += self.batch_flops[i]
            if b >= deadline:
                break
        self.window_s = b - t0

    # --------------------------------------------------------------- results

    @property
    def attempted(self) -> int:
        return self.window_steps

    @property
    def failed(self) -> int:
        return self.skipped

    def end_to_end(self) -> dict:
        pairs = self.window_steps * self.traffic["batch_pairs"]
        return {"train_pairs_per_s": pairs / self.window_s}

    def layer_counters(self) -> dict:
        return {"kind": self.kind, "window_s": self.window_s,
                "useful_flops": self.window_flops}

    def record(self) -> dict:
        return {"steps": self.window_steps, "total_steps": self.steps,
                "checked_losses": self.losses, "skipped": self.skipped,
                "counters": self.engine_counters}

    def release(self) -> None:
        self.engine_counters = ({} if self.control else
                                {k: int(v) for k, v in
                                 self.engine.counters.items()})
        self.engine = self.step = self.state = None

    # ----------------------------------------------------------- correctness

    def checks(self) -> list[tuple[str, float, float]]:
        tr = self.traffic
        params, mv = self.params, None
        ref_losses = []
        for i in range(tr["check_steps"]):
            pairs, targets = self.batches[i]
            loss, grads, params, mv = self._reference_step(
                params, mv, pairs, targets, i)
            ref_losses.append(loss)
            if i == 0:
                g1 = _leaves(grads)
        p_ref = _leaves(params)
        loss_gap = max(abs(a - b) / abs(b)
                       for a, b in zip(self.losses, ref_losses))
        gn = np.asarray([np.linalg.norm(x) for x in g1])
        moving = gn >= tr["still_leaf_share"] * np.median(gn)
        d_prog = [a - b for a, b in zip(self.p_checked, self.p0)]
        d_ref = [a - b for a, b in zip(p_ref, self.p0)]
        lim = self.cell.limits
        bad = bad_counters(self.engine_counters)
        return [("loss_rel_gap", loss_gap, lim["loss_rel_gap"]),
                ("grad_norm_gap", norm_gap(self.g1, g1),
                 lim["grad_norm_gap"]),
                ("update_norm_gap", norm_gap(d_prog, d_ref, moving),
                 lim["update_norm_gap"]),
                ("skipped_steps", float(self.skipped), 0.0),
                ("error_counters", float(sum(bad.values())), 0.0)]
