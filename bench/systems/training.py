"""The program's training path, driven as ``launch/train.py:train_tm``
drives it: batches from ``ShardedBatcher``, one eager
``ops.tm_train_step_kernel`` call per step (fused kernels, no chunking,
no autotune).  The serving cells train their bank through it too."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import core, data


def tm_config(cfg: dict):
    from repro.core.tm import TMConfig

    return TMConfig(
        n_features=cfg["n_features"], n_classes=cfg["n_classes"],
        clauses_per_class=cfg["clauses_per_class"],
        n_states=cfg["n_states"], threshold=cfg["threshold"], s=float(cfg["s"]),
        boost_true_positive=cfg["boost_true_positive"],
        clause_pad_multiple=cfg["clause_pad_multiple"])


class Trainer:
    """One object: the step with its state and its feed.  Set-up drives
    it through its first steps and the window goes on with the same one."""

    def __init__(self, ctx: core.Context, n_train: int, batch: int,
                 salt: int = 0):
        from repro.data import ShardedBatcher
        from repro.kernels import ops

        self.ctx, self.batch = ctx, batch
        self.tmc = tm_config(ctx.cfg)
        x, y = data.samples(ctx.cfg, ctx.seed, salt + 1, n_train)
        self.x, self.y = np.asarray(x), np.asarray(y)
        self.ta0 = data.initial_automata(ctx.cfg, ctx.seed, salt + 2)
        self.ta = self.ta0
        self._loader = ShardedBatcher((self.x, self.y), batch,
                                      seed=core.seed_u32(ctx.seed, salt + 3))
        self._it = iter(self._loader)
        self._seed0 = core.seed_u32(ctx.seed, salt + 4)
        self.steps = 0
        self.record = []           # (x, y, seed) of the steps kept for checks
        self._step = ctx.wrap("train_step", ops.tm_train_step_kernel)

    def step(self, keep: bool = False):
        xb, yb = next(self._it)
        seed = (self._seed0 + self.steps) & 0xFFFFFFFF
        self.ta, _ = self._step(self.tmc, self.ta, jnp.asarray(xb),
                                jnp.asarray(yb), jnp.uint32(seed),
                                batch_chunk=None, fuse=True, autotune=False)
        self.steps += 1
        if keep:
            self.record.append((xb, yb, seed))
        return self.ta

    def close(self):
        self._it.close()


def train_bank(ctx: core.Context, recipe: dict):
    """Train the bank a serving cell serves, by the configuration's recipe.
    Returns the trainer, whose ``record`` holds every step for the
    reference to replay."""
    t0 = time.perf_counter()
    tr = Trainer(ctx, recipe["n_train"], recipe["batch"], salt=10)
    n = recipe["epochs"] * (recipe["n_train"] // recipe["batch"])
    for _ in range(n):
        tr.step(keep=True)
    jax.block_until_ready(tr.ta)
    tr.close()
    ctx.log(f"bank: {n} kernel training steps of {recipe['batch']} in "
            f"{time.perf_counter() - t0:.2f} s")
    return tr
