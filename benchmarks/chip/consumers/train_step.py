"""Consumer that trains the configuration's model on each batch.

Built as ``launch/train.py`` ``run()`` builds it: the decode is
``files_to_tokens`` + ``jnp.asarray``, the step is
``jax.jit(make_train_step(model, ocfg), donate_argnums=0)`` compiled
ahead of time, and every step's loss is fetched to the host. The weights
are the benchmark's, made from the seed on the device by the plain
reference beside the configuration; the optimizer state is the program's
``adamw_init``.

Set-up drives this one compiled step and its state through the first
``checked_steps`` batches of the loader, reading the losses, the first
gradient as AdamW took it (its first moment after one step, over
1 - beta1) and the weights' change after the last of them; the window
then goes on with the same object. After the window ``check`` frees the
program's state, runs the reference over the same rows and compares, and
compares every row the window's steps consumed with the generated
tokens in the sampler's order.
"""
from __future__ import annotations

import gc
import time
from functools import partial
from typing import Dict, List

import numpy as np

from chipbench import flops, gen
from chipbench.runner import Check, log


def gaps(prog: Dict[str, float], ref: Dict[str, float],
         keep=None) -> float:
    """Worst leaf's |norm_prog - norm_ref| over the larger of that leaf's
    reference norm and the median leaf's."""
    names = [n for n in ref if keep is None or n in keep]
    med = float(np.median([ref[n] for n in names]))
    return max(abs(prog[n] - ref[n]) / max(ref[n], med) for n in names)


def compare(prog: Dict, ref, limits: Dict[str, float]) -> Dict[str, Check]:
    """The cell's numbers for the readings ``prog`` ({"loss": [..], "grad":
    {leaf: norm}, "change": {leaf: norm}}) against the float32 reference's
    ``ref`` (losses, first gradient's and change's leaf norms), each with
    its limit. Leaves whose reference gradient is at rounding noise move
    under Adam by round-off alone: their change is not compared."""
    losses, grad, change = ref
    med = float(np.median(list(grad.values())))
    moved = {n for n, g in grad.items() if g >= 1e-3 * med}
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], losses))
    return {"loss_gap": Check(loss_gap, limits["loss_gap"]),
            "grad_gap": Check(gaps(prog["grad"], grad), limits["grad_gap"]),
            "change_gap": Check(gaps(prog["change"], change, moved),
                                limits["change_gap"])}


def flat_norms(tree) -> Dict[str, float]:
    import jax
    return {jax.tree_util.keystr(p): float(x)
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


class Consumer:
    def __init__(self, cell, paths, files, tokens, seed: int, devices):
        import jax
        from repro.configs.base import ModelConfig
        from repro.models import build_model
        from repro.train.optimizer import OptimizerConfig

        self.jax = jax
        self.device = devices[0]
        self.seed, self.tokens = seed, tokens
        self.num_samples = tokens.shape[0]
        self.mc = cell.config["model"]
        self.opt = cell.config["optimizer"]
        self.limits = cell.config["limits"]
        self.ref = cell.reference()
        self.batch = int(cell.traffic["batch"])
        self.checked_steps = int(cell.traffic["checked_steps"])
        self.seq_len = int(cell.config["dataset"]["seq_len"])
        self.cfg = ModelConfig(**{**self.mc, "global_layers":
                                  tuple(self.mc["global_layers"])})
        self.model = build_model(self.cfg)
        o = self.opt
        self.ocfg = OptimizerConfig(
            lr=o["lr"], betas=tuple(o["betas"]), eps=o["eps"],
            weight_decay=o["weight_decay"], grad_clip=o["grad_clip"],
            warmup_steps=o["warmup_steps"], total_steps=o["total_steps"],
            schedule=o["schedule"], min_lr_ratio=o["min_lr_ratio"])
        shapes = jax.eval_shape(self.model.init, jax.random.key(0))
        self.flops_per_sample = flops.train_flops_per_sample(
            flops.param_leaves(shapes), self.seq_len,
            tied=self.cfg.tie_embeddings)
        self._shapes = shapes
        self.seen: List = []             # each step's tokens, on the device
        self.readings: Dict = {}

    def decode(self, blobs):
        import jax.numpy as jnp
        from repro.data.synthetic import files_to_tokens
        return {"tokens": jnp.asarray(files_to_tokens(blobs, self.seq_len))}

    def _weights(self):
        jax = self.jax
        words = self.ref.seed_words(self.seed)
        params = jax.jit(partial(self.ref.init_params, self.mc))(words)
        want = jax.tree.map(lambda s: (s.shape, s.dtype), self._shapes)
        got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        if jax.tree.structure(want) != jax.tree.structure(got) or \
                jax.tree.leaves(want) != jax.tree.leaves(got):
            raise ValueError("the reference's weights do not match the "
                             "program's parameter layout")
        return params, words

    def setup(self, plane, spans) -> None:
        jax = self.jax
        import jax.numpy as jnp
        from repro.train.optimizer import adamw_init
        from repro.train.train_step import TrainState

        t0 = time.perf_counter()
        params, words = self._weights()
        state = TrainState(params, jax.jit(adamw_init)(params), None)
        del params
        jax.block_until_ready(state)
        t1 = time.perf_counter()
        batch_shape = {"tokens": jax.ShapeDtypeStruct(
            (self.batch, self.seq_len), jnp.int32)}
        self.step_fn = self.compile_step(state, batch_shape)
        self.state = state
        t2 = time.perf_counter()
        norms = jax.jit(lambda t: jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), t))
        change = jax.jit(lambda p, w: norms(jax.tree.map(
            jnp.subtract, p, self.ref.init_params(self.mc, w))))
        b1 = self.opt["betas"][0]
        losses = []
        for k in range(self.checked_steps):
            self.step(plane.next(spans))
            losses.append(self.last_loss)
            if k == 0:
                self.readings["grad"] = {
                    n: v / (1 - b1)
                    for n, v in flat_norms(norms(self.state.opt["m"])).items()}
        self.readings["loss"] = losses
        self.readings["change"] = flat_norms(change(self.state.params, words))
        log(f"train_step setup: weights_s={t1 - t0} step_program_s={t2 - t1} "
            f"checked_steps_s={time.perf_counter() - t2}")

    def compile_step(self, state, batch_shape):
        from repro.train.train_step import make_train_step
        return self.jax.jit(make_train_step(self.model, self.ocfg),
                            donate_argnums=0).lower(state,
                                                    batch_shape).compile()

    def step(self, batch) -> int:
        self.state, metrics = self.step_fn(self.state, batch)
        self.last_loss = float(metrics["loss"])
        self.seen.append(batch["tokens"])
        return self.batch

    def check(self):
        n_steps = len(self.seen)
        bad_tokens = bad_rows = 0
        for k, toks in enumerate(self.seen):
            want = self.tokens[gen.batch_indices(self.num_samples, self.batch,
                                                 self.seed, k)]
            diff = np.asarray(toks) != want
            bad_tokens += int(diff.sum())
            bad_rows += int(diff.any(axis=1).sum())
        # free the program's state before the reference runs
        self.seen, self.state, self.step_fn = [], None, None
        gc.collect()

        batches = [self.tokens[gen.batch_indices(self.num_samples, self.batch,
                                                 self.seed, k)]
                   for k in range(self.checked_steps)]
        losses, grad, change = self.ref.train(self.mc, self.opt, self.seed,
                                              batches)
        grad, change = flat_norms(grad), flat_norms(change)
        med = float(np.median(list(grad.values())))
        log(f"train_step: steps={n_steps} loss_prog={self.readings['loss']} "
            f"loss_ref={losses} leaves={len(grad)} leaves_not_compared="
            f"{sorted(n for n, g in grad.items() if g < 1e-3 * med)}")
        checks = {"tokens_mismatched": Check(bad_tokens, 0),
                  **compare(self.readings, (losses, grad, change),
                            self.limits)}
        return checks, n_steps * self.batch, bad_rows
