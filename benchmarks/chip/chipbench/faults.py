"""Faults planted under a run's timed path, to show that ``correct`` fails.

Each fault takes the consumer before set-up and breaks the path beneath
it: an answer altered where it is produced, half of the batch left out
(the mean taken over the rest) by the read or by the train step, a step
that returns its state unchanged.
They exist for the checks' own tests and for ``control.py``; no cell runs
them.
"""
from __future__ import annotations


def answer_altered(consumer) -> None:
    """One byte of the first file of the third batch flipped as the read
    returns it (for token records, one token's low byte)."""
    decode = consumer.decode
    seen = [0]

    def broken(blobs):
        seen[0] += 1
        if seen[0] == 3:
            first = bytearray(blobs[0])
            first[0] ^= 0x5A
            blobs = [bytes(first)] + list(blobs[1:])
        return decode(blobs)

    consumer.decode = broken


def half_batch(consumer) -> None:
    """Every batch's second half replaced by its first: the step then
    averages over half of the samples it was sent."""
    decode = consumer.decode

    def broken(blobs):
        half = list(blobs[:len(blobs) // 2])
        return decode(half + half)

    consumer.decode = broken


def half_batch_step(consumer) -> None:
    """Train steps that take the first half of their batch twice: the
    rows the step was sent are delivered whole, and the step's mean is
    over half of them."""
    import jax.numpy as jnp
    compile_step = consumer.compile_step

    def broken(state, batch_shape):
        fn = compile_step(state, batch_shape)

        def call(s, b):
            t = b["tokens"]
            h = t.shape[0] // 2
            return fn(s, {**b, "tokens": jnp.concatenate([t[:h], t[:h]])})
        return call

    consumer.compile_step = broken


def state_unchanged(consumer) -> None:
    """Train steps that compute their loss but return the state they got."""
    import jax
    from repro.train.train_step import make_train_step

    def compile_step(state, batch_shape):
        step = make_train_step(consumer.model, consumer.ocfg)
        return jax.jit(lambda s, b: (s, step(s, b)[1])).lower(
            state, batch_shape).compile()

    consumer.compile_step = compile_step


FAULTS = {"answer_altered": answer_altered, "half_batch": half_batch,
          "half_batch_step": half_batch_step,
          "state_unchanged": state_unchanged}
