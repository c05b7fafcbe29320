"""Masked-LM batches made on the device from the seed and the step index.

Token ids are uniform over the vocabulary, so every row differs; 15% of
positions are masked as BERT masks them (80% the mask token, 10% a random
token, 10% unchanged).  ``make`` returns ``batch(key, step)`` giving
``(input_ids, (labels, weights))``, the shape the program's masked-LM loss
takes.
"""

import jax
import jax.numpy as jnp


def make(traffic, config):
    b, s = traffic["batch_size"], traffic["seq_len"]
    vocab = config["model"]["kwargs"]["vocab_size"]
    mask_id, p = traffic["mask_token_id"], traffic["mask_prob"]

    def batch(key, step):
        k0, k1, k2, k3 = jax.random.split(jax.random.fold_in(key, step), 4)
        toks = jax.random.randint(k0, (b, s), 0, vocab, jnp.int32)
        masked = jax.random.bernoulli(k1, p, (b, s))
        u = jax.random.uniform(k2, (b, s))
        rand = jax.random.randint(k3, (b, s), 0, vocab, jnp.int32)
        ids = jnp.where(masked & (u < 0.8), mask_id, toks)
        ids = jnp.where(masked & (u >= 0.8) & (u < 0.9), rand, ids)
        return ids, (toks, masked.astype(jnp.float32))

    return batch
