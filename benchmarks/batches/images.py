"""Image-classification batches made on the device from the seed and the
step index: unit-normal NHWC float32 pixels and uniform labels, so every
row differs.  ``make`` returns ``batch(key, step)`` giving ``(images,
labels)``.
"""

import jax
import jax.numpy as jnp


def make(traffic, config):
    b, side = traffic["batch_size"], traffic["image_size"]
    classes = config["model"]["kwargs"]["num_classes"]

    def batch(key, step):
        k0, k1 = jax.random.split(jax.random.fold_in(key, step))
        images = jax.random.normal(k0, (b, side, side, 3), jnp.float32)
        return images, jax.random.randint(k1, (b,), 0, classes, jnp.int32)

    return batch
