"""Plain float32 optimizers for the training references, and the way back
from an optimizer's state after one step to the gradient it was given.

LAMB is You et al. 2019 as apex's FusedLAMB states it: a global-norm clip of
the gradient, Adam moments with bias correction, decoupled weight decay
added to the update, and a per-tensor trust ratio |p|/|u|.  SGD is torch's
momentum SGD with L2 decay in the gradient (dampening 0: the first buffer is
the first decayed gradient).  Hyper-parameters come from the configuration
file, the same numbers the program's optimizer is built with.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

tmap = jax.tree_util.tree_map


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x))
                        for x in jax.tree_util.tree_leaves(tree)))


# ----------------------------------------------------------------- LAMB

def _lamb_clip(grads, hp):
    gnorm = _global_norm(grads)
    scale = jnp.where(gnorm > hp["max_grad_norm"],
                      hp["max_grad_norm"] / (gnorm + 1e-6), 1.0)
    return tmap(lambda g: g * scale, grads)


def lamb_init(params):
    zeros = tmap(jnp.zeros_like, params)
    return {"m": zeros, "v": zeros}


def lamb_step(params, grads, state, t, hp):
    b1, b2 = hp["betas"]
    g = _lamb_clip(grads, hp)
    m = tmap(lambda m, g: b1 * m + (1 - b1) * g, state["m"], g)
    v = tmap(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], g)
    c1, c2 = 1.0 / (1.0 - b1 ** t), 1.0 / (1.0 - b2 ** t)

    def leaf(p, m, v):
        u = (m * c1) / (jnp.sqrt(v * c2) + hp["eps"]) \
            + hp["weight_decay"] * p
        pn, un = jnp.sqrt(jnp.sum(p * p)), jnp.sqrt(jnp.sum(u * u))
        ratio = jnp.where((pn > 0) & (un > 0), pn / un, 1.0)
        return p - hp["lr"] * ratio * u

    return tmap(leaf, params, m, v), {"m": m, "v": v}


def lamb_given_grad(grads, params, hp):
    """The gradient as LAMB's moments receive it: after the clip."""
    del params
    return _lamb_clip(grads, hp)


def lamb_first_grad(moment, params0, hp):
    """From the first moment after one step (it started at zero)."""
    del params0
    return tmap(lambda m: m / (1.0 - hp["betas"][0]), moment)


# ------------------------------------------------------------------ SGD

def sgd_init(params):
    return {"buf": tmap(jnp.zeros_like, params)}


def sgd_step(params, grads, state, t, hp):
    del t
    g = tmap(lambda g, p: g + hp["weight_decay"] * p, grads, params)
    buf = tmap(lambda b, g: hp["momentum"] * b + g, state["buf"], g)
    return tmap(lambda p, b: p - hp["lr"] * b, params, buf), {"buf": buf}


def sgd_given_grad(grads, params, hp):
    del params, hp
    return grads


def sgd_first_grad(buf, params0, hp):
    """The first buffer is the first decayed gradient: take the decay off."""
    return tmap(lambda b, p: b - hp["weight_decay"] * p, buf, params0)
