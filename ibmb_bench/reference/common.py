"""Pieces shared by the plain references: weights, LayerNorm, dropout, the
loss and Adam, written out in ``jax.numpy``.

Every function takes a ``dtype``: float32 is the reference, computed with
every matrix product at ``Precision.HIGHEST``; bfloat16 is the control, the
same mathematics one precision below the configuration's float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def precision(dtype):
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else jax.lax.Precision.DEFAULT)


def matmul(a, b, dtype):
    return jnp.matmul(a, b, precision=precision(dtype))


def glorot(key, shape):
    lim = float(np.sqrt(6.0 / (shape[0] + shape[-1])))
    return jax.random.uniform(key, shape, F32, -lim, lim)


def layer_norm(x, scale, bias, eps=1e-5):
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def hidden_activation(h, p, key, rate):
    """LayerNorm, ReLU and (when ``key`` is given) inverted dropout.

    The dropout key follows the program's stream: one split per hidden
    layer, a Bernoulli keep-mask over the whole padded (N, width) array.
    Returns the activation and the key left for the next layer.
    """
    h = jax.nn.relu(layer_norm(h, p["ln_scale"], p["ln_bias"]))
    if key is not None and rate > 0:
        key, sub = jax.random.split(key)
        keep = jax.random.bernoulli(sub, 1.0 - rate, h.shape)
        h = jnp.where(keep, h / jnp.asarray(1.0 - rate, h.dtype),
                      jnp.zeros((), h.dtype))
    return h, key


def dropout_key(seed_key, epoch: int, step: int):
    """Dropout key of (epoch, step): fold_in 1, then epoch, then step."""
    k = jax.random.fold_in(seed_key, 1)
    return jax.random.fold_in(jax.random.fold_in(k, epoch), step)


def cross_entropy(logits, labels):
    """Mean negative log-likelihood of ``labels`` over the rows given."""
    logits = logits.astype(F32)
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


def adam_step(params, m, v, grads, t: int, lr: float, dtype,
              b1=0.9, b2=0.999, eps=1e-8):
    """One Adam update (Kingma & Ba, bias-corrected) in ``dtype``."""
    c = lambda x: jnp.asarray(x, dtype)
    m = jax.tree_util.tree_map(lambda m_, g: c(b1) * m_ + c(1 - b1) * g,
                               m, grads)
    v = jax.tree_util.tree_map(lambda v_, g: c(b2) * v_ + c(1 - b2) * g * g,
                               v, grads)
    bc1, bc2 = c(1 - b1 ** t), c(1 - b2 ** t)
    params = jax.tree_util.tree_map(
        lambda p, m_, v_: p - c(lr) * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + c(eps)),
        params, m, v)
    return params, m, v


def cast(tree, dtype):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, dtype), tree)


_JITTED = {}


def jitted(fn, *static):
    """One jitted ``fn`` per (fn, static arguments), shared across calls."""
    key = (fn, static)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(lambda *a: fn(*static, *a))
    return _JITTED[key]


def train_steps(loss, model_key, params0, batches, keys, lr: float, dtype):
    """Steps of Adam on ``batches`` from ``params0``, in ``dtype``.

    ``loss(model, params, batch, dtype, key)`` with the model given as a
    hashable ``model_key`` (a sorted tuple of its items). Returns the loss
    of each step, the gradient of the first, and the parameters after the
    last, all as float32 host arrays.
    """
    step = jitted(_value_and_grad, loss, model_key, dtype)
    params = cast(params0, dtype)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad1 = [], None
    for t, (batch, key) in enumerate(zip(batches, keys), start=1):
        batch = {k: jnp.asarray(a) for k, a in batch.items()}
        value, grads = step(params, batch, key)
        if grad1 is None:
            grad1 = to_host(grads)
        losses.append(float(value))
        params, m, v = adam_step(params, m, v, grads, t, lr, dtype)
    return losses, grad1, to_host(params)


def _value_and_grad(loss, model_key, dtype, params, batch, key):
    return jax.value_and_grad(
        lambda p: loss(dict(model_key), p, batch, dtype, key))(params)


def to_host(tree):
    return jax.tree_util.tree_map(
        lambda x: np.asarray(jnp.asarray(x, F32)), tree)
