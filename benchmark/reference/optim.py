"""Plain float32 optimizers for the reference trajectories, as published.

Adam: Kingma & Ba 2015, algorithm 1 with bias correction (AdamW's decoupled
decay at 0 is the same update). SGD: torch.optim.SGD's momentum form, L2 decay
added to the gradient, the first buffer equal to the first decayed gradient.
State and parameters are flat ``{name: array}`` dicts.
"""

import jax.numpy as jnp


def adam_init(params):
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    return {"m": zeros, "v": dict(zeros), "t": jnp.zeros((), jnp.float32)}


def adam_step(params, grads, state, *, lr, beta1=0.9, beta2=0.999, eps=1e-8):
    t = state["t"] + 1.0
    m = {k: beta1 * state["m"][k] + (1 - beta1) * grads[k] for k in params}
    v = {k: beta2 * state["v"][k] + (1 - beta2) * grads[k] ** 2 for k in params}
    c1, c2 = 1 - beta1 ** t, 1 - beta2 ** t
    new = {k: params[k] - lr * (m[k] / c1) / (jnp.sqrt(v[k] / c2) + eps)
           for k in params}
    return new, {"m": m, "v": v, "t": t}


def sgd_init(params):
    return {"buf": {k: jnp.zeros_like(v) for k, v in params.items()},
            "t": jnp.zeros((), jnp.float32)}


def sgd_step(params, grads, state, *, lr, momentum=0.9, weight_decay=0.0):
    first = state["t"] == 0
    buf, new = {}, {}
    for k in params:
        d = grads[k] + weight_decay * params[k]
        buf[k] = jnp.where(first, d, momentum * state["buf"][k] + d)
        new[k] = params[k] - lr * buf[k]
    return new, {"buf": buf, "t": state["t"] + 1.0}
