"""The comparison that decides ``correct``: norms by leaf, gaps against the reference.

A *leaf* is one tensor of the published model; tensors stacked on a leading
layer axis (``stacked_prefix``) count one leaf per layer. Gaps are taken
between norms (program's minus reference's), never the norm of a difference,
and each is measured against the reference's norm of that leaf or of the median
leaf, whichever is larger, because some gradients are all but zero.
"""

import jax
import jax.numpy as jnp
import numpy as np


def leaf_norms(flat, stacked_prefix=None):
    """``{name: norm}`` of a flat ``{name: array}`` dict, float32; a stacked
    leaf gives a vector with one norm per layer. Traceable."""
    out = {}
    for name, x in flat.items():
        x = x.astype(jnp.float32)
        stacked = stacked_prefix is not None and name.startswith(stacked_prefix)
        axes = tuple(range(1, x.ndim)) if stacked else None
        out[name] = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))
    return out


def reference_trajectory(loss_fn, opt_init, opt_step, weights, batches,
                         stacked_prefix):
    """Follow ``weights`` through one optimizer step per stacked batch.

    Returns ``(losses (n,), first_grad_norms, update_norms)``: each step's
    loss, the leaf norms of the first step's gradient, and the leaf norms of
    the parameters' change over all the steps. Traceable; jit it."""
    def step(carry, batch):
        params, opt = carry
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        params, opt = opt_step(params, grads, opt)
        return (params, opt), (loss, leaf_norms(grads, stacked_prefix))

    (final, _), (losses, grad_norms) = jax.lax.scan(
        step, (weights, opt_init(weights)), batches)
    delta = {k: final[k] - weights[k] for k in weights}
    first = {k: v[0] for k, v in grad_norms.items()}
    return losses, first, leaf_norms(delta, stacked_prefix)


def _vector(norms):
    names, vals = [], []
    for name in sorted(norms):
        v = np.atleast_1d(np.asarray(norms[name], np.float64))
        names += [name if v.size == 1 else f"{name}[{i}]" for i in range(v.size)]
        vals.append(v)
    return names, np.concatenate(vals)


def worst_leaf_gap(program, reference):
    """``(gap, where)``: the largest ``|p - r| / max(r, median r)`` over leaves,
    and that leaf with its two norms."""
    names, r = _vector(reference)
    names_p, p = _vector(program)
    if names != names_p:
        raise ValueError("program and reference name different leaves: "
                         f"{sorted(set(names) ^ set(names_p))[:6]}")
    gaps = np.abs(p - r) / np.maximum(r, np.median(r))
    gaps = np.where(np.isfinite(gaps), gaps, np.inf)
    worst = int(np.argmax(gaps))
    return float(gaps[worst]), f"{names[worst]}: {p[worst]:.6g} vs {r[worst]:.6g}"


def compare(program, reference, limits):
    """The run's compared numbers, each beside its limit.

    ``program`` / ``reference``: ``{"losses": (n,), "first_grad": norms,
    "update": norms}``. Returns ``[{name, value, limit, ok, at}]``; a number
    that is not finite fails."""
    rows = []
    lp, lr = np.asarray(program["losses"], np.float64), np.asarray(reference["losses"], np.float64)
    for i, (a, b) in enumerate(zip(lp, lr)):
        rows.append({"name": f"loss_gap.step{i + 1}", "value": abs(a - b) / abs(b),
                     "limit": limits["loss_gap"], "at": f"{a:.6f} vs {b:.6f}"})
    for key in ("first_grad", "update"):
        gap, leaf = worst_leaf_gap(program[key], reference[key])
        rows.append({"name": f"{key}_norm_gap", "value": gap,
                     "limit": limits[f"{key}_norm_gap"], "at": leaf})
    for row in rows:
        row["ok"] = bool(np.isfinite(row["value"]) and row["value"] <= row["limit"])
    return rows
