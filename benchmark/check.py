"""Decide ``correct``: the system against the configuration's plain reference,
a learnable loss that stayed finite, and no compilation inside the window.

The comparison runs after the window has closed and the peak memory has been
read, so that the float32 reference never sets the peak, and its time is in
neither ``setup_s`` nor the window.
"""

from __future__ import annotations

import math

import jax
import numpy as np

from benchmark import cells


def _update_norm(new, old) -> float:
    return math.sqrt(sum(
        float(np.sum(np.square(a.astype(np.float64) - b.astype(np.float64))))
        for a, b in zip(jax.tree_util.tree_leaves(new), jax.tree_util.tree_leaves(old))
    ))


def against_reference(cell, mesh, seed: int, feed) -> dict:
    """``check.steps`` training steps of the system (through
    ``DistributedDataParallel`` on the cell's mesh, dropout and flip off)
    from the same seeded initialisation and the same seeded batches as the
    plain reference on one worker; per step the loss and the global norm of
    the parameter change, each within the configuration's tolerance."""
    cfg, chk = cell.config, cell.config["check"]
    if chk["batch"] % cell.chips:
        raise ValueError(f"check batch {chk['batch']} does not divide over {cell.chips} chips")
    system = cells.load_system(cell)
    batches = feed.sample_batches(chk["steps"], chk["batch"])
    model, ddp = system.build_ddp(cell, mesh, check=True)
    variables = system.init_variables(model, cfg, seed)
    init_params, init_mstate = jax.device_get(variables)
    state = system.init_state(model, ddp, cfg, seed, variables)
    losses, norms, prev = [], [], init_params
    ones = system.unit_weights(cfg, chk["batch"])
    for batch in batches:
        state, m = ddp.train_step(state, ddp.shard((*batch, ones)))
        m, new = jax.device_get((m, state.params))
        losses.append(float(np.sum(m["loss_sum"]) / np.sum(m["n"])))
        norms.append(_update_norm(new, prev))
        prev = new
    del state
    reference = cells.load_module("reference", cell.config_name, cell.root)
    ref_losses, ref_norms = reference.train_steps(cfg, init_params, init_mstate, batches)

    def worst(ours, theirs):
        return max(abs(a - b) / abs(b) for a, b in zip(ours, theirs))

    loss_err, norm_err = worst(losses, ref_losses), worst(norms, ref_norms)
    return {
        "ok": bool(loss_err <= chk["loss_rtol"] and norm_err <= chk["update_norm_rtol"]),
        "loss": losses, "reference_loss": ref_losses, "loss_rel_err": loss_err,
        "update_norm": norms, "reference_update_norm": ref_norms,
        "update_norm_rel_err": norm_err,
        "loss_rtol": chk["loss_rtol"], "update_norm_rtol": chk["update_norm_rtol"],
    }


def window_losses(window) -> dict:
    """Every loss read back in the window is finite and the last read-back's
    mean is below the first's (the seeded data is learnable)."""
    means = [s / n if n else float("nan") for s, n in window["readbacks"]]
    finite = [math.isfinite(m) for m in means]
    return {
        "ok": bool(len(means) >= 2 and all(finite) and means[-1] < means[0]),
        "first": means[0] if means else None,
        "last": means[-1] if means else None,
        "readbacks": len(means),
        "non_finite": finite.count(False),
    }
