"""The comparison that decides ``correct``.

The system's loss function, its compiled step and (on several chips) its
gradient reduction are held to the plain float32 reference of the
configuration's family, at the published widths, on seeded data, during
set-up. After the window every loss must be finite and every chip's copy
of the parameters bit-identical.

Tolerances belong to the family's reference
(``reference/<family>.py:TOLERANCES``, with their reasons there), because
how far bf16 compute strays from float32 is a property of the model: a
language model's gradient keeps to 1%, a deep BatchNorm/ReLU network's at
random weights does not. Errors are relative; for a gradient leaf
``|g_sys - g_ref|_2 / max(|g_ref|_2, LEAF_FLOOR * |g_ref over all
leaves|_2 / sqrt(n_leaves))``: a leaf whose reference gradient is a
rounding-sized sliver of the tree's is judged against that sliver of the
tree's norm and not against itself.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LEAF_FLOOR = 0.05


@jax.jit
def _norms(sys_grads, ref_grads):
    diff = jax.tree.map(
        lambda a, b: jnp.sqrt(jnp.sum(
            (a.astype(jnp.float32) - b.astype(jnp.float32)) ** 2)),
        sys_grads, ref_grads)
    ref = jax.tree.map(
        lambda b: jnp.sqrt(jnp.sum(b.astype(jnp.float32) ** 2)), ref_grads)
    return diff, ref


def compare_loss(what: str, got: float, want: float, tol: dict) -> dict:
    err = abs(got - want) / max(abs(want), 1e-30)
    return {"check": what, "got": got, "want": want, "rel_err": err,
            "tol": tol["loss_rtol"],
            "ok": bool(math.isfinite(got) and err <= tol["loss_rtol"])}


def compare_grads(what: str, sys_grads, ref_grads, tol: dict) -> dict:
    diff, ref = _norms(sys_grads, ref_grads)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(ref)[0]]
    d = np.array([float(x) for x in jax.tree.leaves(diff)])
    r = np.array([float(x) for x in jax.tree.leaves(ref)])
    tree_ref = math.sqrt(float((r ** 2).sum()))
    tree_err = math.sqrt(float((d ** 2).sum())) / max(tree_ref, 1e-30)
    floor = LEAF_FLOOR * tree_ref / math.sqrt(len(r))
    leaf_err = d / np.maximum(r, max(floor, 1e-30))
    worst = int(np.argmax(leaf_err))
    ok = (np.isfinite(d).all() and tree_err <= tol["grad_tree_rtol"]
          and float(leaf_err[worst]) <= tol["grad_leaf_rtol"])
    return {"check": what, "tree_rel_err": tree_err,
            "worst_leaf": paths[worst],
            "worst_leaf_rel_err": float(leaf_err[worst]),
            "tol": [tol["grad_tree_rtol"], tol["grad_leaf_rtol"]],
            "ok": bool(ok)}


def replicas_identical(params, mesh, axes) -> bool:
    """Whether every device of the mesh holds bit-identical parameters:
    the bits of each leaf, as integers, have equal maximum and minimum
    over the mesh."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    def local(tree):
        same = jnp.bool_(True)
        for leaf in jax.tree.leaves(tree):
            bits = lax.bitcast_convert_type(
                leaf, jnp.dtype(f"int{leaf.dtype.itemsize * 8}"))
            same &= jnp.all(lax.pmax(bits, axes) == lax.pmin(bits, axes))
        return same

    fn = jax.jit(shard_map(local, mesh=mesh, in_specs=(P(),), out_specs=P(),
                           check_vma=False))
    return bool(fn(params))
