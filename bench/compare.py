"""The numbers that decide `correct` for a training cell.

The program's first steps are compared with the plain reference's, step
for step on the same batches:

* ``loss_gap``: the largest relative gap of the loss over the steps, and
  ``first_loss_gap`` the first step's alone;
* ``grad_norm_gap``: the first gradient as the optimizer got it (clipped),
  read back from the program's Adam state after one step (m1 / (1 - b1));
  per leaf, the gap between the program's norm and the reference's,
  against the larger of the reference's norm of that leaf and of the
  median leaf; the worst leaf counts;
* ``grad_dir_gap``: one less the cosine between the program's and the
  reference's first gradient, per leaf; the worst leaf counts;
* ``update_norm_gap``: as ``grad_norm_gap`` for each leaf's change over
  the steps.

Leaves whose first reference gradient is under a thousandth of the median
leaf's move under Adam by round-off alone, and are left out of the update
and direction gaps.

A policy served from a slot table (R2D2) is compared too: every call made
on the initial weights is replayed lane by lane through the reference
(`policy_numbers`). Each cell's workload file says which of these numbers
it compares, by giving each a limit.
"""

import numpy as np

FLAT_GRAD_SHARE = 1e-3


def leaves(tree, prefix=()):
    """{path: float64 vector} of a nested dict of arrays."""
    if isinstance(tree, dict):
        out = {}
        for k in sorted(tree):
            out.update(leaves(tree[k], prefix + (k,)))
        return out
    return {"/".join(prefix): np.asarray(tree, np.float64).ravel()}


def leaf_norms(tree):
    return {k: float(np.linalg.norm(v)) for k, v in leaves(tree).items()}


def tree_sub(a, b):
    if isinstance(a, dict):
        return {k: tree_sub(a[k], b[k]) for k in a}
    return np.asarray(a, np.float64) - np.asarray(b, np.float64)


def _worst_gap(prog: dict, ref: dict, keep=None):
    med = float(np.median(list(ref.values())))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in ref if keep is None or k in keep}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def training_numbers(prog: dict, ref: dict, b1: float) -> dict:
    """``prog``: losses, params0, m1 (Adam's first moment after step 1)
    and params (after the last step). ``ref``: what `reference.common.
    train` returns. Returns {name: (value, detail)}."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss = np.abs(lp - lr) / np.maximum(np.abs(lr), 1e-30)
    steps = " ".join(f"{x:.3g}" for x in loss)

    g_ref = leaves(ref["grads"])
    g_prog = {k: v / (1.0 - b1) for k, v in leaves(prog["m1"]).items()}
    n_ref = {k: float(np.linalg.norm(v)) for k, v in g_ref.items()}
    n_prog = {k: float(np.linalg.norm(v)) for k, v in g_prog.items()}
    grad, grad_leaf = _worst_gap(n_prog, n_ref)

    med_g = float(np.median(list(n_ref.values())))
    moving = {k for k, v in n_ref.items() if v >= FLAT_GRAD_SHARE * med_g}
    cos_gap = {k: 1.0 - float(np.dot(g_prog[k], g_ref[k]))
               / max(n_prog[k] * n_ref[k], 1e-300) for k in moving}
    dir_leaf = max(cos_gap, key=cos_gap.get)
    u_ref = leaf_norms(tree_sub(ref["params"][-1], ref["params"][0]))
    u_prog = leaf_norms(tree_sub(prog["params"], prog["params0"]))
    upd, upd_leaf = _worst_gap(u_prog, u_ref, keep=moving)
    left_out = sorted(set(n_ref) - moving)
    return {"loss_gap": (float(loss.max()), f"steps {steps}"),
            "first_loss_gap": (float(loss[0]), f"steps {steps}"),
            "grad_norm_gap": (grad, grad_leaf),
            "grad_dir_gap": (cos_gap[dir_leaf], dir_leaf),
            "update_norm_gap": (upd, upd_leaf + (
                f"; left out {left_out}" if left_out else ""))}


def readings(prog, batches, reference, b1, controls=False) -> dict:
    """The training numbers of the program against ``reference(batches,
    dtype)``; with ``controls`` also those of the bfloat16 control and of
    the reference fed half of each batch (the half-batch fault), each in
    the program's place."""
    ref = reference(batches)
    out = {"program": training_numbers(prog, ref, b1), "reference": ref}
    if controls:
        out["control"] = training_numbers(
            as_prog(reference(batches, "bfloat16"), b1), ref, b1)
        out["half_batch"] = training_numbers(
            as_prog(reference(half(batches)), b1), ref, b1)
    return out


def lane_histories(calls, lane_block=16, step_pad=64):
    """The policy calls (ids, obs, actions), in order, laid out per lane.

    Returns ``obs`` (L, T, ...): each lane's observations in the order it
    was served, zero-padded to multiples of ``lane_block`` lanes and
    ``step_pad`` steps (so the reference's programs keep their shapes from
    run to run); ``lane`` and ``step``: where each served position of each
    call sits in it; ``actions``: the served actions; ``sizes``: each
    call's number of lanes."""
    ids = np.concatenate([c[0] for c in calls]).astype(np.int64)
    lanes = {x: i for i, x in enumerate(sorted(set(ids.tolist())))}
    lane = np.array([lanes[x] for x in ids.tolist()], np.int64)
    step = np.zeros(len(lane), np.int64)
    seen = np.zeros(len(lanes), np.int64)
    for j, i in enumerate(lane):
        step[j], seen[i] = seen[i], seen[i] + 1
    pad = -(-max(seen) // step_pad) * step_pad
    n_lanes = -(-len(lanes) // lane_block) * lane_block
    frame = calls[0][1].shape[1:]
    obs = np.zeros((n_lanes, pad) + frame, calls[0][1].dtype)
    obs[lane, step] = np.concatenate([c[1] for c in calls])
    return {"obs": obs, "lane": lane, "step": step,
            "actions": np.concatenate([c[2] for c in calls]).astype(np.int64),
            "sizes": [len(c[0]) for c in calls]}


def exploration(seed, sizes, epsilon, num_actions):
    """The ε-greedy draws of a policy that makes, per call of ``n`` lanes,
    ``rng.random(n) < epsilon`` and then ``rng.integers(0, A, n)`` from one
    ``numpy.random.default_rng(seed)``: (explored mask, drawn actions)."""
    rng = np.random.default_rng(seed)
    explore, drawn = [], []
    for n in sizes:
        explore.append(rng.random(n) < epsilon)
        drawn.append(rng.integers(0, num_actions, n))
    return np.concatenate(explore), np.concatenate(drawn)


def policy_numbers(actions, q_ref, explore, drawn) -> dict:
    """The served actions against the reference's Q at the same positions.

    * ``policy_gap``: the widest gap by which a greedy action's reference
      Q lies below the reference's best, over the median spread (best
      less worst) of the reference's Q;
    * ``explore_draw_gap``: the explored positions whose action is not the
      one drawn (exact: limit 0).
    """
    n = len(actions)
    greedy = ~explore
    spread = float(np.median(q_ref.max(1) - q_ref.min(1)))
    gap = (q_ref.max(1) - q_ref[np.arange(n), actions]) / max(spread, 1e-30)
    g = np.where(greedy, gap, -np.inf)
    worst = int(np.argmax(g))
    misses = int(np.sum(greedy & (gap > 0)))
    wrong = int(np.sum(actions[explore] != drawn[explore]))
    return {"policy_gap": (float(g[worst]), f"position {worst} of {n}; "
                           f"{misses} of {int(greedy.sum())} greedy actions "
                           f"off the reference's best"),
            "explore_draw_gap": (float(wrong),
                                 f"of {int(explore.sum())} explored")}


def policy_readings(served, q_fn, seed, epsilon, controls=False) -> dict:
    """The policy numbers of the served actions against the float32
    reference ``q_fn()``; with ``controls`` also those of the bfloat16
    control (``q_fn(dtype="bfloat16")``) and of two slot-table faults
    planted in the reference (``q_fn(carry=...)``: each lane's state taken
    from its neighbour's row, ``slot_shuffle``; the state never written
    back, ``state_stale``), each acting greedily where the program did and
    taking the drawn action where it explored."""
    at = (served["lane"], served["step"])
    q = q_fn()[at]
    explore, drawn = exploration(seed, served["sizes"], epsilon, q.shape[1])
    out = {"program": policy_numbers(served["actions"], q, explore, drawn)}
    if controls:
        for kind, kw in (("control", {"dtype": "bfloat16"}),
                         ("slot_shuffle", {"carry": "shuffle"}),
                         ("state_stale", {"carry": "stale"})):
            greedy = q_fn(**kw)[at].argmax(1)
            out[kind] = policy_numbers(np.where(explore, drawn, greedy), q,
                                       explore, drawn)
    return out


def half(batches):
    """The batches with the second half of their rows left out."""
    return [{k: v[: len(v) // 2] for k, v in b.items()} for b in batches]


def as_prog(ref: dict, b1: float) -> dict:
    """A reference run put in the program's place (the control, or a
    planted fault), in the form `training_numbers` reads."""
    m1 = _scale(ref["grads"], 1.0 - b1)
    return {"losses": ref["losses"], "params0": ref["params"][0],
            "m1": m1, "params": ref["params"][-1]}


def _scale(tree, s):
    if isinstance(tree, dict):
        return {k: _scale(v, s) for k, v in tree.items()}
    return np.asarray(tree, np.float64) * s
