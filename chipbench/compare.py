"""The numbers that decide ``correct``, each a gap to the plain
reference (see PERF.md for the readings each limit was set from)."""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax
import numpy as np

# a leaf whose first reference gradient is under this share of the
# median leaf's moves by round-off alone and is left out of the norms
NEGLIGIBLE_GRAD = 1e-3


def leaf_norms(tree) -> List[float]:
    return [float(np.linalg.norm(np.asarray(x, np.float64)))
            for x in jax.tree.leaves(tree)]


def norm_gaps(prog, ref, keep: Sequence[bool]) -> List[float]:
    """Each leaf's gap between the program's norm and the reference's,
    over the reference's norm of that leaf or of the median leaf,
    whichever is larger (0 for a leaf left out)."""
    p, r = leaf_norms(prog), leaf_norms(ref)
    if len(p) != len(r):
        return [float("inf")]
    med = float(np.median([x for x, k in zip(r, keep) if k]))
    return [abs(a - b) / max(b, med) if k else 0.0
            for a, b, k in zip(p, r, keep)]


def _kept(ref_grad) -> List[bool]:
    g = leaf_norms(ref_grad)
    return [x >= NEGLIGIBLE_GRAD * float(np.median(g)) for x in g]


def _loss_gap(losses: Sequence[float], ref_losses: Sequence[float]) -> float:
    return float(max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)))


def train_readings(losses: Sequence[float], grad, delta,
                   ref_losses: Sequence[float], ref_grad, ref_delta
                   ) -> Dict[str, float]:
    """Loss of each step, the first gradient's norms and the norms of
    the weights' change over the steps, against the reference."""
    if len(losses) != len(ref_losses) or not np.all(np.isfinite(losses)):
        return {"loss_gap": float("inf"), "grad1_gap": float("inf"),
                "delta_gap": float("inf"), "delta_median_gap": float("inf")}
    keep = _kept(ref_grad)
    grad_gaps = norm_gaps(grad, ref_grad, keep)
    delta_gaps = norm_gaps(delta, ref_delta, keep)
    names = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_leaves_with_path(ref_grad)]
    return {
        "loss_gap": _loss_gap(losses, ref_losses),
        "grad1_gap": float(max(grad_gaps)),
        "delta_gap": float(max(delta_gaps)),
        "delta_median_gap": float(np.median(
            [x for x, k in zip(delta_gaps, keep) if k])),
        # where the worst gaps lie, and what was left out (not compared)
        "grad1_worst_leaf": names[int(np.argmax(grad_gaps))],
        "delta_worst_leaf": names[int(np.argmax(delta_gaps))],
        "leaves_left_out": [n for n, k in zip(names, keep) if not k]}


def window_readings(losses: Sequence[float], grads: Dict[int, Any],
                    ref_losses: Sequence[float], ref_grads: Dict[int, Any]
                    ) -> Dict[str, Any]:
    """The loss of every step after the first ones, and the gradient's
    norms at each later step compared (by the worst leaf, as for the
    first gradient), against the reference; a step the program did not
    reach reads as infinite."""
    if len(losses) != len(ref_losses) or not np.all(np.isfinite(losses)) \
            or set(grads) != set(ref_grads):
        return {"window_loss_gap": float("inf"),
                "window_grad_gap": float("inf")}
    by_step = {str(k): float(max(norm_gaps(grads[k], ref_grads[k],
                                           _kept(ref_grads[k]))))
               for k in sorted(ref_grads)}
    return {"window_loss_gap": _loss_gap(losses, ref_losses),
            "window_grad_gap": max(by_step.values()),
            "window_grad_gap_by_step": by_step}


def score_gap(served: np.ndarray, ref: np.ndarray) -> float:
    """Widest gap between a served score and the reference's, over the
    root mean square of the reference's scores."""
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    if served.shape != ref.shape or not np.all(np.isfinite(served)):
        return float("inf")
    return float(np.abs(served - ref).max() / np.sqrt(np.mean(ref * ref)))


def tree_sub(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                        - np.asarray(y, np.float64), a, b)


def tree_scale(a, s: float):
    return jax.tree.map(lambda x: np.asarray(x, np.float64) * s, a)

