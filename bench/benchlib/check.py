"""The comparison that decides ``correct`` for a training cell.

The program is driven from the seed through its first three updates, by
the same call and on the same object that the measured window then uses.
The plain reference (the configuration's family's ``train``) follows the
same three updates from the same seed. Three numbers are compared, each
against a limit the cell's file states:

``loss_gap``
    The relative gap between the program's loss and the reference's in the
    first update, |L_p - L_r| / |L_r| (infinite where any of the
    program's losses is not finite). The later updates' losses are not
    compared: from the second update on, the two sides act with parameters
    that differ in rounding, a few categorical draws flip, the games go on
    differently, and the relative gap of those losses swings from 1e-5 to
    0.9 from seed to seed on a sound program on a TPU v5e. The later
    updates show in ``change_gap``.
``grad_gap``
    The first gradient as the optimizer got it (after clipping). The
    program's is read back from RMSProp's accumulator after one update,
    which then holds (1 - decay) g^2. Per leaf, the gap between the two
    norms, |‖g_p‖ - ‖g_r‖|, over the larger of the reference leaf's norm
    and the median leaf's; the worst leaf counts.
``change_gap``
    The parameters' change over the three updates, ‖p_3 - p_0‖ per leaf,
    compared the same way. Leaves whose reference gradient norm is under a
    thousandth of the median leaf's are left out: they move by round-off
    alone.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
# a leaf whose reference gradient is below this share of the median leaf's
# is left out of change_gap
STILL_LEAF = 1e-3


def _flat(tree) -> Dict[str, np.ndarray]:
    import jax

    return {jax.tree_util.keystr(path): np.asarray(leaf, np.float64)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def _norms(tree) -> Dict[str, float]:
    return {k: float(np.linalg.norm(v)) for k, v in _flat(tree).items()}


def _worst_gap(prog: Dict[str, float], ref: Dict[str, float],
               leaves: List[str]) -> float:
    floor = float(np.median([ref[k] for k in leaves])) if leaves else 0.0
    worst = 0.0
    for k in leaves:
        denom = max(ref[k], floor)
        gap = abs(prog[k] - ref[k]) / denom if denom > 0 else (
            0.0 if prog[k] == 0 else math.inf)
        if not math.isfinite(prog[k]):
            gap = math.inf
        worst = max(worst, gap)
    return worst


def program_grad_norms(sq_after_one, decay: float) -> Dict[str, float]:
    """Per-leaf gradient norms from RMSProp's accumulator after one update
    from zero: sq = (1 - decay) g^2."""
    return {k: float(math.sqrt(np.sum(v) / (1.0 - decay)))
            for k, v in _flat(sq_after_one).items()}


def readings(prog: dict, ref: dict, decay: float) -> Dict[str, float]:
    """prog: ``losses``, ``sq1``, ``params0``, ``params``; ref: the dict
    the family's reference ``train`` returns. Returns the three numbers."""
    if len(prog["losses"]) != len(ref["losses"]):
        raise ValueError("program and reference ran different step counts")
    lp, lr = prog["losses"][0], ref["losses"][0]
    loss_gap = abs(lp - lr) / abs(lr) if lr != 0 else abs(lp)
    if not all(math.isfinite(x) for x in prog["losses"]):
        loss_gap = math.inf

    g_prog = program_grad_norms(prog["sq1"], decay)
    g_ref = _norms(ref["grads"])
    if set(g_prog) != set(g_ref):
        raise ValueError(f"parameter trees differ: program {sorted(g_prog)}, "
                         f"reference {sorted(g_ref)}")
    leaves = sorted(g_ref)
    grad_gap = _worst_gap(g_prog, g_ref, leaves)

    def change(before, after):
        b, a = _flat(before), _flat(after)
        return {k: float(np.linalg.norm(a[k] - b[k])) for k in b}

    c_prog = change(prog["params0"], prog["params"])
    c_ref = change(ref["params0"], ref["params"])
    median_g = float(np.median([g_ref[k] for k in leaves]))
    moving = [k for k in leaves if g_ref[k] >= STILL_LEAF * median_g]
    change_gap = _worst_gap(c_prog, c_ref, moving)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """True when every number is finite and within its limit."""
    return all(math.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in NUMBERS)
