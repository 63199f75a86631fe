"""The comparison that decides ``correct``: each number beside its limit.

A check is {"name", "value", "limit"}; a run is correct when every value is
finite and at or under its limit, and nothing failed.
"""

import math
import statistics


def leaf_gaps(prog, ref, paths, skip=()):
    """The gap between the program's norm and the reference's, leaf by leaf,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but nought). ``prog`` maps a
    leaf's path to its norm; ``ref`` lists the norms in the order of
    ``paths``. -> {path: gap}, the leaves in ``skip`` left out."""
    med = statistics.median(ref)
    return {path: abs(prog[path] - r) / max(r, med)
            for path, r in zip(paths, ref) if path not in skip}


def worst_leaf_gap(prog, ref, paths, skip=()):
    """-> (worst gap, its leaf). A NaN is the worst there is."""
    worst, at = 0.0, None
    for path, gap in leaf_gaps(prog, ref, paths, skip).items():
        if math.isnan(gap):
            return gap, path
        if gap > worst:
            worst, at = gap, path
    return worst, at


def median_leaf_gap(prog, ref, paths, skip=()):
    """The median over the leaves of the same gaps: steady from seed to
    seed where the worst leaf is the noise of one small leaf."""
    gaps = list(leaf_gaps(prog, ref, paths, skip).values())
    if any(math.isnan(g) for g in gaps):
        return math.nan
    return statistics.median(gaps)


def top_leaves(prog, ref, paths, skip=(), n=5):
    """The n widest gaps with their leaves, for the look a reader takes."""
    gaps = leaf_gaps(prog, ref, paths, skip)
    return sorted(([g, p] for p, g in gaps.items()), reverse=True,
                  key=lambda x: (math.isnan(x[0]), x[0]))[:n]


def still_leaves(ref_grad, paths, share=1e-3):
    """Leaves whose gradient is nought to rounding in the reference (under a
    thousandth of the median leaf's): under Adam they move by round-off
    alone, so the parameters' change is not compared on them."""
    med = statistics.median(ref_grad)
    return {p for p, g in zip(paths, ref_grad) if g < share * med}


def training(prog, ref, limits):
    """``prog``: losses [3], grad {path: norm}, delta {path: norm}. ``ref``:
    what the reference's ``train`` returns. Every number below is worked
    out and kept in the notes; those that the cell's limits file names are
    compared, in its order. -> (checks, notes)."""
    paths = ref["paths"]
    grads, deltas = list(ref["grad_norms"]), list(ref["delta_norms"])
    skip = still_leaves(grads, paths)
    numbers = {f"loss_step{i + 1}_rel": abs(a - b) / abs(b)
               for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]))}
    numbers["grad1_worst_leaf"], g_at = worst_leaf_gap(prog["grad"], grads, paths)
    numbers["grad1_median_leaf"] = median_leaf_gap(prog["grad"], grads, paths)
    numbers["delta3_worst_leaf"], d_at = worst_leaf_gap(
        prog["delta"], deltas, paths, skip)
    numbers["delta3_median_leaf"] = median_leaf_gap(
        prog["delta"], deltas, paths, skip)
    checks = [{"name": name, "value": numbers[name], "limit": limit}
              for name, limit in limits.items()]
    return checks, {"numbers": numbers, "grad1_leaf": g_at,
                    "delta3_leaf": d_at, "still_leaves": len(skip),
                    "grad1_top": top_leaves(prog["grad"], grads, paths),
                    "delta3_top": top_leaves(prog["delta"], deltas, paths, skip)}


def serving(gaps, limits):
    worst = max((float(g.max()) for g in gaps if len(g)), default=math.nan)
    return [{"name": "served_logit_gap_max", "value": worst,
             "limit": limits["served_logit_gap_max"]}]


def verdict(checks, failed=0):
    ok = failed == 0 and bool(checks)
    for c in checks:
        v = c["value"]
        if not (isinstance(v, (int, float)) and math.isfinite(v)
                and v <= c["limit"]):
            ok = False
    return ok


def as_pairs(checks):
    """Short plain names, each with its number and its limit."""
    return {c["name"]: [c["value"], c["limit"]] for c in checks}
