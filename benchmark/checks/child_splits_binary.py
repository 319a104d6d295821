"""The first tree's SECOND level against the plain binary log-loss reference:
the recorded split of each child of the root (feature, threshold bin, gain,
rows, rows sent left) against the best split a numpy histogram of that
child's own rows finds over every column. Rows exact, gain to ``gain_rtol``.

The root's histogram is one pass over all rows; a child's is the first built
from a partitioned segment (the smaller child) or by subtraction from the
pooled parent (the larger): what ``root_split_binary`` cannot see.

With thousands of candidates two gains may tie inside the tolerance. Another
(feature, bin) than numpy's is accepted only where numpy's OWN gain for the
program's (feature, bin) is within ``gain_rtol`` of numpy's best; the program's
gain and left rows are then held to numpy's for that split, and the detail
line says ``tie``."""
import numpy as np


def run(args, c):
    from reference import binary_children
    rtol = float(args["gain_rtol"])
    bounds = [np.asarray(m.upper_bounds, np.float64) for m in c["binned"].bin_mappers]
    t = c["trees"][0]
    if t["num_leaves"] < 2:
        return False, "the first tree has no split"

    def bin_of(node):
        f = int(t["split_feature"][node])
        return f, int(np.searchsorted(bounds[f][:-1], float(t["threshold"][node]), side="left"))

    def rows_of(child):
        return int(t["internal_count"][child] if child >= 0 else t["leaf_count"][~child])

    kids = [int(t["left_child"][0]), int(t["right_child"][0])]
    root_f, root_b = bin_of(0)
    probe = [(side,) + bin_of(k) for side, k in enumerate(kids) if k >= 0]
    children, probed = binary_children.child_splits(
        c["X"], c["label"], bounds, root_f, root_b,
        min_data_in_leaf=c["params"].get("min_data_in_leaf", 20),
        min_sum_hessian=c["params"].get("min_sum_hessian_in_leaf", 1e-3), probe=probe)
    ok, compared, worst, said = True, 0, 0.0, []
    for side, k in enumerate(kids):
        ref, name = children[side], ("left", "right")[side]
        if rows_of(k) != ref["rows"]:
            ok = False
            said.append("%s: %d rows, numpy %d" % (name, rows_of(k), ref["rows"]))
            continue
        if k < 0:
            said.append("%s: %d rows, left a leaf" % (name, ref["rows"]))
            continue
        compared += 1
        pf, pb = bin_of(k)
        pgain, pleft = float(t["split_gain"][k]), rows_of(int(t["left_child"][k]))
        if ref["feature"] is None:
            ok = False
            said.append("%s: program split feature %d bin %d, numpy allows no split" % (name, pf, pb))
            continue
        gain, left, tie = ref["gain"], ref["left_rows"], ""
        if (pf, pb) != (ref["feature"], ref["bin"]):
            at = probed.get((side, pf, pb))
            if at is None or abs(at[0] - gain) > rtol * abs(gain):
                ok = False
                said.append("%s: program feature %d bin %d (gain %.6g), numpy feature %d bin %d (gain %.6g; "
                            "its gain for the program's split %s)" % (
                                name, pf, pb, pgain, ref["feature"], ref["bin"], gain,
                                "none" if at is None else "%.6g" % at[0]))
                continue
            tie = " tie: numpy's best is feature %d bin %d, gain %.9g against %.9g here" % (
                ref["feature"], ref["bin"], gain, at[0])
            gain, left = at
        dist = abs(gain - pgain) / abs(gain)
        worst = max(worst, dist)
        good = dist <= rtol and pleft == left
        ok = ok and good
        said.append("%s: %d rows, feature %d bin %d, gain %.6g against numpy %.6g (off %.3g), left rows %d against %d%s" % (
            name, ref["rows"], pf, pb, pgain, gain, dist, pleft, left, tie))
    need = int(args.get("min_children", 2))
    if compared < need:
        ok = False
        said.append("%d children of the root were split, want %d" % (compared, need))
    return ok, "root feature %d bin %d; %s; worst gain distance %.3g (limit %g)" % (
        root_f, root_b, "; ".join(said), worst, rtol)
