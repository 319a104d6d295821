"""The first tree's root split (feature, bin, gain) against the plain binary
log-loss reference over the raw CSC columns of every used feature: the
program reaches it through the bundled histogram and its per-feature view,
the reference never bundles."""
import numpy as np


def run(args, c):
    from reference import efb
    gm = efb.group_map_of(c["binned"])
    j, b, gain = efb.root_split(
        efb.csc_of(c["X"]), c["label"], gm,
        min_data_in_leaf=c["params"].get("min_data_in_leaf", 20),
        min_sum_hessian=c["params"].get("min_sum_hessian_in_leaf", 1e-3))
    f = int(gm.column[j])
    t = c["trees"][0]
    pf, pthr, pgain = int(t["split_feature"][0]), float(t["threshold"][0]), float(t["split_gain"][0])
    pj = int(np.flatnonzero(gm.column == pf)[0])
    pb = int(np.searchsorted(gm.bounds[pj][:-1], pthr, side="left"))
    ok = (f, b) == (pf, pb) and abs(gain - pgain) <= float(args["gain_rtol"]) * abs(gain)
    return ok, "program column %d bin %d (gain %.6g), numpy column %d bin %d (gain %.6g)" % (
        pf, pb, pgain, f, b, gain)
