"""The first tree's root split (feature, bin, gain) against the plain binary
log-loss reference, which computes the gradients at the initial score itself,
builds the histograms and scans them."""
import numpy as np


def run(args, c):
    from reference import binary_root
    bounds = [np.asarray(m.upper_bounds, np.float64) for m in c["binned"].bin_mappers]
    f, b, gain = binary_root.root_split(
        c["X"], c["label"], bounds,
        min_data_in_leaf=c["params"].get("min_data_in_leaf", 20),
        min_sum_hessian=c["params"].get("min_sum_hessian_in_leaf", 1e-3))
    t = c["trees"][0]
    pf, pthr, pgain = int(t["split_feature"][0]), float(t["threshold"][0]), float(t["split_gain"][0])
    pb = int(np.searchsorted(bounds[pf][:-1], pthr, side="left"))
    ok = (f, b) == (pf, pb) and abs(gain - pgain) <= float(args["gain_rtol"]) * abs(gain)
    return ok, "program feature %d bin %d (gain %.6g), numpy feature %d bin %d (gain %.6g)" % (
        pf, pb, pgain, f, b, gain)
