"""The roots of the first trees (``trees``, by index) against the plain
reference's search (``reference/tweedie.py``): tree i's root over iteration
i's bag and tree i's columns, at the scores that the trees before it give
those rows in a walk of the model text (0 for the first tree, whose
``boost_from_average`` is false, which the check requires; so the second
tree's root compares the log link's e^{(1-rho)s} and e^{(2-rho)s} terms).
Feature, threshold bin or left set of bins, default_left, rows and left
rows exact, gain to ``gain_rtol`` of the node's terms; another split passes
only as a tie inside ``gain_rtol`` by the reference's own gain for it, and
the line says ``tie`` (``tweedie.judge``).

The detail also gives the gain distance the reference itself would read
with each row's g and h rounded to bfloat16 (one term a row, the
histogram precision below the program's): ``gain_rtol`` lies between the
program's reading and that one."""
import numpy as np


def run(args, c):
    import ml_dtypes
    from reference import tweedie
    params = c["params"]
    if params.get("boost_from_average", True):
        return False, "this check wants boost_from_average false (the first tree at score 0)"
    rtol = float(args["gain_rtol"])
    ok, said = True, []
    for i in args["trees"]:
        t = c["trees"][i]
        if t["num_leaves"] < 2:
            return False, "tree %d has no split" % i
        d = tweedie.tree_inputs(c, i)
        cols, subset, g, h = d["cols"], d["subset"], d["g"], d["h"]
        hists = tweedie.subset_histograms(d["fb"], g, h, cols, subset)
        s, best = tweedie.best_split(hists, len(d["inbag"]), g.sum(), h.sum(), cols, subset,
                                     params)
        prog = tweedie.program_split(t, 0, cols)
        good, dist, words = tweedie.judge(prog, s, hists, best, subset, rtol, cols)
        ok = ok and good
        if np.isfinite(dist):
            f = prog["feature"]
            col = cols[f]
            fb = d["fb"][:, [int(np.searchsorted(np.flatnonzero(subset), f))]]
            g16, h16 = (a.astype(ml_dtypes.bfloat16).astype(np.float64) for a in (g, h))
            s16 = tweedie.Search(len(d["inbag"]), g16.sum(), h16.sum(), params)
            hist16 = tweedie.histograms(fb, g16, h16, [col])[0]
            gain16 = s16.gain_of(col, *hist16, prog["left"], prog["default_left"])[0]
            gain = s.gain_of(col, *hists[f], prog["left"], prog["default_left"])[0]
            words += "; bfloat16 terms would be off %.3g" % (abs(gain16 - gain) / s.scale(gain))
        said.append("tree %d root over its bag: %s" % (i, words))
    return ok, "%s (gain limit %g)" % ("; ".join(said), rtol)
