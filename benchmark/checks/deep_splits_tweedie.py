"""Tree ``tree``'s splits past its first ``past`` against the plain
reference (``reference/tweedie.py``): node k of the model text is the tree's
(k+1)-th split, so these are the splits made after leaf ``past`` - 1 had
been made, and they split the leaves whose ids, histogram pool rows and
state lie past the 8-bit range (for ``past`` 256). Each such node holds the
rows of the tree's bag that a walk of the model text sends through it, and
its recorded split is held to the reference's best over those rows and the
tree's columns, from float64 histograms (a leaf's from its rows, a node's
the sum of its children's), at the scores the trees before it give:
feature, threshold bin or left set of bins, default_left, the node's rows
and its left child's exact, gain and a tie's gap to ``gain_rtol`` of the
node's terms (``tweedie.judge``). A leaf split on another leaf's
histogram finds another best split over its own rows, or a gain off by far
more."""
import numpy as np


def run(args, c):
    from reference import model_text, tweedie
    rtol, past, i = float(args["gain_rtol"]), int(args["past"]), int(args["tree"])
    t = c["trees"][i]
    nodes = list(range(past, t["num_leaves"] - 1))
    if not nodes:
        return False, "tree %d has %d splits, want more than %d" % (i, t["num_leaves"] - 1, past)
    d = tweedie.tree_inputs(c, i)
    cols, subset = d["cols"], d["subset"]
    of = tweedie.node_histograms(t, model_text.route(t, c["X"][d["inbag"]]), d["fb"], d["g"],
                                 d["h"], cols, subset)
    ok, worst, finite, ties, bad = True, 0.0, 0.0, 0, []
    for k in nodes:
        hists, n, G, H = of(k)
        if H <= 0.0:
            ok = False
            bad.append("node %d: %d rows of the bag reach it, with hessian %g (program %d rows)"
                       % (k, n, H, int(t["internal_count"][k])))
            continue
        s, best = tweedie.best_split(hists, n, G, H, cols, subset, c["params"])
        good, dist, words = tweedie.judge(tweedie.program_split(t, k, cols), s, hists, best,
                                          subset, rtol, cols)
        ties += " tie:" in words
        worst = max(worst, dist)
        finite = max(finite, dist if np.isfinite(dist) else 0.0)
        if not good:
            ok = False
            bad.append("node %d: %s" % (k, words))
    return ok, ("tree %d, nodes %d..%d (%d splits): worst distance %.3g (limit %g; %.3g "
                "among the splits the rules allow), %d ties; %d wrong%s" % (
                    i, nodes[0], nodes[-1], len(nodes), worst, rtol, finite, ties, len(bad),
                    (": " + "; ".join(bad[:3])) if bad else ""))
