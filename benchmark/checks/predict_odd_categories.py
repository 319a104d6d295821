"""``predict`` on rows that hold what no training row held in a categorical
column: a NaN, negative values (whole and not), a category beyond every one
seen, one past 32 bits and one with a fraction. ``values`` are spliced, in
turn, into the ``columns`` of the first ``rows`` rows (row r takes
``values[r % len(values)]`` in column ``columns[(r // len(values)) %
len(columns)]``; every other cell stays what the table holds) and the
program's raw score is held to the plain walk of the model text, which
follows upstream's ``Tree::CategoricalDecision``: NaN right, truncated
toward zero, negative right, not in the node's set right.

``predict`` itself takes the table's own rows and cannot be told to splice;
this file is the same comparison on the doctored copy. No threshold's
nearest float32 is at stake in the spliced cells, and the rows are the ones
``predict`` has already held to both walks, so one walk is compared here."""
import numpy as np


def run(args, c):
    from reference import model_text
    n = min(int(args["rows"]), c["rows"])
    X = np.array(c["X"][:n], dtype=np.float32, copy=True)
    values = np.array([float(v) for v in args["values"]], np.float32)
    cols = np.array(args["columns"], np.int64)
    r = np.arange(n)
    X[r, cols[(r // len(values)) % len(cols)]] = values[r % len(values)]
    exact = model_text.raw_score(c["header"], c["trees"], X)
    near = model_text.raw_score(c["header"], c["trees"], X, nearest32=True)
    theirs = np.asarray(c["booster"].predict(X, raw_score=True), np.float64)
    err = np.minimum(np.abs(exact - theirs), np.abs(near - theirs))
    worst = float(err.max())
    at = int(err.argmax())
    return worst <= float(args["tol"]), (
        "max |program predict - plain reference| %.3e on %d rows with %s spliced into columns %s, "
        "%d trees (tol %g); worst row %d holds %r in column %d" % (
            worst, n, [float(v) for v in values], cols.tolist(), len(c["trees"]), args["tol"], at,
            float(values[at % len(values)]), int(cols[(at // len(values)) % len(cols)])))
