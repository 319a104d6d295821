"""``predict`` (raw score) given CSR rows, against the plain numpy walk of the
model text over the same rows made dense: the first ``rows`` rows go into the
program as they came (scipy CSR) and into the reference as a float32 array.
Tolerance and threshold-edge allowance are those of the ``predict`` check:
float32 sums of some hundred leaf values in two orders, and a row whose value
IS the nearest float32 of a threshold may match the walk with thresholds
rounded so."""
import numpy as np


def run(args, c):
    from reference import model_text
    n = min(int(args["rows"]), c["rows"])
    Xs = c["X"][:n]
    dense = np.ascontiguousarray(Xs.toarray(), dtype=np.float32)
    exact = model_text.raw_score(c["header"], c["trees"], dense)
    near = model_text.raw_score(c["header"], c["trees"], dense, nearest32=True)
    theirs = np.asarray(c["booster"].predict(Xs, raw_score=True), np.float64)
    edge = exact != near
    err = np.abs(exact - theirs)
    err[edge] = np.minimum(err[edge], np.abs(near - theirs)[edge])
    worst, edges = float(err.max()), int(edge.sum())
    ok = worst <= float(args["tol"]) and edges <= int(args["edge_rows_max"])
    return ok, ("max |program predict(CSR) - plain reference(dense)| %.3e on %d rows, %d trees "
                "(tol %g); %d rows sit on a threshold's nearest float32 (at most %d)" % (
                    worst, n, len(c["trees"]), args["tol"], edges, args["edge_rows_max"]))
