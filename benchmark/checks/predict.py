"""The program's ``predict`` (raw score) against a plain numpy walk of the
model text on the first ``rows`` rows. The tolerance allows float32 sums of
some hundred leaf values in two orders, and nothing more.

The walk compares ``x <= threshold`` exactly, as training binned the rows. The
program's predictor holds each threshold rounded to the NEAREST float32, which
can lie above it, so a row whose value is that very float32 goes the other
way there (a defect of the program, PERF.md section 7; seen on the chip in two
seeds of six at 16.8M rows). Such rows are found by walking a second time
with thresholds rounded so; they may match either walk, they are counted, and
more than ``edge_rows_max`` of them fail the check. Every other row must
match the exact walk."""
import numpy as np


def run(args, c):
    from reference import model_text
    n = min(int(args["rows"]), c["rows"])
    X = c["X"][:n]
    exact = model_text.raw_score(c["header"], c["trees"], X)
    near = model_text.raw_score(c["header"], c["trees"], X, nearest32=True)
    theirs = np.asarray(c["booster"].predict(X, raw_score=True), np.float64)
    edge = exact != near
    err = np.abs(exact - theirs)
    err[edge] = np.minimum(err[edge], np.abs(near - theirs)[edge])
    worst, edges = float(err.max()), int(edge.sum())
    ok = worst <= float(args["tol"]) and edges <= int(args["edge_rows_max"])
    return ok, ("max |program predict - plain reference| %.3e on %d rows, %d trees (tol %g); "
                "%d rows sit on a threshold's nearest float32 (at most %d)" % (
                    worst, n, len(c["trees"]), args["tol"], edges, args["edge_rows_max"]))
