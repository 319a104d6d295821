"""Quality as a FLOOR only, for a count target: the RMSE of the predictions
e^s of a plain walk of the model text (``reference/model_text.py``) on the
first ``rows`` rows is at most ``rmse_max_share`` of the RMSE of the same
rows before the first tree (every score 0, every prediction 1). A share,
not a level: the level follows the seed's sales (5.48-5.74 before any tree
over five seeds), the share what the trees learned. The mean tweedie
deviance is reported beside it."""
import numpy as np


def run(args, c):
    from reference import model_text, tweedie
    n = min(int(args["rows"]), c["rows"])
    y = np.asarray(c["label"][:n], np.float64)
    raw = model_text.raw_score(c["header"], c["trees"], c["X"][:n])
    rho = float(c["params"].get("tweedie_variance_power", 1.5))
    value, untrained = tweedie.rmse(y, raw), tweedie.rmse(y, np.zeros(n))
    most = float(args["rmse_max_share"])
    return value <= most * untrained, (
        "rmse %.6f on the first %d rows after %d trees, %.4f of the %.6f before any tree "
        "(at most %s); tweedie deviance %.6f (%.6f before)" % (
            value, n, len(c["trees"]), value / untrained, untrained, most,
            tweedie.deviance(y, raw, rho), tweedie.deviance(y, 0.0 * raw, rho)))
