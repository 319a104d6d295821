"""The bundling guarantee, held on every row: the plain reference bundles the
raw CSR under the program's group map and the stated conflict rule, and

- its bundled matrix equals the program's byte for byte (a row the program
  binned is a row the reference bins the same way);
- its count of conflict rows (two sub-features of one bundle both set) equals
  the count the program took while binning (``efb_conflict_rows`` of the
  constructed dataset; a program from before that count existed reports none,
  and only the reference's is judged);
- the share of such rows is at most ``share_max``."""
import numpy as np


def run(args, c):
    from reference import efb
    gm = efb.group_map_of(c["binned"])
    bundled, rows = efb.bundle_of(c["X"], gm)
    same = bundled.shape == c["binned"].binned.shape and np.array_equal(bundled, c["binned"].binned)
    theirs = getattr(c["binned"], "efb_conflict_rows", None)
    share = len(rows) / float(c["rows"])
    ok = same and theirs in (None, len(rows)) and share <= float(args["share_max"])
    return ok, ("bundled matrix %s == reference's: %s; conflict rows: reference %d, program %s; "
                "share %.3e (at most %g)" % (bundled.shape, same, len(rows), theirs, share,
                                             args["share_max"]))
