"""What ``Dataset.construct`` made of the one-hot table is what the
configuration says a row carries on the device: ``columns`` device columns
(the configuration's ``shape.features``, from which the rooflines count their
bytes) of uint8, the widest ``column_bins`` wide, holding ``used_features``
features of which the widest has ``feature_bins`` bins. All four are static
shapes of the compiled block, so none may follow ``--seed``."""
import numpy as np


def run(args, c):
    b = c["binned"]
    got = {"columns": int(b.binned.shape[1]), "dtype": str(b.binned.dtype),
           "used_features": len(b.used_feature_indices),
           "column_bins": max(int(g.num_bins) for g in b.groups),
           "feature_bins": max(int(m.num_bins) for m in b.bin_mappers)}
    want = {"columns": int(args["columns"]), "dtype": "uint8",
            "used_features": int(args["used_features"]),
            "column_bins": int(args["column_bins"]),
            "feature_bins": int(args["feature_bins"])}
    sizes = sorted(len(g.feature_indices) for g in b.groups)
    return got == want and b.binned.dtype == np.uint8, \
        "constructed %s, configured %s; features per column %s" % (got, want, sizes)
