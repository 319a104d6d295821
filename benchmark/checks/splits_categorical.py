"""The first tree's first splits, in split order, against the plain search of
``reference/categorical_split.py``: for each node its rows, the column, the
left side (a threshold bin, or the SET of category values), the rows sent
left (all exact) and the gain (to ``gain_rtol``), from float64 histograms of
the node's own rows over every column.

Nodes are compared from the root on, in the order the program split them,
until both children of the root (where they were split) and ``min_many``
many-against-many categorical nodes have been compared, and at most
``max_nodes`` of them; a first tree whose first ``max_nodes`` splits hold
fewer such nodes fails the check (a child of the root that the leaf-wise
order reaches later than that is named in the detail and fails nothing).
The root's histogram is one pass over all rows, a child's comes from a
partitioned segment or by subtraction from the pool, and a
many-against-many node's left side is a prefix of the sorted bins: what
``root_split_binary`` and ``child_splits_binary`` cannot see.

A node's rows are those the RECORDED splits above it send there (by the
bins, as training partitions them), so a wrong split fails at its own node
and at every node under it by its rows.

Two candidates may tie inside the tolerance. Another split than the
reference's passes only where the reference's OWN gain for the program's
split is within ``gain_rtol`` of its best; the program's gain and left rows
are then held to the reference's for that split, and the detail says
``tie``."""
import numpy as np


def _columns(binned):
    cols = []
    for m in binned.bin_mappers:
        if int(m.bin_type) == 1:
            cols.append({"categories": np.asarray(m.categories, np.int64)})
        else:
            cols.append({"bounds": np.asarray(m.upper_bounds, np.float64)})
    return cols


def run(args, c):
    from reference import categorical_split as ref
    rtol = float(args["gain_rtol"])
    need, most = int(args.get("min_many", 2)), int(args.get("max_nodes", 16))
    used = list(c["binned"].used_feature_indices)
    if used != list(range(c["X"].shape[1])):
        return False, "the data layer dropped columns (%s used): this check wants all of them" % used
    columns = _columns(c["binned"])
    t = c["trees"][0]
    splits = t["num_leaves"] - 1
    if splits < 1:
        return False, "the first tree has no split"
    label = c["label"]
    p = float(np.mean(label, dtype=np.float64))    # boost_from_average
    y = label.astype(np.float64)
    bins = ref.bins_of(c["X"], columns)

    def recorded(node):
        """-> (feature, left: threshold bin or sorted tuple of bins, go-left table)."""
        f = int(t["split_feature"][node])
        col = columns[f]
        table = np.zeros(ref.num_bins(col), bool)
        if t["is_categorical"][node]:
            cats = col["categories"]
            at = {int(v): b for b, v in enumerate(cats)}
            left = tuple(sorted(at[int(v)] for v in t["cat_sets"][node]))
            table[list(left)] = True
        else:
            left = int(np.searchsorted(col["bounds"][:-1], float(t["threshold"][node]), side="left"))
            table[:left + 1] = True
        return f, left, table

    def rows_of(child):
        return int(t["internal_count"][child] if child >= 0 else t["leaf_count"][~child])

    def show(f, left):
        if isinstance(left, tuple):     # category values, the first few of a long set
            values = sorted(int(columns[f]["categories"][b]) for b in left)
            return "column %d set of %d %s%s" % (f, len(values), values[:6], "..." if len(values) > 6 else "")
        return "column %d bin %d" % (f, left)

    node_of = np.zeros(len(y), np.int32)          # the node each row waits at; < 0: past the compared ones
    root_kids = {int(k) for k in (t["left_child"][0], t["right_child"][0]) if k >= 0}
    ok, many, worst, said, seen = True, 0, 0.0, [], set()
    for node in range(min(splits, most)):
        if many >= need and root_kids <= seen:
            break
        rows = None if node == 0 else np.flatnonzero(node_of == node)
        n = len(y) if rows is None else len(rows)
        f, left, table = recorded(node)
        go_left = table[bins[f] if rows is None else bins[f][rows]]
        pleft, pgain = int(go_left.sum()), float(t["split_gain"][node])
        hists = ref.histograms(bins, rows, y, columns)
        sum_y = float(y.sum() if rows is None else y[rows].sum())
        search, best = ref.best_split(hists, n, sum_y, p, columns, c["params"])
        seen.add(node)
        name = "node %d (%d rows)" % (node, n)
        if n != int(t["internal_count"][node]) or pleft != rows_of(int(t["left_child"][node])):
            ok = False
            said.append("%s: recorded %d rows, %d left; its own split over the rows above sends %d, %d left" % (
                name, int(t["internal_count"][node]), rows_of(int(t["left_child"][node])), n, pleft))
        elif best is None:
            ok = False
            said.append("%s: program %s, the reference allows no split" % (name, show(f, left)))
        else:
            gain, lrows, tie = best["gain"], best["left_rows"], ""
            kind = best["kind"]
            if (f, left) != (best["feature"], best["left"]):
                mine = search.gain_of(columns[f], *hists[f], left)
                if not np.isfinite(mine[0]) or abs(mine[0] - gain) > rtol * abs(gain):
                    ok = False
                    said.append("%s: program %s (gain %.6g), reference %s (gain %.6g; its gain for the "
                                "program's split %.6g)" % (name, show(f, left), pgain,
                                                           show(best["feature"], best["left"]), gain, mine[0]))
                    kind = None
                else:
                    tie = " tie: the reference's best is %s, gain %.9g against %.9g here" % (
                        show(best["feature"], best["left"]), gain, mine[0])
                    gain, lrows = mine
                    kind = search.kind_of(columns[f])
            if kind is not None:
                dist = abs(gain - pgain) / abs(gain)
                worst = max(worst, dist)
                good = dist <= rtol and pleft == lrows
                ok = ok and good
                many += kind == ref.MANY_VS_MANY and good
                said.append("%s: %s %s, gain %.6g against %.6g (off %.3g), left rows %d against %d%s" % (
                    name, kind, show(f, left), pgain, gain, dist, pleft, lrows, tie))
        # send the node's rows on by the recorded split
        at = slice(None) if rows is None else rows
        node_of[at] = np.where(go_left, t["left_child"][node], t["right_child"][node])
    if many < need:
        ok = False
        said.append("%d many-against-many nodes compared among the first %d splits, want %d" % (
            many, min(splits, most), need))
    if not root_kids <= seen:       # no fault: the leaf-wise order got to them later
        said.append("of the root's children %s only %s are among the first %d splits" % (
            sorted(root_kids), sorted(root_kids & seen), most))
    return ok, "%s; worst gain distance %.3g (limit %g)" % ("; ".join(said), worst, rtol)
