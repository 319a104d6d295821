"""``tree_census`` for a job that bags its rows: every tree has more than
``more_than`` leaves and at most the configured ``num_leaves``, every leaf
holds at least ``min_data_in_leaf`` rows of the bag, and a tree's recorded
leaf counts sum to its iteration's rows in the bag, recomputed from
``bagging_seed`` by ``reference/tweedie.py`` ``bag``: an out-of-bag row
counts in no leaf."""


def run(args, c):
    from reference import tweedie
    most = int(c["params"]["num_leaves"])
    fewest = int(c["params"].get("min_data_in_leaf", 20))
    least = int(args["more_than"])
    leaves = [t["num_leaves"] for t in c["trees"]]
    smallest = min((int(t["leaf_count"].min()) for t in c["trees"]), default=None)
    wrong = [i for i, t in enumerate(c["trees"])
             if int(t["leaf_count"].sum()) != int(tweedie.bag(c["rows"], c["params"], i).sum())]
    ok = (bool(leaves) and least < min(leaves) and max(leaves) <= most and not wrong
          and smallest >= fewest)
    return ok, ("%d trees, leaves %s..%s (want more than %d, at most %d); smallest leaf %s rows "
                "(at least %d); trees whose leaf counts do not sum to their iteration's bag: %s"
                % (len(leaves), min(leaves, default=None), max(leaves, default=None),
                   least, most, smallest, fewest, wrong[:10] or "none"))
