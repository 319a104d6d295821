"""Share of the HBM roofline of the kernels under one scope: the bytes the
algorithm needs for the traced trees (the benchmark's own function of shapes
and of the row counts the trees recorded) over the peak, over kernel time."""
import arith
from reference import model_text


def read(args, facts):
    trace, trees = facts.get("trace"), facts.get("trace_trees")
    seconds = trace["by_scope"].get(args["scope"], 0.0) if trace else 0.0
    if not seconds or not trees or not facts["peaks"]:
        return None
    total = 0
    for t in trees:
        parent, left, right = model_text.split_rows(t)
        if args["bytes"] == "partition":
            total += arith.partition_bytes(parent, facts["features"])
        elif args["bytes"] == "histogram":
            total += arith.histogram_bytes(left, right, facts["features"])
        else:
            raise KeyError(args["bytes"])
    return arith.roofline_pct(total, seconds, facts["peaks"]["hbm_bytes_per_s"])
