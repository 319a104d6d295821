"""Share of the HBM roofline of the row router's STREAM form (``ops/route.py``
while a block of every column fits VMEM): at the end of a tree it reads the
transposed binned matrix once, a byte a (row, device column), and writes the
leaf vector once, an int32 a row. Bytes a tree = N x (F + 4), N the rows at
the tree's root as the trees of the traced block recorded them; over the
peak, over the device time under ``lgbtpu/route`` (table assembly included:
it is the router's).

The bytes function lives here because ``arith.py`` may not be edited by the
PR that adds this metric; it belongs there, with the wide form's (one column
a round: N x (splits + 4)), which this reader does not know: a cell whose
router takes the wide form is not on this metric's list."""
import arith
from reference import model_text


def route_stream_bytes(rows, features, bin_bytes=1):
    return int(rows) * (features * bin_bytes + 4)


def read(args, facts):
    trace, trees = facts.get("trace"), facts.get("trace_trees")
    seconds = trace["by_scope"].get(args["scope"], 0.0) if trace else 0.0
    if not seconds or not trees or not facts["peaks"]:
        return None
    total = sum(route_stream_bytes(model_text.split_rows(t)[0][0], facts["features"])
                for t in trees if t["num_leaves"] > 1)
    return arith.roofline_pct(total, seconds, facts["peaks"]["hbm_bytes_per_s"])
