"""What is left of a reading the harness took itself (``setup_s``) once
every stretch of it that a record of the program names is taken off: each
entry of ``less`` is a ``program_record`` argument set (record, which, add,
subtract). ``None`` where one of them has nothing to read (a commit from
before that record existed): a remainder against fewer parts would be
another number under the same name, so the metric is then left out."""
from readers import program_record


def read(args, facts):
    whole = facts["values"].get(args["value"])
    parts = [program_record.read(part, facts) for part in args["less"]]
    if whole is None or any(p is None for p in parts):
        return None
    return whole - sum(parts)
