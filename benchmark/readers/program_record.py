"""A number from a record the program wrote about itself
(``lightgbm_tpu.obs.telemetry.record``): the first or the last record of a
name, the sum of some of its fields less the sum of others. ``None`` where
the program writes no such record, or one without these fields (a commit
from before the record existed): the metric is then left out.

The checks that run before the readers call ``predict``, never ``lgb.train``
or ``Dataset.construct``, so the records are the job's."""


def read(args, facts):
    try:
        from lightgbm_tpu.obs import telemetry
        found = telemetry.records(args["record"])
    except Exception:
        return None
    if not found:
        return None
    rec = found[0] if args.get("which") == "first" else found[-1]
    try:
        return (sum(float(rec[k]) for k in args["add"])
                - sum(float(rec[k]) for k in args.get("subtract", [])))
    except (KeyError, TypeError, ValueError):
        return None
