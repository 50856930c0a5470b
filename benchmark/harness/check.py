"""`correct`: every verdict the service returned for a window request,
against the reference verdict of the same request.

A request asked for a bool gets the AND of its sets' reference verdicts;
one asked for per-set verdicts gets the vector.  Two numbers are
compared, each with the limit 0: requests whose verdict differs from the
reference (`wrong_verdicts`), and requests that never got a verdict nor
an error by the drain limit (`missing_verdicts`).  A request that failed
with an error (refused, host path, device fault) counts in `failed`
instead; a late verdict is late, not wrong.
"""

LIMITS = {"wrong_verdicts": 0, "missing_verdicts": 0}


def compare(records, reference, want_per_set):
    wrong = missing = compared = 0
    for rec in records:
        if rec.error is not None:
            continue
        if rec.done is None:
            missing += 1
            continue
        per_set = reference.request(rec.request)
        want = per_set if want_per_set else all(per_set)
        got = list(rec.verdict) if want_per_set else rec.verdict
        compared += 1
        if got != want:
            wrong += 1
    numbers = {"wrong_verdicts": wrong, "missing_verdicts": missing}
    ok = all(numbers[k] <= LIMITS[k] for k in LIMITS)
    return ok, numbers, compared


def lines(numbers):
    """`name value (limit L)` per compared number."""
    return [f"{k} {v} (limit {LIMITS[k]})" for k, v in numbers.items()]


def as_json(numbers):
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in numbers.items()}
