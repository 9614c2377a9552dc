"""The last line of a run, and the comparison's numbers beside their limits."""
import json
import sys


def checks_block(checks):
    """[(name, value, limit)] -> {"name": {"value": v, "limit": l}}; a
    number passes when it is finite and at most its limit."""
    return {n: {"value": v, "limit": l} for n, v, l in checks}


def _met(value, limit):
    return value is not None and value == value and value <= limit


def is_correct(checks):
    return bool(checks) and all(_met(v, l) for _, v, l in checks)


def emit(correct, attempted, failed, metrics, device, checks,
         breakdown=None):
    """Each number compared beside its limit as the last lines of standard
    error, then the result as the last line of standard output."""
    for name, value, limit in checks:
        print(f"check {name}: {value} limit {limit} "
              f"{'ok' if _met(value, limit) else 'NOT MET'}",
              file=sys.stderr)
    sys.stderr.flush()
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    line["checks"] = checks_block(checks)
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
