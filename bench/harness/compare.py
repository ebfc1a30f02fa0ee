"""The comparison that decides ``correct``.

A loop's ``check`` returns the numbers it compared with the plain
reference, each an error that is lower when better; the cell's limits
file (``bench/limits/<cell>.json``) holds each number's limit, with the
readings it was set from.  A run is correct when every number is at or
below its limit; a number that is missing or not a number fails.
"""
from __future__ import annotations

import math


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the limits' names."""
    out, ok = {}, True
    for name, entry in limits.items():
        limit = float(entry["limit"])
        value = numbers.get(name)
        good = (value is not None and not math.isnan(value)
                and value <= limit)
        ok = ok and good
        out[name] = {"value": None if value is None else float(value),
                     "limit": limit}
    return ok, out

