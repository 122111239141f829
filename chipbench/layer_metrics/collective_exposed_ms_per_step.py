"""The part of the collective time during which no other operation ran
on that chip, per train step."""


def read(record):
    t = record.get("trace") or {}
    if "collective_exposed_s" not in t or record["counters"]["chips"] < 2:
        return None
    return 1e3 * t["collective_exposed_s"] / t["steps"]
