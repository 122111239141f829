"""Median over the window's report groups of (host time between the
completion of one group's last step and the next's / steps in a group);
a group ends with a loss fetch, so it is device time plus whatever the
host adds."""


def read(record):
    groups = record["counters"].get("group_step_s")
    if not groups:
        return None
    groups = sorted(groups)
    return 1e3 * groups[len(groups) // 2]
