"""Relation scans one job's batch executes after shared-scan fusion
(``BatchStats.n_scan_steps``, a count the planner fixes at compile time)."""


def read(run):
    return None if run.scan_steps is None else float(run.scan_steps)
