"""Bytes that any implementation of a job must move, from the
configuration's shapes alone (not from the plan, so fusing or removing
scans cannot make them stale)."""

from __future__ import annotations


def covar_job_bytes(cfg) -> float:
    """One covar batch: every column that some aggregate reads (features,
    the label, and the join keys), read once, plus the dense results
    written, all 4-byte values."""
    used = set(cfg.features_cont) | set(cfg.features_cat) | {cfg.label}
    read = 0
    for rel, attrs in cfg.attrs.items():
        joins = set()
        for a, b in cfg.edges:
            if rel in (a, b):
                joins |= set(cfg.attrs[a]) & set(cfg.attrs[b])
        cols = [x for x in attrs if x in used or x in joins]
        read += len(cols) * cfg.n_rows(rel)
    xs = len(cfg.features_cont) + 1
    out = 1 + xs + xs * (xs + 1) // 2
    cats = [cfg.domains[c] for c in cfg.features_cat]
    out += sum(d * (1 + xs) for d in cats)
    out += sum(a * b for i, a in enumerate(cats) for b in cats[i + 1:])
    return 4.0 * (read + out)
