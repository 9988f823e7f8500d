"""The jobs' share of the HBM roofline: the bytes any implementation of
the covar batch must move (``bench/lib/costs.py``, from the configuration's
shapes alone) over the HBM peak (``bench/peaks.json``), against the device
busy time inside the traced jobs, in percent."""

from bench.lib.costs import covar_job_bytes


def read(run):
    t = run.trace
    if t is None or not t.n_spans("bench.job"):
        return None
    busy = max(t.busy_in("bench.job").values(), default=0.0)
    if busy <= 0:
        return None
    least = t.n_spans("bench.job") * covar_job_bytes(run.cfg) \
        / run.peak["hbm_bytes_per_s"]
    return 100.0 * least / busy
