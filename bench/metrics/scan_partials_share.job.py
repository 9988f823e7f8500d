"""Share of the jobs' device busy time spent forming each scan block's
partial sums (``segment_sum`` or the axis sum, with its zero fill): the
leaf ops under a ``partials`` scope (``core/lowering/xla.py``), by the op
names of the optimized program the window ran, in percent."""

from bench.lib import scopes


def read(run):
    t = run.trace
    if t is None or run.jobs is None or not t.devices:
        return None
    text = scopes.compiled_text(run.jobs.unit)
    if text is None:
        return None
    return scopes.share_under(t, scopes.hlo_ops(text), "partials")
