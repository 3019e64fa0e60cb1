"""Planner (core/session.py, oep.py, omp.py): mean per window job of the
self time of ``session.init`` (the per-job session), ``session.plan``
(signatures, slicing, costs, OEP, leases, purge) and ``session.record``
(the cost model's records and flush): what their child spans, chiefly
``meta.txn``, leave."""
import jobspans


def read(run):
    return jobspans.mean_per_job(
        run, lambda root, spans: jobspans.self_time(
            spans, "session.init", "session.plan", "session.record"))
