"""Executor (core/executor.py): mean per window job of the self time of
``executor.run`` and of each ``executor.decide`` (OMP's verdict on a
node): scheduling, waits and bookkeeping outside node runs, store calls
and metadata transactions."""
import jobspans


def read(run):
    return jobspans.mean_per_job(
        run, lambda root, spans: jobspans.self_time(
            spans, "executor.run", "executor.decide"))
