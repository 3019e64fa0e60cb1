"""Planner (core/session.py, oep.py, omp.py): mean over the window's
iterations of the job's run time minus the executor's wall clock —
signatures, the reuse plan, leases and the cost model's bookkeeping."""


def read(run):
    its = run["iterations"]
    if not its:
        return None
    return sum(i["run_seconds"] - i["total_seconds"] for i in its) / len(its)
