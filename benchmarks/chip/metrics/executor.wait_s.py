"""Executor (core/executor.py): mean per iteration of the executor's wall
clock outside node runs and saves — chiefly waiting for a loaded value's
transfer to the device and for node results, which the executor's
per-node seconds leave out."""


def read(run):
    its = run["iterations"]
    if not its:
        return None
    return sum(i["total_seconds"] - i["mat_seconds"]
               - sum(i["node_seconds"].values()) for i in its) / len(its)
