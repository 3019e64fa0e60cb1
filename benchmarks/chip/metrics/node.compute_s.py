"""Executor and node programs (core/executor.py, train/steps.py): mean
per iteration of the executor's seconds in nodes it computed (each
node's own device time included: the executor blocks on its result)."""


def read(run):
    its = run["iterations"]
    if not its:
        return None
    return sum(sum(t for n, t in i["node_seconds"].items()
                   if i["node_states"].get(n) == "compute")
               for i in its) / len(its)
