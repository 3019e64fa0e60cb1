"""Store tiers (core/store.py, core/memtier.py): mean per iteration of
the executor's seconds in nodes it loaded."""


def read(run):
    its = run["iterations"]
    if not its:
        return None
    return sum(sum(t for n, t in i["node_seconds"].items()
                   if i["node_states"].get(n) == "load")
               for i in its) / len(its)
