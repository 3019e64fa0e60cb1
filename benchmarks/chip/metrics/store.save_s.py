"""Store tiers, materialization (core/store.py, core/omp.py): mean per
iteration of the seconds spent saving values."""


def read(run):
    its = run["iterations"]
    if not its:
        return None
    return sum(i["mat_seconds"] for i in its) / len(its)
