"""Store tiers, metadata I/O (core/locking.py ``update_json``): mean per
window job of its ``meta.txn`` spans, each a locked read-modify-write of
a JSON file (costs, fleet ledger, index, bandwidth statistics)."""
import jobspans


def read(run):
    return jobspans.mean_per_job(
        run, lambda root, spans: jobspans.durations(spans, "meta.txn"))
