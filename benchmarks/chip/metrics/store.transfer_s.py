"""Store tiers, host<->device (core/store.py, core/executor.py): mean per
window job of the time covered by its ``store.to_device`` and
``store.to_host`` spans and by ``executor.block``, the wait for a loaded
value's copy to the device."""
import jobspans


def read(run):
    return jobspans.mean_per_job(
        run, lambda root, spans: jobspans.union(
            spans, "store.to_device", "store.to_host", "executor.block"))
