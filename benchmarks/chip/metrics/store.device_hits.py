"""Store tiers, device tier (core/devtier.py): mean per window job of
its ``store.load`` spans with ``tier == "device"``, loads served by the
value still on the device. A program without a device tier reads
nothing."""
import jobspans


def read(run):
    try:
        import repro.core.devtier  # noqa: F401
    except ImportError:
        return None
    # mean_per_job reads nanoseconds as seconds: a count times 1e9 comes
    # back as the count.
    return jobspans.mean_per_job(
        run, lambda root, spans: 1e9 * sum(
            1 for s in spans if s.name == "store.load"
            and s.attrs.get("tier") == "device"))
