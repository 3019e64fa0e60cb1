import os
import sys

# Tests must see exactly the real local device set (1 CPU) — the 512-device
# override belongs ONLY to launch/dryrun.py.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                "benchmarks"))

try:
    # Deep property-testing profile for the nightly tier-2 CI job
    # (--hypothesis-profile=ci-deep). hypothesis is a dev-only dependency
    # (requirements-dev.txt); local runs without it just use the inline
    # @settings on each test.
    from hypothesis import HealthCheck, settings

    settings.register_profile(
        "ci-deep", max_examples=60, deadline=None,
        suppress_health_check=[HealthCheck.function_scoped_fixture,
                               HealthCheck.too_slow])
except ImportError:
    pass

