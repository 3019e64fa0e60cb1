# Launch layer: production mesh builders, the multi-pod dry-run driver,
# and the train/serve entry points. NOTE: dryrun must be executed as
# ``python -m repro.launch.dryrun`` (its main() force-sets 512 host devices
# before the first backend use); importing does NOT touch device state.
from . import mesh, shapes

__all__ = ["mesh", "shapes"]
