# launch: mesh construction, multi-pod dry-run, HLO analysis, drivers.
from . import mesh  # noqa: F401
