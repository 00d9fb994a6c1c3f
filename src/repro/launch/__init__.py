# NOTE: dryrun is intentionally NOT imported here — it sets XLA_FLAGS at
# import time and must only ever be run as a standalone entry point.
from .mesh import make_local_mesh, make_production_mesh
