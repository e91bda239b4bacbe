# ``kernel`` (and with it Pallas, over a second to import) loads only
# where the kernel runs: see ops.py
from . import ops, ref  # noqa: F401
