import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Tests run on the single real CPU device (the 512-device override is
# for the mesh lowering in launch/dryrun.py only).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# No persistent compilation cache in tests (the launchers turn it on);
# the environment variable carries the choice into child processes.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
