import os
import sys


def pytest_configure(config):
    # CI runs the fast tier first (-m "not slow"), then -m slow: a fast
    # failure short-circuits before any multi-device subprocess spawns.
    config.addinivalue_line(
        "markers",
        "slow: ICI-subprocess tests (forced multi-device meshes / driver "
        "e2e runs in child processes)")

# Tests must see exactly ONE device (the dry-run forces 512 in its own
# subprocess only). Keep XLA flags clean here.
os.environ.pop("XLA_FLAGS", None)
# Tests run on the CPU unless the caller asks otherwise: on a machine with
# a chip, a test process must not take it from the program that owns it.
# Set before JAX is first imported, and inherited by child processes.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Tests leave the persistent compile cache alone, in this process and in
# the entry points they start as children.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)
