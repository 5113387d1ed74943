import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))


def pytest_configure(config):
    # The runs here are small CPU programs: keep them out of the checkout's
    # persistent compilation cache.
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
