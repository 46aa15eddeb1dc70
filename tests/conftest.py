"""Test harness: force an 8-device virtual CPU mesh before any backend init.

The tests exercise multi-chip sharding logic on virtual CPU devices;
chip runs go through chip_smoke.py. XLA_FLAGS is read at CPU client
creation, so utils/backend.force_virtual_cpu must run here, before any
test touches a backend. JAX's persistent compile cache is switched off:
entry points the tests call arm it (utils/backend.arm_compile_cache),
and the suite must stay cache-free; the tests of the cache itself turn
it on around their own compiles.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from karpenter_tpu.utils.backend import force_virtual_cpu  # noqa: E402

if not os.environ.get("KARPENTER_TEST_REAL_BACKEND"):
    # KARPENTER_TEST_REAL_BACKEND=1 leaves the real backend in place so
    # the TPU-gated cases (tests/test_pallas_binpack.py's compiled-Mosaic
    # equality tests) run on a chip. Only with a narrow test selection:
    # the full suite assumes the 8-device CPU mesh.
    force_virtual_cpu(8)
jax.config.update("jax_enable_compilation_cache", False)


def pytest_collection_modifyitems(config, items):
    """Randomize test order (the reference's battletest runs randomized,
    Makefile:25-31; pytest-randomly is not in this image, so the shuffle
    lives here). Opt-in via KARPENTER_TEST_SHUFFLE=<seed> ('random' picks
    one); the seed is printed so any ordering failure is reproducible."""
    seed = os.environ.get("KARPENTER_TEST_SHUFFLE")
    if not seed:
        return
    import random

    if seed == "random":
        seed = str(random.SystemRandom().randrange(2**31))
    print(f"\n[conftest] shuffling test order with seed {seed}")
    random.Random(int(seed)).shuffle(items)
