import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import random  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def seed_all():
    """Reseed the global host RNGs before every test.

    Test order must never change outcomes: anything that (even
    accidentally) reads ``np.random`` or ``random`` global state gets the
    same stream regardless of which tests ran before it. Audit note:
    the suite's tests draw through explicit ``np.random.default_rng`` /
    ``jax.random.PRNGKey`` generators (test_loadgen / test_scheduler use
    seeded LoadConfig streams); the one deliberate global reseed —
    test_serving's determinism-across-host-RNG test — overrides this
    per-test baseline, which is exactly its point.
    """
    np.random.seed(0)
    random.seed(0)


@pytest.fixture(scope="session")
def host_mesh():
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh()


@pytest.fixture()
def mesh_ctx(host_mesh):
    with jax.set_mesh(host_mesh):
        yield host_mesh
