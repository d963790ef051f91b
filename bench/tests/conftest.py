"""Fixtures of the benchmark's own tests (run on the CPU)."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


@pytest.fixture()
def mesh_ctx():
    """The one-device host mesh the unsharded engine is driven under."""
    import jax
    from repro.launch.mesh import make_host_mesh
    with jax.set_mesh(make_host_mesh()):
        yield
