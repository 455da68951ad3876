import pytest
from jax import set_mesh

from repro.launch.mesh import make_mesh


@pytest.fixture(scope="session")
def mesh():
    """1x1 mesh with production axis names (smoke tests see 1 device —
    the 512-device override belongs ONLY to launch/dryrun.py)."""
    return make_mesh((1, 1), ("data", "model"))


@pytest.fixture(scope="session")
def rules(mesh):
    from repro.sharding import rules_for
    return rules_for(mesh)


@pytest.fixture(autouse=True)
def _use_mesh(mesh):
    with set_mesh(mesh):
        yield
