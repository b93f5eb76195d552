import pytest


@pytest.fixture(scope="session")
def smoke_mesh():
    from repro.launch.mesh import make_smoke_mesh
    return make_smoke_mesh()
